"""Fused persistent-state GDN decode kernel (paper Alg. 2, TPU-native).

One `pallas_call` per token performs, for every value-head:

  read pass : one traversal of the (d_k, d_v) state block in VMEM computing
              BOTH the retrieval r = S^T k and the partial output S^T q as a
              single stacked (2, d_k) @ (d_k, d_v) MXU matmul
  write pass: S <- g*S + k (beta (v - r))^T  written back through the same
              VMEM block, aliased in-place onto the input state buffer
              (``input_output_aliases``) — the TPU analogue of the paper's
              persistent BRAM state: the state is touched exactly once each
              way per token and never copied.

Grid: (batch, h_v / head_block).  ``head_block`` is the direct analogue of
the paper's H_iter design knob (v-heads per dataflow iteration) and is swept
in the benchmarks.  GVA: q/k blocks hold the head_block // n_rep shared heads
of the block (one head when head_block < n_rep) and are broadcast to their
value-head group inside the kernel (the paper's paired-head datapath).

Block layout: every per-head row is its own trailing ``(1, d)`` tile and every
per-head scalar (gate, beta) its own ``(1, 1)`` tile — q/k as (B, Hk, 1, d_k),
v/o as (B, Hv, 1, d_v), g/beta as (B, Hv, 1, 1).  The last two dims of each
block then equal the array's, which the TPU lowering requires whatever
head_block is; the reshapes in the wrapper are free.

``delta_rule=False`` degenerates to the Mamba-2 / SSD decode update
(S <- g*S + k v^T, o = S^T q) and is used by the mamba2 architecture.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_OUTER = (((0,), (0,)), ((), ()))                 # contract the unit dim:
                                                  # (1, m) x (1, n) -> (m, n)


def _kernel(q_ref, k_ref, v_ref, s_ref, g_ref, b_ref, o_ref, s_out_ref, *,
            head_block: int, n_rep: int, scale: float, delta_rule: bool):
    for h in range(head_block):                    # fully unrolled head loop
        hk = h // n_rep                            # shared GVA q/k head
        S = s_ref[0, h].astype(jnp.float32)        # (d_k, d_v) — read pass
        kk = k_ref[0, hk].astype(jnp.float32)      # (1, d_k)
        qq = q_ref[0, hk].astype(jnp.float32)      # (1, d_k)
        g = g_ref[0, h].astype(jnp.float32)        # (1, 1)
        kq = jnp.concatenate([kk, qq], axis=0)     # (2, d_k)
        rr = jnp.dot(kq, S, preferred_element_type=jnp.float32)  # (2, d_v)
        r, sq = rr[0:1], rr[1:2]                   # (1, d_v) each
        vv = v_ref[0, h].astype(jnp.float32)       # (1, d_v)
        if delta_rule:
            dv = b_ref[0, h].astype(jnp.float32) * (vv - r)   # delta correction
        else:                                      # SSD / mamba2 path
            dv = vv
        alpha = jnp.sum(kk * qq, axis=1, keepdims=True)       # q^T k, (1, 1)
        o = scale * (g * sq + alpha * dv)          # fused output correction
        S_new = g * S + jax.lax.dot_general(
            kk, dv, _OUTER, preferred_element_type=jnp.float32)
        o_ref[0, h] = o.astype(o_ref.dtype)
        s_out_ref[0, h] = S_new.astype(s_out_ref.dtype)  # write pass (aliased)


@functools.partial(
    jax.jit,
    static_argnames=("head_block", "scale", "delta_rule", "interpret"))
def gdn_decode_pallas(q, k, v, S, g, beta, *, head_block: int = 8,
                      scale: float | None = None, delta_rule: bool = True,
                      interpret: bool = False):
    """Fused GDN decode step.

    q, k : (B, Hk, d_k)       v: (B, Hv, d_v)
    S    : (B, Hv, d_k, d_v)  g, beta: (B, Hv)
    Returns (o, S_new) with o: (B, Hv, d_v); S_new aliases S's buffer.
    """
    B, Hk, d_k = q.shape
    _, Hv, d_v = v.shape
    n_rep = Hv // Hk
    assert Hv % Hk == 0
    hb = min(head_block, Hv)
    assert Hv % hb == 0 and (hb % n_rep == 0 or n_rep % hb == 0), \
        (Hv, hb, n_rep)
    hbk = max(1, hb // n_rep)                      # q/k heads per block
    if scale is None:
        scale = (1.0 / (d_k ** 0.5)) if delta_rule else 1.0

    grid = (B, Hv // hb)
    kern = functools.partial(_kernel, head_block=hb, n_rep=n_rep,
                             scale=scale, delta_rule=delta_rule)
    out_shape = [
        jax.ShapeDtypeStruct((B, Hv, 1, d_v), v.dtype),
        jax.ShapeDtypeStruct(S.shape, S.dtype),
    ]
    qk_map = lambda b, i: (b, (i * hb) // (n_rep * hbk), 0, 0)
    row_map = lambda b, i: (b, i, 0, 0)
    in_specs = [
        pl.BlockSpec((1, hbk, 1, d_k), qk_map),            # q
        pl.BlockSpec((1, hbk, 1, d_k), qk_map),            # k
        pl.BlockSpec((1, hb, 1, d_v), row_map),            # v
        pl.BlockSpec((1, hb, d_k, d_v), row_map),          # S
        pl.BlockSpec((1, hb, 1, 1), row_map),              # g
        pl.BlockSpec((1, hb, 1, 1), row_map),              # beta
    ]
    out_specs = [
        pl.BlockSpec((1, hb, 1, d_v), row_map),
        pl.BlockSpec((1, hb, d_k, d_v), row_map),
    ]
    o, S_new = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={3: 1},               # S updated in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL)),
        interpret=interpret,
        name=f"gdn_decode_hb{hb}",
    )(q[:, :, None], k[:, :, None], v[:, :, None], S,
      g[:, :, None, None], beta[:, :, None, None])
    return o.reshape(B, Hv, d_v), S_new
