"""Flash-decode GQA attention kernel (for the attention archs / hybrid layers).

Embodies the same one-pass discipline the paper applies to recurrent state,
applied to the KV cache: each decode step makes exactly one streaming pass
over K and V with online softmax, accumulating in VMEM scratch.  Grid is
(batch, kv_heads, kv_blocks) with the kv-block dimension sequential; the
group of Hg = Hq // Hkv query heads sharing a kv head is processed together
(GQA analogue of the paper's GVA paired-head datapath).

Supports a per-sequence valid ``length`` (for batched serving with ragged
contexts) and an optional sliding ``window`` (SWA archs: h2o-danube,
mixtral, recurrentgemma local attention) via position masking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_t: int, n_blocks: int, scale: float, window: int | None):
    b, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (Hg, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (Bt, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (Bt, d)
    length = len_ref[b]                               # SMEM scalar

    s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (Hg, Bt)
    pos = t * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # occupancy mask owned by the kernel: slot t holds a token iff
    # t < length (linear phase) or always (rolling phase, length > T) —
    # callers pass the raw token count, the kernel clamps to the buffer
    total = n_blocks * block_t
    valid = pos < jnp.minimum(length, total)
    if window is not None:
        # window masking must compare *absolute positions*: slot `pos`
        # holds the largest p < length with p ≡ pos (mod T), which is
        # `pos` itself only in the linear phase — in the rolling phase
        # the newest tokens wrap onto the lowest slots
        p_abs = (length - 1) - jnp.mod(length - 1 - pos, total)
        valid = jnp.logical_and(valid, p_abs >= length - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                               # (Hg, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(t == n_blocks - 1)
    def _():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_t", "scale", "window", "interpret"))
def attn_decode_pallas(q, k_cache, v_cache, length, *, block_t: int = 256,
                       scale: float | None = None, window: int | None = None,
                       interpret: bool = False):
    """One-token GQA attention against a KV cache.

    q        : (B, Hq, d)
    k_cache  : (B, Hkv, T, d);  v_cache same
    length   : (B,) int32 — valid tokens seen so far per sequence (may
               exceed T for rolling caches: the kernel clamps the
               occupancy mask to the buffer itself, so callers never
               pre-clamp; with masked ragged prefill upstream this is
               the count of *real* tokens, padding excluded)
    Returns o: (B, Hq, d).
    """
    B, Hq, d = q.shape
    _, Hkv, T, _ = k_cache.shape
    Hg = Hq // Hkv
    assert Hq % Hkv == 0
    bt = min(block_t, T)
    assert T % bt == 0
    n_blocks = T // bt
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    qg = q.reshape(B, Hkv, Hg, d)

    kern = functools.partial(_kernel, block_t=bt, n_blocks=n_blocks,
                             scale=scale, window=window)
    grid = (B, Hkv, n_blocks)
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                     # length -> SMEM
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, Hg, d), lambda b, h, t, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bt, d), lambda b, h, t, ln: (b, h, t, 0)),
                pl.BlockSpec((1, 1, bt, d), lambda b, h, t, ln: (b, h, t, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, Hg, d),
                                   lambda b, h, t, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hg, 1), jnp.float32),
                pltpu.VMEM((Hg, 1), jnp.float32),
                pltpu.VMEM((Hg, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Hg, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name=f"attn_decode_bt{bt}",
    )(length.reshape(B).astype(jnp.int32), qg, k_cache, v_cache)
    return o.reshape(B, Hq, d)
