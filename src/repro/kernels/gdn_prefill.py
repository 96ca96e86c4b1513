"""Chunkwise GDN prefill kernel — state persistent in VMEM across chunks.

This is the *strongest* TPU analogue of the paper's persistent BRAM state:
one ``pallas_call`` processes the whole sequence for a (batch, v-head) pair,
carrying the (d_k, d_v) state in a VMEM scratch buffer across the sequential
chunk grid dimension.  State touches HBM exactly twice per sequence (initial
load, final store) — zero intermediate round-trips, vs. one round-trip per
chunk for a chunk-at-a-time GPU kernel.

Math (gated UT/WY transform, identical to ``repro.core.gdn.prefill_chunkwise``):
  (I + A) U = beta * (V - gamma_prev * (K @ S0)),   A strictly lower
  O  = scale * (gamma * (Q @ S0) + M @ U)
  S' = gamma_C * S0 + (exp(L_C - L) * K)^T @ U

The triangular inverse (I + A)^{-1} is computed *exactly* with the nilpotent
doubling identity  sum_i (-A)^i = prod_j (I + (-A)^{2^j})  — log2(C) MXU
matmuls, no sequential forward substitution (TPU-friendly; a row-by-row
solve would serialize on the VPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))                    # a @ b.T
_TN = (((0,), (0,)), ((), ()))                    # a.T @ b


def _nilpotent_inv_apply(A, rhs, chunk):
    """Compute (I + A)^{-1} @ rhs for strictly-lower-triangular A, exactly."""
    X = rhs
    M = -A
    steps = max(1, (chunk - 1).bit_length())       # 2^steps >= chunk
    for _ in range(steps):
        X = X + jnp.dot(M, X, preferred_element_type=jnp.float32)
        M = jnp.dot(M, M, preferred_element_type=jnp.float32)
    return X


def _kernel(vl_ref, q_ref, k_ref, v_ref, gc_ref, lr_ref, s0_ref, o_ref,
            s_out_ref, s_scr, *, chunk: int, scale: float, delta_rule: bool,
            n_chunks: int):
    bh, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    S0 = s_scr[...]                                   # (d_k, d_v) resident
    q = q_ref[0].astype(jnp.float32)                  # (C, d_k)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                  # (C, d_v)
    # ragged sequence: positions >= valid_len are padding.  The wrapper has
    # already zeroed their log-gates and betas; zeroing k/v here makes every
    # padded token an exact no-op on the state (g=1, rank-1 update 0) and on
    # every valid output row (their M/A columns vanish), so a fixed-size
    # masked chunk is provably the same program as a right-sized one.
    pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    vm = pos < vl_ref[bh]                             # (C, 1)
    k = jnp.where(vm, k, 0.0)
    v = jnp.where(vm, v, 0.0)
    gc = gc_ref[0, 0]                                 # (C, 3) columns
    L, L_prev, beta = gc[:, 0:1], gc[:, 1:2], gc[:, 2:3]
    L_row = lr_ref[0, 0]                              # (1, C)
    gamma = jnp.exp(L)                                # (C, 1)
    gamma_prev = jnp.exp(L_prev)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    qk = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    M = jnp.where(row >= col, jnp.exp(L - L_row) * qk, 0.0)   # incl. lower

    if delta_rule:
        kk = jax.lax.dot_general(k, k, _NT,
                                 preferred_element_type=jnp.float32)
        A = jnp.where(row > col, beta * jnp.exp(L_prev - L_row) * kk, 0.0)
        rhs = beta * (v - gamma_prev *
                      jnp.dot(k, S0, preferred_element_type=jnp.float32))
        U = _nilpotent_inv_apply(A, rhs, chunk)
    else:                                             # SSD / mamba2
        U = v

    O = scale * (gamma * jnp.dot(q, S0, preferred_element_type=jnp.float32)
                 + jnp.dot(M, U, preferred_element_type=jnp.float32))
    o_ref[0] = O.astype(o_ref.dtype)

    L_end = L_row[:, chunk - 1:chunk]                 # (1, 1)
    S_new = jnp.exp(L_end) * S0 + jax.lax.dot_general(
        jnp.exp(L_end - L) * k, U, _TN, preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    @pl.when(c == n_chunks - 1)
    def _():
        s_out_ref[0] = S_new.astype(s_out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "scale", "delta_rule", "interpret"))
def gdn_prefill_pallas(q, k, v, log_g, beta, S0, valid_len=None, *,
                       chunk: int = 64, scale: float | None = None,
                       delta_rule: bool = True, interpret: bool = False):
    """Chunkwise prefill over full sequences, state resident in VMEM.

    q, k : (BH, T, d_k) with BH = batch * h_v (q/k pre-grouped per v-head by
           the caller index map — see ops.gdn_prefill for the GVA mapping)
    v    : (BH, T, d_v);  log_g, beta: (BH, T);  S0: (BH, d_k, d_v)
    valid_len : optional (BH,) int32 — per-sequence count of real tokens;
           positions >= valid_len are padding, masked so the final state and
           the valid output rows are exactly those of an unpadded sequence
           (rows past valid_len are garbage — ignore them).
    Returns O: (BH, T, d_v), S_final: (BH, d_k, d_v).

    The per-token gate terms are small (BH, T) arrays, so the wrapper
    prepares them in XLA: the in-chunk cumulative log-gate L, its exclusive
    form L - log_g and beta, laid out as (BH, n_chunks, C, 3) columns plus L
    as a (BH, n_chunks, 1, C) row.  Every block's last two dims then equal
    the array's (the TPU tiling rule), and the kernel needs no in-VMEM
    cumsum or transpose.  ``valid_len`` rides in SMEM (scalar prefetch).
    """
    BH, T, d_k = q.shape
    d_v = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0
    n_chunks = T // chunk
    if scale is None:
        scale = (1.0 / (d_k ** 0.5)) if delta_rule else 1.0

    if valid_len is None:
        vl = jnp.full((BH,), T, jnp.int32)
    else:
        vl = valid_len.reshape(BH).astype(jnp.int32)
    real = jnp.arange(T)[None, :] < vl[:, None]       # (BH, T)
    lg = jnp.where(real, log_g.astype(jnp.float32), 0.0)
    bt = jnp.where(real, beta.astype(jnp.float32), 0.0)
    lg = lg.reshape(BH, n_chunks, chunk)
    L = jnp.cumsum(lg, axis=-1)
    gate_cols = jnp.stack([L, L - lg, bt.reshape(lg.shape)], axis=-1)
    L_row = L[:, :, None, :]

    kern = functools.partial(_kernel, chunk=chunk, scale=scale,
                             delta_rule=delta_rule, n_chunks=n_chunks)
    out_shape = [
        jax.ShapeDtypeStruct((BH, T, d_v), v.dtype),
        jax.ShapeDtypeStruct((BH, d_k, d_v), S0.dtype),
    ]
    in_specs = [
        pl.BlockSpec((1, chunk, d_k), lambda b, c, vl: (b, c, 0)),   # q
        pl.BlockSpec((1, chunk, d_k), lambda b, c, vl: (b, c, 0)),   # k
        pl.BlockSpec((1, chunk, d_v), lambda b, c, vl: (b, c, 0)),   # v
        pl.BlockSpec((1, 1, chunk, 3), lambda b, c, vl: (b, c, 0, 0)),
        pl.BlockSpec((1, 1, 1, chunk), lambda b, c, vl: (b, c, 0, 0)),
        pl.BlockSpec((1, d_k, d_v), lambda b, c, vl: (b, 0, 0)),     # S0
    ]
    out_specs = [
        pl.BlockSpec((1, chunk, d_v), lambda b, c, vl: (b, c, 0)),
        pl.BlockSpec((1, d_k, d_v), lambda b, c, vl: (b, 0, 0)),
    ]
    O, S_fin = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_chunks),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((d_k, d_v), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY)),
        interpret=interpret,
        name=f"gdn_prefill_c{chunk}",
    )(vl, q, k, v, gate_cols, L_row, S0)
    return O, S_fin
