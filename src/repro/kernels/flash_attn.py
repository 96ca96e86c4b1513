"""Flash-attention training kernel (fwd + bwd) — Pallas TPU.

The train-cell roofline is dominated by attention score traffic: any XLA
formulation materializes O(B·H·T²) bytes of scores/probabilities to HBM
(measured in EXPERIMENTS.md §Perf). This kernel applies the paper's
persistent-on-chip discipline to attention: score blocks live ONLY in VMEM;
HBM traffic is O(B·H·T·d) (q, k, v, o + per-row (m, l) statistics).

Forward: grid (B·Hkv, n_q_blocks, n_kv_blocks), kv sequential; online
softmax accumulators in VMEM scratch; emits o and the logsumexp residuals.
Backward: two kernels — dq (kv sequential per q block) and dk/dv
(q sequential per kv block) — recomputing p = exp(s − lse) blockwise from
the saved statistics, never materializing a (T, T) tensor.

Causal always; optional sliding window (SWA archs). GQA: the G = Hq/Hkv
query heads sharing a kv head are processed in one grid cell (paper's
paired-head datapath, as in gdn_decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mask(qi, kj, bq, bk, window, valid=None):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = q_pos >= k_pos
    if window is not None:
        m = jnp.logical_and(m, (q_pos - k_pos) < window)
    if valid is not None:
        # ragged sequence: key positions >= valid_len are padding and must
        # not contribute to any score row (the matching mask to the
        # gdn_prefill kernel's k/v/gate zeroing)
        m = jnp.logical_and(m, k_pos < valid)
    return m


# ----------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                m_scr, l_scr, acc_scr, *, G, bq, bk, n_kv, scale, window,
                vl_ref=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = k_ref[0].astype(jnp.float32)             # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    mask = _mask(qi, kj, bq, bk, window,
                 None if vl_ref is None else vl_ref[0, 0])
    for g in range(G):                           # unrolled GQA group loop
        q = q_ref[0, g].astype(jnp.float32)      # (bq, hd)
        s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[g][:, None]               # (bq, 1)
        l_prev = l_scr[g][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[g] = corr * acc_scr[g] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[g] = m_new[:, 0]
        l_scr[g] = l_new[:, 0]

    @pl.when(kj == n_kv - 1)
    def _():
        for g in range(G):
            l = jnp.maximum(l_scr[g][:, None], 1e-30)
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            m_ref[0, g] = m_scr[g]
            l_ref[0, g] = l_scr[g]


def _fwd_kernel_ragged(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                       m_scr, l_scr, acc_scr, **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr,
                acc_scr, vl_ref=vl_ref, **kw)


def _len_spec(valid_len, BH, in_specs, args):
    """Prepend the (BH, 1) per-sequence valid-length input (ragged calls)."""
    spec = pl.BlockSpec((1, 1), lambda b, i, j: (b, 0))
    return ([spec] + in_specs,
            (valid_len.reshape(BH, 1).astype(jnp.int32),) + args)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "scale",
                                             "window", "interpret"))
def flash_fwd(q, k, v, valid_len=None, *, block_q=512, block_kv=512,
              scale=None, window=None, interpret=False):
    """q: (BH, G, T, hd); k, v: (BH, T, hd) -> o, m, l.

    ``valid_len`` (optional, (BH,) int32): key positions >= valid_len are
    padding and masked out of every score row; output rows at padded query
    positions are garbage (callers must ignore / zero their cotangents).
    """
    BH, G, T, hd = q.shape
    bq, bk = min(block_q, T), min(block_kv, T)
    assert T % bq == 0 and T % bk == 0
    nq, nkv = T // bq, T // bk
    if scale is None:
        scale = hd ** -0.5
    kern = functools.partial(
        _fwd_kernel if valid_len is None else _fwd_kernel_ragged,
        G=G, bq=bq, bk=bk, n_kv=nkv, scale=scale, window=window)
    in_specs = [
        pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
    ]
    args = (q, k, v)
    if valid_len is not None:
        in_specs, args = _len_spec(valid_len, BH, in_specs, args)
    o, m, l = pl.pallas_call(
        kern,
        grid=(BH, nq, nkv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, G, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, G, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, G, T), jnp.float32),
            jax.ShapeDtypeStruct((BH, G, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name=f"flash_fwd_bq{bq}",
    )(*args)
    return o, m, l


# ----------------------------------------------------------------- backward

def _p_block(q, k, m, l, qi, kj, bq, bk, scale, window, valid=None):
    s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = jnp.where(_mask(qi, kj, bq, bk, window, valid), s, NEG_INF)
    return jnp.exp(s - m[:, None]) / jnp.maximum(l, 1e-30)[:, None]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dlt_ref, dq_ref,
               dq_scr, *, G, bq, bk, n_kv, scale, window, vl_ref=None):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    valid = None if vl_ref is None else vl_ref[0, 0]
    for g in range(G):
        q = q_ref[0, g].astype(jnp.float32)
        do = do_ref[0, g].astype(jnp.float32)
        p = _p_block(q, k, m_ref[0, g], l_ref[0, g], qi, kj, bq, bk,
                     scale, window, valid)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dlt_ref[0, g][:, None])
        dq_scr[g] += scale * jnp.dot(ds, k,
                                     preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dlt_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, G, bq, bk, n_q, scale,
                window, vl_ref=None):
    kj, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    valid = None if vl_ref is None else vl_ref[0, 0]
    for g in range(G):
        q = q_ref[0, g].astype(jnp.float32)
        do = do_ref[0, g].astype(jnp.float32)
        p = _p_block(q, k, m_ref[0, g], l_ref[0, g], qi, kj, bq, bk,
                     scale, window, valid)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dlt_ref[0, g][:, None])
        dk_scr[...] += scale * jnp.dot(ds.T, q,
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel_ragged(vl_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                      dlt_ref, dq_ref, dq_scr, **kw):
    _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dlt_ref, dq_ref,
               dq_scr, vl_ref=vl_ref, **kw)


def _dkv_kernel_ragged(vl_ref, q_ref, k_ref, v_ref, do_ref, m_ref, l_ref,
                       dlt_ref, dk_ref, dv_ref, dk_scr, dv_scr, **kw):
    _dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dlt_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, vl_ref=vl_ref, **kw)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "scale",
                                             "window", "interpret"))
def flash_bwd(q, k, v, o, m, l, do, valid_len=None, *, block_q=512,
              block_kv=512, scale=None, window=None, interpret=False):
    BH, G, T, hd = q.shape
    bq, bk = min(block_q, T), min(block_kv, T)
    nq, nkv = T // bq, T // bk
    if scale is None:
        scale = hd ** -0.5
    # delta = rowsum(do * o) — cheap, pure XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)

    dq_specs = [
        pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        pl.BlockSpec((1, G, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, G, bq), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, G, bq), lambda b, i, j: (b, 0, i)),
    ]
    dq_args = (q, k, v, do, m, l, delta)
    if valid_len is not None:
        dq_specs, dq_args = _len_spec(valid_len, BH, dq_specs, dq_args)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel if valid_len is None else _dq_kernel_ragged,
            G=G, bq=bq, bk=bk, n_kv=nkv, scale=scale, window=window),
        grid=(BH, nq, nkv),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, G, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((G, bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_args)

    dkv_specs = [
        pl.BlockSpec((1, G, bq, hd), lambda b, j, i: (b, 0, i, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, G, bq, hd), lambda b, j, i: (b, 0, i, 0)),
        pl.BlockSpec((1, G, bq), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, G, bq), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, G, bq), lambda b, j, i: (b, 0, i)),
    ]
    dkv_args = (q, k, v, do, m, l, delta)
    if valid_len is not None:
        dkv_specs, dkv_args = _len_spec(valid_len, BH, dkv_specs, dkv_args)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel if valid_len is None else _dkv_kernel_ragged,
            G=G, bq=bq, bk=bk, n_q=nq, scale=scale, window=window),
        grid=(BH, nkv, nq),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.PARALLEL,
                                 pltpu.ARBITRARY)),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_args)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ----------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, block_q=512, block_kv=512, window=None,
                    interpret=False, valid_len=None):
    """Causal (optionally windowed) GQA flash attention.

    q: (B, T, Hq, hd); k, v: (B, T, Hkv, hd). Returns (B, T, Hq, hd).
    Scores never touch HBM; residuals are o + (m, l) per row.

    ``valid_len`` (optional, (B,) int32) marks ragged sequences padded to
    T: key positions >= valid_len are masked out of every score row (and
    out of the dk/dv accumulations), so padded rows never leak into valid
    outputs or gradients.  Output rows and dq rows at padded query
    positions are garbage — mask them (and their loss terms) upstream.
    """
    o, _, _ = _flash_fwd_shaped(q, k, v, valid_len, block_q, block_kv,
                                window, interpret)
    return o


def _reshape_in(q, k, v):
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hkv, G, T, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    return qh, kh, vh, (B, T, Hq, Hkv, hd)


def _len_per_bh(valid_len, Hkv):
    """(B,) per-sequence lengths -> (B * Hkv,) per-grid-row lengths."""
    if valid_len is None:
        return None
    return jnp.repeat(valid_len.astype(jnp.int32), Hkv, axis=0)


def _flash_fwd_shaped(q, k, v, valid_len, block_q, block_kv, window,
                      interpret):
    qh, kh, vh, (B, T, Hq, Hkv, hd) = _reshape_in(q, k, v)
    o, m, l = flash_fwd(qh, kh, vh, _len_per_bh(valid_len, Hkv),
                        block_q=block_q, block_kv=block_kv,
                        window=window, interpret=interpret)
    o_out = o.reshape(B, Hkv, Hq // Hkv, T, hd).reshape(
        B, Hq, T, hd).transpose(0, 2, 1, 3)
    return o_out, m, l


def _fwd_rule(q, k, v, block_q, block_kv, window, interpret,
              valid_len=None):
    o, m, l = _flash_fwd_shaped(q, k, v, valid_len, block_q, block_kv,
                                window, interpret)
    return o, (q, k, v, o, m, l, valid_len)


def _bwd_rule(block_q, block_kv, window, interpret, res, do):
    q, k, v, o, m, l, valid_len = res
    qh, kh, vh, (B, T, Hq, Hkv, hd) = _reshape_in(q, k, v)
    G = Hq // Hkv
    oh = o.transpose(0, 2, 1, 3).reshape(B * Hkv, G, T, hd)
    doh = do.transpose(0, 2, 1, 3).reshape(B * Hkv, G, T, hd)
    dq, dk, dv = flash_bwd(qh, kh, vh, oh, m, l, doh,
                           _len_per_bh(valid_len, Hkv), block_q=block_q,
                           block_kv=block_kv, window=window,
                           interpret=interpret)
    dq_out = dq.reshape(B, Hq, T, hd).transpose(0, 2, 1, 3)
    dk_out = dk.reshape(B, Hkv, T, hd).transpose(0, 2, 1, 3)
    dv_out = dv.reshape(B, Hkv, T, hd).transpose(0, 2, 1, 3)
    if valid_len is None:
        return dq_out, dk_out, dv_out, None
    # int32 primal: the only well-typed cotangent is float0 zeros
    return dq_out, dk_out, dv_out, np.zeros(valid_len.shape,
                                            jax.dtypes.float0)


flash_attention.defvjp(_fwd_rule, _bwd_rule)
