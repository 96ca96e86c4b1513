"""Gated DeltaNet mixer layer — the paper's primitive as a model layer.

Projects the residual stream to q/k (h_k heads) and v (h_v = R*h_k heads,
Grouped Value Attention), computes the per-head gates from token-dependent
inputs (paper Eqs. 5-6), L2-normalizes q/k (delta-rule stability), and runs:

  * train / prefill: chunkwise-parallel gated delta rule
    (pure-JAX `core.gdn.gdn_prefill` for the differentiable path,
     Pallas `kernels.ops.gdn_prefill` with VMEM-resident state for serving)
  * decode: the fused one-read-one-write persistent-state step
    (pure-JAX fused Alg. 2, or the Pallas `gdn_decode` kernel on TPU)

State cache: GDNState(S (B, Hv, d_k, d_v) fp32, conv carries none — the
paper's layer has no conv).

Qwen3-Next's GDN layer (``qnext_gdn_*``, the ``qnext_gdn`` mixer kind)
shares the projections' shapes and the core, and adds what the
published layer has (HF ``qwen3_next``):

  * a causal depthwise conv of width 4, no bias, then SiLU, over the
    q|k|v channels (2 Hk d_k + Hv d_v), before the L2 norm of q and k;
    its carry of the last 3 positions is cached (``GDNConvState``);
  * the gate log g = -exp(A_log) softplus(a + dt_bias)
    (``core.gdn.qnext_log_gate``) and the decayed-read recurrence
    (``decayed_read=True``);
  * a z projection and a gated RMSNorm per value head,
    rmsnorm(o) w silu(z), before the output projection.

It runs the XLA core only: the Pallas kernels implement Algorithm 1.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import gdn as gdn_core
from repro.models import layers


class GDNState(NamedTuple):
    S: jax.Array          # (B, Hv, d_k, d_v) fp32 — the persistent state


class GDNConvState(NamedTuple):
    """Qwen3-Next's GDN cache: the state and the short conv's carry."""
    S: jax.Array          # (B, Hv, d_k, d_v) fp32
    conv: jax.Array       # (B, conv_width - 1, 2 Hk d_k + Hv d_v)


def init_gdn(key, d_model, n_k_heads, n_v_heads, head_dim,
             dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    s = d_model ** -0.5
    hv, hk, hd = n_v_heads, n_k_heads, head_dim
    return {
        "wq": (jax.random.normal(ks[0], (d_model, hk, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, hk, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, hv, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (hv, hd, d_model))
               * ((hv * hd) ** -0.5)).astype(dtype),
        "w_alpha": (jax.random.normal(ks[4], (d_model, hv)) * s).astype(dtype),
        "w_beta": (jax.random.normal(ks[5], (d_model, hv)) * s).astype(dtype),
        # per-head learned gate parameters (paper Eq. 5)
        "A_log": jnp.zeros((hv,), jnp.float32),
        "dt_bias": jnp.full((hv,), 0.5, jnp.float32),
    }


def _l2norm(x, eps=1e-6):
    n = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), -1,
                         keepdims=True) + eps)
    return (x.astype(jnp.float32) / n).astype(x.dtype)


def _proj(p, x):
    """x: (B, T, d) -> q,k (B,T,Hk,hd), v (B,T,Hv,hd), log_g/beta (B,T,Hv)."""
    q = _l2norm(jnp.einsum("btd,dhk->bthk", x, p["wq"]).astype(x.dtype))
    k = _l2norm(jnp.einsum("btd,dhk->bthk", x, p["wk"]).astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"]).astype(x.dtype)
    alpha = jnp.einsum("btd,dh->bth", x, p["w_alpha"]).astype(jnp.float32)
    b = jnp.einsum("btd,dh->bth", x, p["w_beta"]).astype(jnp.float32)
    log_g = gdn_core.log_gate(alpha, p["A_log"], p["dt_bias"])
    beta = jax.nn.sigmoid(b)
    return q, k, v, log_g, beta


def gdn_train(p, x, *, chunk=64):
    """Full-sequence gated delta rule (differentiable chunkwise path)."""
    B, T, _ = x.shape
    hv = p["wv"].shape[1]
    hd = p["wv"].shape[2]
    q, k, v, log_g, beta = _proj(p, x)
    S0 = jnp.zeros((B, hv, q.shape[-1], hd), jnp.float32)
    O, _ = gdn_core.gdn_prefill(q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32), log_g, beta, S0,
                                chunk=chunk)
    O = O.astype(x.dtype)
    return jnp.einsum("bthk,hkd->btd", O, p["wo"]).astype(x.dtype)


def mask_ragged_inputs(valid_len, k, v, log_g, beta):
    """Zero the kernel inputs at padded positions (>= ``valid_len``).

    A padded token with k = v = beta = 0 and log_g = 0 (gate 1) is an exact
    no-op on the recurrent state and contributes nothing to any valid
    output row, so a fixed-size chunk with a ragged tail computes the same
    state/output as the unpadded sequence (outputs at padded rows are
    garbage — callers ignore them).  ``valid_len``: scalar int32, or a
    (B,) vector for per-row raggedness (the batched multi-prompt staging
    path) — a scalar broadcasts to every row bitwise-identically.
    """
    vl = jnp.reshape(jnp.asarray(valid_len, jnp.int32), (-1, 1))
    vm = jnp.arange(k.shape[1])[None, :] < vl          # (B or 1, T)
    k = jnp.where(vm[:, :, None, None], k, jnp.zeros_like(k))
    v = jnp.where(vm[:, :, None, None], v, jnp.zeros_like(v))
    log_g = jnp.where(vm[:, :, None], log_g, jnp.zeros_like(log_g))
    beta = jnp.where(vm[:, :, None], beta, jnp.zeros_like(beta))
    return k, v, log_g, beta


def gdn_prefill(p, x, state: GDNState, *, chunk=64, use_pallas=False,
                valid_len=None):
    """Prompt processing; returns (out, final state).

    ``valid_len`` (optional scalar or per-row (B,) int32): positions
    >= valid_len of ``x`` are padding — masked so the returned state
    equals the unpadded run (the Pallas kernel masks internally; the XLA
    path pre-masks k/v/gates).
    """
    q, k, v, log_g, beta = _proj(p, x)
    if use_pallas:
        from repro.kernels import ops
        O, S = ops.gdn_prefill(q, k, v, log_g, beta, state.S, chunk=chunk,
                               valid_len=valid_len)
    else:
        if valid_len is not None:
            k, v, log_g, beta = mask_ragged_inputs(valid_len, k, v,
                                                   log_g, beta)
        O, S = gdn_core.gdn_prefill(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), log_g, beta,
            state.S.astype(jnp.float32), chunk=chunk)
        S = S.astype(state.S.dtype)
    O = O.astype(x.dtype)
    out = jnp.einsum("bthk,hkd->btd", O, p["wo"]).astype(x.dtype)
    return out, GDNState(S=S)


def gdn_decode(p, x_t, state: GDNState, *, use_pallas=False, head_block=8,
               fused=True):
    """One-token decode step: paper Alg. 2 (fused, default) or the Alg. 1
    three-pass reference (`fused=False`, the `gdn_naive` registry kind —
    XLA path only). x_t: (B, d_model)."""
    x = x_t[:, None, :]
    q, k, v, log_g, beta = _proj(p, x)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    g = jnp.exp(log_g[:, 0])
    beta = beta[:, 0]
    if use_pallas and fused:
        from repro.kernels import ops
        o, S = ops.gdn_decode(q, k, v, state.S, g, beta,
                              head_block=head_block)
    else:
        o, S = gdn_core.gdn_decode(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32),
                                   state.S.astype(jnp.float32), g, beta,
                                   fused=fused)
        o = o.astype(x_t.dtype)
        S = S.astype(state.S.dtype)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"]).astype(x_t.dtype)
    return out, GDNState(S=S)


# ---------------------------------------------------------------- Qwen3-Next

def init_qnext_gdn(key, d_model, n_k_heads, n_v_heads, head_dim, conv_width,
                   dtype=jnp.float32):
    """``init_gdn``'s projections (drawn from the same keys), plus the
    z projection, the bias-free conv over q|k|v and the gated norm."""
    p = init_gdn(key, d_model, n_k_heads, n_v_heads, head_dim, dtype)
    ks = jax.random.split(jax.random.fold_in(key, 1), 2)
    hv, hd = n_v_heads, head_dim
    p["wz"] = (jax.random.normal(ks[0], (d_model, hv, hd))
               * d_model ** -0.5).astype(dtype)
    p["conv"] = layers.init_conv1d(ks[1], (2 * n_k_heads + hv) * hd,
                                   conv_width, dtype, bias=False)
    p["dt_bias"] = jnp.ones((hv,), jnp.float32)
    p["norm"] = layers.init_rmsnorm(hd)
    return p


def _qnext_qkv(p, x):
    """x: (B, T, d) -> the conv's input, q|k|v flattened (B, T, C)."""
    B, T, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"]).astype(x.dtype)
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"]).astype(x.dtype)
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"]).astype(x.dtype)
    return jnp.concatenate([q.reshape(B, T, -1), k.reshape(B, T, -1),
                            v.reshape(B, T, -1)], -1)


def _qnext_heads(p, x, qkv):
    """The conv's activated output -> q, k (L2-normalized), v, and the
    gates, as ``_proj`` returns them."""
    hk, hd = p["wq"].shape[1], p["wq"].shape[2]
    hv = p["wv"].shape[1]
    lead = qkv.shape[:-1]
    q, k, v = jnp.split(qkv, [hk * hd, 2 * hk * hd], -1)
    q = _l2norm(q.reshape(*lead, hk, hd))
    k = _l2norm(k.reshape(*lead, hk, hd))
    v = v.reshape(*lead, hv, hd)
    a = jnp.einsum("btd,dh->bth", x, p["w_alpha"]).astype(jnp.float32)
    b = jnp.einsum("btd,dh->bth", x, p["w_beta"]).astype(jnp.float32)
    log_g = gdn_core.qnext_log_gate(a, p["A_log"], p["dt_bias"])
    return q, k, v, log_g, jax.nn.sigmoid(b)


def _qnext_out(p, x, O):
    """Gated RMSNorm per value head, then the output projection.
    O: (B, T, Hv, d_v) float32."""
    z = jnp.einsum("btd,dhk->bthk", x, p["wz"]).astype(x.dtype)
    o = layers.rmsnorm_fwd(p["norm"], O.astype(x.dtype))
    o = (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
         ).astype(x.dtype)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"]).astype(x.dtype)


def qnext_gdn_train(p, x, *, chunk=64):
    """Full sequence from an empty state (differentiable chunkwise path)."""
    B = x.shape[0]
    hv, hd = p["wv"].shape[1], p["wv"].shape[2]
    w, C = p["conv"]["w"].shape
    empty = GDNConvState(S=jnp.zeros((B, hv, p["wq"].shape[2], hd),
                                     jnp.float32),
                         conv=jnp.zeros((B, w - 1, C), x.dtype))
    return qnext_gdn_prefill(p, x, empty, chunk=chunk)[0]


def qnext_gdn_prefill(p, x, state: GDNConvState, *, chunk=64,
                      valid_len=None):
    """Prompt chunk resumed from ``state``; returns (out, new state).
    ``valid_len`` as in ``gdn_prefill``: the conv carry is taken at the
    valid boundary and padded positions are no-ops on S."""
    qkv, conv = layers.conv1d_prefill(p["conv"], _qnext_qkv(p, x),
                                      state.conv, valid_len)
    q, k, v, log_g, beta = _qnext_heads(p, x, qkv)
    if valid_len is not None:
        k, v, log_g, beta = mask_ragged_inputs(valid_len, k, v, log_g, beta)
    O, S = gdn_core.gdn_prefill(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        log_g, beta, state.S.astype(jnp.float32), chunk=chunk,
        decayed_read=True)
    return _qnext_out(p, x, O), GDNConvState(
        S=S.astype(state.S.dtype), conv=conv.astype(state.conv.dtype))


def qnext_gdn_decode(p, x_t, state: GDNConvState):
    """One-token step (fused, decayed read). x_t: (B, d_model)."""
    x = x_t[:, None, :]
    y, conv = layers.conv1d_decode(p["conv"], _qnext_qkv(p, x)[:, 0],
                                   state.conv)
    q, k, v, log_g, beta = _qnext_heads(p, x, layers.silu(y)[:, None])
    o, S = gdn_core.gdn_decode(
        q[:, 0].astype(jnp.float32), k[:, 0].astype(jnp.float32),
        v[:, 0].astype(jnp.float32), state.S.astype(jnp.float32),
        jnp.exp(log_g[:, 0]), beta[:, 0], decayed_read=True)
    return (_qnext_out(p, x, o[:, None])[:, 0],
            GDNConvState(S=S.astype(state.S.dtype), conv=conv))
