"""Shared model layers: RMSNorm, RoPE, SwiGLU MLP, embeddings, causal conv.

Functional style: ``init_*`` returns a param pytree (nested dicts of arrays),
``*_fwd`` applies it.  All matmuls run in the activation dtype (bf16 on TPU)
with fp32 accumulation via ``preferred_element_type``; norms and softmax in
fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dot(x, w):
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


# ------------------------------------------------------------------ RMSNorm

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm_fwd(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


# ------------------------------------------------------------------ RoPE

def rope_freqs(head_dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta=10000.0, rotary_dim=None):
    """x: (..., T, H, hd) or (..., H, hd) with positions broadcastable.

    ``rotary_dim`` (partial rotary): only the first ``rotary_dim`` dims
    of each head rotate, with the frequencies of a ``rotary_dim``-wide
    head; the rest pass through."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        rot = apply_rope(x[..., :rotary_dim], positions, theta)
        return jnp.concatenate([rot, x[..., rotary_dim:]], -1)
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., T, hd/2)
    cos = jnp.cos(ang)[..., None, :]                  # (..., T, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ MLP

def init_mlp(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    return {
        "wi_gate": (jax.random.normal(k1, (d_model, d_ff)) * s_in).astype(dtype),
        "wi_up": (jax.random.normal(k2, (d_model, d_ff)) * s_in).astype(dtype),
        "wo": (jax.random.normal(k3, (d_ff, d_model)) * s_ff).astype(dtype),
    }


def mlp_fwd(p, x):
    g = dot(x, p["wi_gate"])
    u = dot(x, p["wi_up"])
    return dot(jax.nn.silu(g) * u, p["wo"])


# ------------------------------------------------------------------ Embedding

def init_embedding(key, vocab, d_model, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, d_model))
                      * (d_model ** -0.5)).astype(dtype)}


def embed_fwd(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def logits_fwd(p, x, table=None):
    """Project to vocab. table given => tied embeddings."""
    w = table if table is not None else p["table"]
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ causal conv1d
# (mamba2 / short-conv mixers; decode keeps a (width-1)-token cache)

def init_conv1d(key, channels, width, dtype=jnp.float32, bias=True):
    w = jax.random.normal(key, (width, channels)) * (width ** -0.5)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((channels,), dtype)
    return p


def silu(x):
    """SiLU in float32, returned in ``x``'s dtype."""
    return jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype)


def conv1d_fwd(p, x):
    """Causal depthwise conv over (B, T, C) (bias optional)."""
    width = p["w"].shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * p["w"][i] for i in range(width))
    return out + p["b"] if "b" in p else out


def conv1d_decode(p, x_t, cache):
    """One-step conv. x_t: (B, C); cache: (B, width-1, C). Returns (y, cache)."""
    width = p["w"].shape[0]
    full = jnp.concatenate([cache, x_t[:, None, :]], axis=1)  # (B, width, C)
    y = jnp.einsum("bwc,wc->bc", full.astype(jnp.float32),
                   p["w"].astype(jnp.float32)).astype(x_t.dtype)
    return (y + p["b"] if "b" in p else y), full[:, 1:, :]


def conv1d_prefill(p, u, cache, valid_len=None):
    """Causal conv seeded with the carry ``cache`` (B, width-1, C), then
    SiLU; returns (activated output (B, T, C), new carry).

    With ``valid_len`` set, the returned carry is the last ``w - 1``
    *valid* inputs (rows ``[valid_len, valid_len + w - 1)`` of
    cache‖u) — the carry serial decode would hold after the valid
    prefix, not the padded garbage at the block's end.  ``valid_len`` may
    be a per-row (B,) vector (the batched staging path): each row's carry
    is gathered at its own boundary; a scalar keeps the
    ``dynamic_slice`` path bitwise-unchanged.
    """
    T = u.shape[1]
    w = p["w"].shape[0]
    full = jnp.concatenate([cache.astype(u.dtype), u], axis=1)
    out = conv1d_fwd(p, full)[:, -T:, :]
    if valid_len is None:
        tail = full[:, -(w - 1):, :]
    else:
        vl = jnp.asarray(valid_len, jnp.int32)
        if vl.ndim == 0:
            tail = jax.lax.dynamic_slice_in_dim(full, vl, w - 1, axis=1)
        else:
            idx = vl[:, None] + jnp.arange(w - 1)[None, :]   # (B, w-1)
            tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return silu(out), tail


# ------------------------------------------------------------------ loss

def cross_entropy(logits, labels, z_loss=0.0):
    """logits: (..., V) fp32; labels: (...) int32. Mean over all positions."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss > 0.0:
        loss = loss + z_loss * lse ** 2
    return jnp.mean(loss)
