"""Qwen3-Next's gated attention: plain float32 reference and cost model
(HF ``qwen3_next``).

Causal grouped-query softmax attention; q and k RMS-normalized per head,
then a rotary embedding on the first ``rotary_dim`` dims of each head
(halves of that part rotated, frequencies of a ``rotary_dim``-wide head);
q head j reads kv head j // (heads / kv_heads); each head's output is
multiplied by sigmoid(h Wg) before the output projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.numerics import F32, draw, ein, mm, rmsnorm


def init(key, d_model: int, m: dict, dtype) -> dict:
    hq, hkv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": draw(ks[0], (d_model, hq, hd), s, dtype),
        "wk": draw(ks[1], (d_model, hkv, hd), s, dtype),
        "wv": draw(ks[2], (d_model, hkv, hd), s, dtype),
        "wo": draw(ks[3], (hq, hd, d_model), (hq * hd) ** -0.5, dtype),
        "wg": draw(jax.random.fold_in(key, 1), (d_model, hq, hd), s, dtype),
    }


def _rope(x, theta, r):
    T = x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs           # (T, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x[..., :r], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


def forward(p, h, m: dict, mode: str):
    hq, hkv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    T, eps = h.shape[1], m["norm_eps"]
    q = rmsnorm(mm("btd,dhk->bthk", h, p["wq"], mode), 1.0, eps)
    k = rmsnorm(mm("btd,dhk->bthk", h, p["wk"], mode), 1.0, eps)
    q = _rope(q, m["rope_theta"], m["rotary_dim"])
    k = _rope(k, m["rope_theta"], m["rotary_dim"])
    v = mm("btd,dhk->bthk", h, p["wv"], mode)
    k = jnp.repeat(k, hq // hkv, 2)
    v = jnp.repeat(v, hq // hkv, 2)
    s = ein("bthk,bshk->bhts", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = ein("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
    o = o * jax.nn.sigmoid(mm("btd,dhk->bthk", h, p["wg"], mode))
    return mm("bthk,hkd->btd", o, p["wo"], mode)


def matmul_params(d_model: int, m: dict) -> int:
    hq, hkv, hd = m["heads"], m["kv_heads"], m["head_dim"]
    return d_model * hd * (2 * hq + 2 * hkv) + hq * hd * d_model


def params(d_model: int, m: dict) -> int:
    return matmul_params(d_model, m) + 2 * m["head_dim"]      # q/k norms


def token_flops(m: dict, ctx: int) -> float:
    """q k^T and p v over ``ctx`` cached positions."""
    return 2.0 * m["heads"] * m["head_dim"] * ctx * 2


def state_bytes(m: dict, state_itemsize: int, act_itemsize: int) -> int:
    return 0


def kv_bytes_per_position(m: dict, act_itemsize: int) -> int:
    return 2 * m["kv_heads"] * m["head_dim"] * act_itemsize
