"""Gated DeltaNet layer: plain float32 reference and cost model.

The recurrence is the paper's Algorithm 1, one token at a time:
  r = S^T k;  S <- g S + k (beta (v - r))^T;  o = S^T q / sqrt(d_k)
with g = exp(-sigmoid(alpha) exp(A_log) softplus(dt_bias)),
beta = sigmoid(b), q and k L2-normalized, and each q/k head shared by
v_heads / k_heads consecutive value heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.numerics import F32, draw, ein, l2norm, mm


def init(key, d_model: int, m: dict, dtype) -> dict:
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    ks = jax.random.split(key, 7)
    s = d_model ** -0.5
    return {
        "wq": draw(ks[0], (d_model, hk, hd), s, dtype),
        "wk": draw(ks[1], (d_model, hk, hd), s, dtype),
        "wv": draw(ks[2], (d_model, hv, hd), s, dtype),
        "wo": draw(ks[3], (hv, hd, d_model), (hv * hd) ** -0.5, dtype),
        "w_alpha": draw(ks[4], (d_model, hv), s, dtype),
        "w_beta": draw(ks[5], (d_model, hv), s, dtype),
        "A_log": jnp.zeros((hv,), F32),
        "dt_bias": jnp.full((hv,), 0.5, F32),
    }


def forward(p, h, m: dict, mode: str):
    """h: (B, T, d) float32, positions 0..T-1 from an empty state."""
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    rep = hv // hk
    q = jnp.repeat(l2norm(mm("btd,dhk->bthk", h, p["wq"], mode)), rep, 2)
    k = jnp.repeat(l2norm(mm("btd,dhk->bthk", h, p["wk"], mode)), rep, 2)
    v = mm("btd,dhk->bthk", h, p["wv"], mode)
    alpha = mm("btd,dh->bth", h, p["w_alpha"], mode)
    beta = jax.nn.sigmoid(mm("btd,dh->bth", h, p["w_beta"], mode))
    g = jnp.exp(-jax.nn.sigmoid(alpha) * jnp.exp(p["A_log"])
                * jax.nn.softplus(p["dt_bias"]))
    scale = hd ** -0.5

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        r = ein("bhk,bhkv->bhv", k_t, S)
        dv = b_t[..., None] * (v_t - r)
        S = g_t[..., None, None] * S + k_t[..., :, None] * dv[..., None, :]
        return S, scale * ein("bhk,bhkv->bhv", q_t, S)

    B = h.shape[0]
    S0 = jnp.zeros((B, hv, hd, hd), F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, S0, xs)
    return mm("bthk,hkd->btd", jnp.moveaxis(o, 0, 1), p["wo"], mode)


def params(d_model: int, m: dict) -> int:
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    return d_model * hd * (2 * hk + hv) + hv * hd * d_model \
        + 2 * d_model * hv + 2 * hv


def matmul_params(d_model: int, m: dict) -> int:
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    return d_model * hd * (2 * hk + hv) + hv * hd * d_model + 2 * d_model * hv


def token_flops(m: dict, ctx: int) -> float:
    """State-update FLOPs per token beyond the weight matmuls (one read
    pass for S^T k and S^T q, one rank-1 write, the delta and output
    corrections)."""
    d = m["head_dim"]
    return m["v_heads"] * (7.0 * d * d + 8.0 * d)


def state_bytes(m: dict, state_itemsize: int, act_itemsize: int) -> int:
    d = m["head_dim"]
    return m["v_heads"] * d * d * state_itemsize


def kv_bytes_per_position(m: dict, act_itemsize: int) -> int:
    return 0
