"""Qwen3-Next's Gated DeltaNet layer: plain float32 reference and cost
model (HF ``qwen3_next``, the published Gated DeltaNet, arXiv:2412.06464).

  q|k|v = silu(conv(h Wq | h Wk | h Wv))   causal depthwise, width
                                           ``conv_width``, no bias
  q, k L2-normalized; each q/k head shared by v_heads / k_heads
  consecutive value heads;  beta = sigmoid(h Wb)
  g = exp(-exp(A_log) softplus(h Wa + dt_bias))
  per token:  S <- g S;  r = S^T k;  S <- S + k (beta (v - r))^T;
              o = S^T q / sqrt(d_k)
  out = (rmsnorm(o) w * silu(h Wz)) Wo     the norm per value head
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.numerics import F32, draw, ein, l2norm, mm, rmsnorm


def _channels(m: dict) -> int:
    return (2 * m["k_heads"] + m["v_heads"]) * m["head_dim"]


def init(key, d_model: int, m: dict, dtype) -> dict:
    hk, hv, hd, w = m["k_heads"], m["v_heads"], m["head_dim"], \
        m["conv_width"]
    ks = jax.random.split(key, 7)
    kz, kc = jax.random.split(jax.random.fold_in(key, 1), 2)
    s = d_model ** -0.5
    return {
        "wq": draw(ks[0], (d_model, hk, hd), s, dtype),
        "wk": draw(ks[1], (d_model, hk, hd), s, dtype),
        "wv": draw(ks[2], (d_model, hv, hd), s, dtype),
        "wo": draw(ks[3], (hv, hd, d_model), (hv * hd) ** -0.5, dtype),
        "w_a": draw(ks[4], (d_model, hv), s, dtype),
        "w_b": draw(ks[5], (d_model, hv), s, dtype),
        "wz": draw(kz, (d_model, hv, hd), s, dtype),
        "conv": draw(kc, (w, _channels(m)), w ** -0.5, dtype),
        "A_log": jnp.zeros((hv,), F32),
        "dt_bias": jnp.ones((hv,), F32),
        "norm": jnp.ones((hd,), F32),
    }


def forward(p, h, m: dict, mode: str):
    """h: (B, T, d) float32, positions 0..T-1 from an empty state."""
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    B, T, _ = h.shape
    rep = hv // hk
    qkv = jnp.concatenate(
        [mm("btd,dhk->bthk", h, p[w], mode).reshape(B, T, -1)
         for w in ("wq", "wk", "wv")], -1)
    width = p["conv"].shape[0]
    pad = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(pad[:, i:i + T] * p["conv"][i]
                          for i in range(width)))
    q, k, v = jnp.split(qkv, [hk * hd, 2 * hk * hd], -1)
    q = jnp.repeat(l2norm(q.reshape(B, T, hk, hd)), rep, 2)
    k = jnp.repeat(l2norm(k.reshape(B, T, hk, hd)), rep, 2)
    v = v.reshape(B, T, hv, hd)
    a = mm("btd,dh->bth", h, p["w_a"], mode)
    beta = jax.nn.sigmoid(mm("btd,dh->bth", h, p["w_b"], mode))
    g = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))
    scale = hd ** -0.5

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = g_t[..., None, None] * S
        r = ein("bhk,bhkv->bhv", k_t, S)
        S = S + k_t[..., :, None] * (b_t[..., None] * (v_t - r))[..., None, :]
        return S, scale * ein("bhk,bhkv->bhv", q_t, S)

    S0 = jnp.zeros((B, hv, hd, hd), F32)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, S0, xs)
    z = mm("btd,dhk->bthk", h, p["wz"], mode)
    o = rmsnorm(jnp.moveaxis(o, 0, 1), p["norm"], m["norm_eps"]) \
        * jax.nn.silu(z)
    return mm("bthk,hkd->btd", o, p["wo"], mode)


def matmul_params(d_model: int, m: dict) -> int:
    hk, hv, hd = m["k_heads"], m["v_heads"], m["head_dim"]
    return (d_model * hd * (2 * hk + hv)          # q, k, v
            + 2 * d_model * hv * hd               # z, out
            + 2 * d_model * hv)                   # a, b


def params(d_model: int, m: dict) -> int:
    return (matmul_params(d_model, m) + m["conv_width"] * _channels(m)
            + 2 * m["v_heads"] + m["head_dim"])


def token_flops(m: dict, ctx: int) -> float:
    """State-update FLOPs per token beyond the weight matmuls (decay,
    the S^T k and S^T q reads, the rank-1 write, the delta and output
    corrections), as for the paper's layer."""
    d = m["head_dim"]
    return m["v_heads"] * (7.0 * d * d + 8.0 * d)


def state_bytes(m: dict, state_itemsize: int, act_itemsize: int) -> int:
    """The state S plus the conv carry (the last width - 1 inputs)."""
    d = m["head_dim"]
    return (m["v_heads"] * d * d * state_itemsize
            + (m["conv_width"] - 1) * _channels(m) * act_itemsize)


def kv_bytes_per_position(m: dict, act_itemsize: int) -> int:
    return 0
