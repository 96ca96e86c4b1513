"""Mamba-2 (SSD) layer: plain float32 reference and cost model.

  z = x Wz;  u = silu(conv(x Wx));  B = silu(conv(x WB));  C = silu(conv(x WC))
  dt = softplus(x Wdt + dt_bias);  g = exp(-exp(A_log) dt)
  per head h:  S <- g S + B (dt u_h)^T;  y_h = S^T C + D_h u_h
  out = (rmsnorm(y) * silu(z)) Wout
with causal depthwise convolutions of width ``conv_width`` (their own
filters for u, B and C, zero bias) and one B/C shared by all heads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.numerics import F32, draw, ein, mm, rmsnorm


def init(key, d_model: int, m: dict, dtype) -> dict:
    di, hd, ds, w = m["d_inner"], m["headdim"], m["d_state"], m["conv_width"]
    nh = di // hd
    ks = jax.random.split(key, 9)
    s = d_model ** -0.5

    def conv(k, c):
        return {"w": draw(k, (w, c), w ** -0.5, dtype),
                "b": jnp.zeros((c,), F32)}

    return {
        "w_z": draw(ks[0], (d_model, di), s, dtype),
        "w_x": draw(ks[1], (d_model, di), s, dtype),
        "w_B": draw(ks[2], (d_model, ds), s, dtype),
        "w_C": draw(ks[3], (d_model, ds), s, dtype),
        "w_dt": draw(ks[4], (d_model, nh), s, dtype),
        "conv_x": conv(ks[5], di),
        "conv_B": conv(ks[6], ds),
        "conv_C": conv(ks[7], ds),
        "A_log": jnp.zeros((nh,), F32),
        "dt_bias": jnp.full((nh,), 0.5, F32),
        "D": jnp.ones((nh,), F32),
        "norm": jnp.ones((di,), F32),
        "out_proj": draw(ks[8], (di, d_model), di ** -0.5, dtype),
    }


def _conv(c, u):
    w = c["w"].shape[0]
    T = u.shape[1]
    pad = jnp.pad(u, ((0, 0), (w - 1, 0), (0, 0)))
    return sum(pad[:, i:i + T] * c["w"][i] for i in range(w)) + c["b"]


def forward(p, h, m: dict, mode: str):
    hd = m["headdim"]
    nh = m["d_inner"] // hd
    z = mm("btd,de->bte", h, p["w_z"], mode)
    u = jax.nn.silu(_conv(p["conv_x"], mm("btd,de->bte", h, p["w_x"], mode)))
    Bm = jax.nn.silu(_conv(p["conv_B"], mm("btd,de->bte", h, p["w_B"], mode)))
    Cm = jax.nn.silu(_conv(p["conv_C"], mm("btd,de->bte", h, p["w_C"], mode)))
    dt = jax.nn.softplus(mm("btd,de->bte", h, p["w_dt"], mode) + p["dt_bias"])
    g = jnp.exp(-jnp.exp(p["A_log"]) * dt)                    # (B, T, nh)
    uh = u.reshape(u.shape[:2] + (nh, hd))
    v = uh * dt[..., None]

    def step(S, xs):
        b_t, c_t, v_t, g_t = xs
        S = g_t[..., None, None] * S + b_t[:, None, :, None] * v_t[..., None, :]
        return S, ein("bhsv,bs->bhv", S, c_t)

    B = h.shape[0]
    S0 = jnp.zeros((B, nh, m["d_state"], hd), F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (Bm, Cm, v, g))
    _, y = jax.lax.scan(step, S0, xs)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * uh
    y = rmsnorm(y.reshape(u.shape), p["norm"], 1e-6) * jax.nn.silu(z)
    return mm("bte,ed->btd", y, p["out_proj"], mode)


def params(d_model: int, m: dict) -> int:
    di, hd, ds, w = m["d_inner"], m["headdim"], m["d_state"], m["conv_width"]
    nh = di // hd
    return matmul_params(d_model, m) + (w + 1) * (di + 2 * ds) + 3 * nh + di


def matmul_params(d_model: int, m: dict) -> int:
    di, hd, ds = m["d_inner"], m["headdim"], m["d_state"]
    return d_model * (2 * di + 2 * ds + di // hd) + di * d_model


def token_flops(m: dict, ctx: int) -> float:
    """State-update FLOPs per token beyond the weight matmuls."""
    nh = m["d_inner"] // m["headdim"]
    return nh * 5.0 * m["d_state"] * m["headdim"]


def state_bytes(m: dict, state_itemsize: int, act_itemsize: int) -> int:
    """SSD state plus the conv carries (the last width - 1 inputs)."""
    di, hd, ds, w = m["d_inner"], m["headdim"], m["d_state"], m["conv_width"]
    return ((di // hd) * ds * hd * state_itemsize
            + (w - 1) * (di + 2 * ds) * act_itemsize)


def kv_bytes_per_position(m: dict, act_itemsize: int) -> int:
    return 0
