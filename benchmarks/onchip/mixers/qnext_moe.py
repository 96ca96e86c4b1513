"""Qwen3-Next's sparse MoE, one chip's share: plain float32 reference and
cost model (HF ``qwen3_next``).

  p = softmax(h Wr) over all ``experts``;  the top ``top_k`` of p,
  renormalized to sum to 1 (``norm_topk_prob``), weigh their experts;
  out = sum over the ``held`` experts first..first+held-1 that a token
        picked of  w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
        + sigmoid(h Wsg) (silu(h Wsg_s) * (h Wsu)) Wsd     (shared expert)

The experts this chip does not hold add nothing here, as in the program:
their part of the result lies on the other chips of the expert-parallel
stage.  Expert e's weights are drawn from its own key (e folded into the
projection's key).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.numerics import draw, mm


def init(key, d_model: int, m: dict, dtype) -> dict:
    E, n, f, fs = m["experts"], m["held"], m["expert_width"], \
        m["shared_width"]
    ks = jax.random.split(key, 6)
    s = d_model ** -0.5
    experts = m["first"] + jnp.arange(n)

    def held(k, shape, scale):
        return jax.vmap(lambda e: draw(jax.random.fold_in(k, e), shape,
                                       scale, dtype))(experts)

    k1, k2, k3 = jax.random.split(ks[4], 3)
    return {
        "router": draw(ks[0], (d_model, E), s, dtype),
        "w_gate": held(ks[1], (d_model, f), s),
        "w_up": held(ks[2], (d_model, f), s),
        "w_down": held(ks[3], (f, d_model), f ** -0.5),
        "s_gate": draw(k1, (d_model, fs), s, dtype),
        "s_up": draw(k2, (d_model, fs), s, dtype),
        "s_down": draw(k3, (fs, d_model), fs ** -0.5, dtype),
        "s_weight": draw(ks[5], (d_model, 1), s, dtype),
    }


def forward(p, h, m: dict, mode: str):
    n = m["held"]
    probs = jax.nn.softmax(mm("btd,de->bte", h, p["router"], mode), -1)
    vals, idx = jax.lax.top_k(probs, m["top_k"])
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    local = idx - m["first"]
    w = jnp.sum(jax.nn.one_hot(jnp.where((local >= 0) & (local < n),
                                         local, n), n + 1)[..., :n]
                * vals[..., None], -2)                          # (B, T, n)
    a = (jax.nn.silu(mm("btd,edf->btef", h, p["w_gate"], mode))
         * mm("btd,edf->btef", h, p["w_up"], mode))
    y = mm("btef,efd->btd", a * w[..., None], p["w_down"], mode)
    shared = mm("btf,fd->btd",
                jax.nn.silu(mm("btd,df->btf", h, p["s_gate"], mode))
                * mm("btd,df->btf", h, p["s_up"], mode), p["s_down"], mode)
    return y + jax.nn.sigmoid(mm("btd,do->bto", h, p["s_weight"], mode)) \
        * shared


def params(d_model: int, m: dict) -> int:
    """The held experts, the router, the shared expert and its gate."""
    return (3 * d_model * m["expert_width"] * m["held"]
            + d_model * m["experts"] + 3 * d_model * m["shared_width"]
            + d_model)


def matmul_params(d_model: int, m: dict) -> float:
    """Weights that multiply a token here, on average: the router, the
    shared expert and its gate, and of the held experts the expected
    top_k x held / experts a token picks."""
    return (d_model * m["experts"] + 3 * d_model * m["shared_width"]
            + d_model + m["top_k"] * m["held"] / m["experts"]
            * 3 * d_model * m["expert_width"])


def token_flops(m: dict, ctx: int) -> float:
    return 0.0


def state_bytes(m: dict, state_itemsize: int, act_itemsize: int) -> int:
    return 0


def kv_bytes_per_position(m: dict, act_itemsize: int) -> int:
    return 0
