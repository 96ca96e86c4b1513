"""Mixture-of-Experts FFN (GShard/Switch-style capacity dispatch, EP-shardable).

Top-k softmax router with grouped one-hot dispatch: tokens are processed in
groups of ``group_size`` with a per-group expert capacity
C = group_size * k * cf / E.  Dispatch/combine einsum overhead relative to
expert FLOPs is E*C/(3*d_ff) = group_size*k*cf/(3*d_ff) — group_size is
chosen per-arch to keep this under ~25% (mixtral: 1024 -> 6%, arctic-480b:
1024 -> 18%).  GSPMD lowers expert parallelism to all-to-alls when the
expert dim of the weights is sharded on the `model` axis and tokens on
`data`.

Decode uses a dense all-expert einsum: at serving batch sizes every expert
is hit with near-certainty, so weight *traffic* (the roofline term that
dominates decode) is identical to a gather-based dispatch, with no dynamic
shapes.  Load-balancing auxiliary loss included for training.

Held experts (``held_*``, the ``qnext_moe`` mixer kind): an expert layer
that is told which experts it holds — ``n_held`` consecutive experts
from ``first`` of the router's ``n_experts`` — as one chip of an
expert-parallel deployment holds them.  The softmax router spans all
experts and picks ``top_k``, renormalized over the k (Qwen3-Next's
``norm_topk_prob``); the layer computes only its held experts' part of
the result, with no capacity and no dropped token: every held expert's
weights multiply every token, scaled by its combine weight (0 where the
token did not pick it).  A shared SwiGLU expert, gated by
sigmoid(x w_gate), is added on every chip alike.  Expert e's weights are
drawn from its own key, so any share draws the experts a whole layer
would hold at those indices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(key, d_model, d_ff, n_experts, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    sf = d_ff ** -0.5
    return {
        "router": (jax.random.normal(ks[0], (d_model, n_experts)) * s
                   ).astype(jnp.float32),
        "wi_gate": (jax.random.normal(ks[1], (n_experts, d_model, d_ff)) * s
                    ).astype(dtype),
        "wi_up": (jax.random.normal(ks[2], (n_experts, d_model, d_ff)) * s
                  ).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_experts, d_ff, d_model)) * sf
               ).astype(dtype),
    }


def moe_fwd(p, x, *, top_k=2, capacity_factor=1.25, group_size=1024):
    """x: (B, T, d) -> (y (B, T, d), aux_loss scalar)."""
    B, T, d = x.shape
    E = p["router"].shape[1]
    N = B * T
    S = min(group_size, N)
    assert N % S == 0, (N, S)
    G = N // S
    C = max(1, int(S * top_k * capacity_factor / E))

    xf = x.reshape(G, S, d)
    logits = jnp.einsum("gsd,de->gse", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)              # (G, S, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # ----- load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                         # (E,)
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # ----- queue position of each (token, slot) within its expert, per group
    onehot_i = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # (G, S, k, E)
    flat = onehot_i.reshape(G, S * top_k, E)
    pos = jnp.cumsum(flat, axis=1) - 1
    pos = jnp.sum(pos * flat, axis=-1).reshape(G, S, top_k)   # (G, S, k)
    keep = pos < C
    gate_kept = gate_vals * keep

    # dispatch mask folded over k: (G, S, E, C)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                            dtype=x.dtype)[..., :C]           # (G, S, k, C)
    disp = jnp.einsum("gske,gskc->gsec",
                      jax.nn.one_hot(idx, E, dtype=x.dtype), pos_oh)
    comb = jnp.einsum("gske,gskc,gsk->gsec",
                      jax.nn.one_hot(idx, E, dtype=x.dtype), pos_oh,
                      gate_kept.astype(x.dtype))

    xe = jnp.einsum("gsec,gsd->gecd", disp, xf)               # (G, E, C, d)
    h = jnp.einsum("gecd,edf->gecf", xe, p["wi_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("gecd,edf->gecf", xe, p["wi_up"],
                   preferred_element_type=jnp.float32)
    hh = (jax.nn.silu(h) * u).astype(x.dtype)
    ye = jnp.einsum("gecf,efd->gecd", hh, p["wo"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    y = jnp.einsum("gsec,gecd->gsd", comb, ye)
    return y.reshape(B, T, d), aux


def moe_decode(p, x_t, *, top_k=2):
    """Single-token-per-sequence MoE. x_t: (B, d)."""
    B, d = x_t.shape
    E = p["router"].shape[1]
    logits = jnp.dot(x_t.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, top_k)              # (B, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(idx, E, dtype=x_t.dtype)              # (B, k, E)
    w = jnp.einsum("bke,bk->be", oh, gate_vals.astype(x_t.dtype))
    h = jnp.einsum("bd,edf->bef", x_t, p["wi_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("bd,edf->bef", x_t, p["wi_up"],
                   preferred_element_type=jnp.float32)
    hh = (jax.nn.silu(h) * u).astype(x_t.dtype)
    ye = jnp.einsum("bef,efd->bed", hh, p["wo"],
                    preferred_element_type=jnp.float32).astype(x_t.dtype)
    return jnp.einsum("bed,be->bd", ye, w)


# ---------------------------------------------------------------- held experts

def init_held_moe(key, d_model, d_ff, n_experts, n_held, shared_d_ff,
                  dtype=jnp.float32, first=0):
    ks = jax.random.split(key, 6)
    s, sf = d_model ** -0.5, d_ff ** -0.5

    def experts(k, shape, scale):
        keys = jax.vmap(lambda e: jax.random.fold_in(k, e))(
            first + jnp.arange(n_held))
        return (jax.vmap(lambda ke: jax.random.normal(ke, shape))(keys)
                * scale).astype(dtype)

    return {
        "router": (jax.random.normal(ks[0], (d_model, n_experts)) * s
                   ).astype(dtype),
        "wi_gate": experts(ks[1], (d_model, d_ff), s),
        "wi_up": experts(ks[2], (d_model, d_ff), s),
        "wo": experts(ks[3], (d_ff, d_model), sf),
        "shared": layers.init_mlp(ks[4], d_model, shared_d_ff, dtype),
        "shared_gate": (jax.random.normal(ks[5], (d_model, 1)) * s
                        ).astype(dtype),
    }


def held_routing(p, x, *, top_k, first=0):
    """Route over every expert; return the held experts' combine weights
    (..., n_held) float32 (0 where a token did not pick one) and
    per-token picks of each held expert (..., n_held) int32."""
    n = p["wi_gate"].shape[0]
    logits = jnp.einsum("...d,de->...e", x, p["router"],
                        preferred_element_type=jnp.float32)
    vals, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    local = idx - first
    local = jnp.where((local >= 0) & (local < n), local, n)   # n: absent
    picks = jax.nn.one_hot(local, n + 1, dtype=jnp.int32)[..., :n]
    w = jnp.einsum("...ke,...k->...e", picks.astype(jnp.float32), vals)
    return w, jnp.sum(picks, -2)


def held_experts(p, x, w):
    """sum_e w_e SwiGLU_e(x) over the held experts, dropless: one matmul
    per projection over all held experts, the combine folded into the
    down projection."""
    h = jnp.einsum("...d,edf->...ef", x, p["wi_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("...d,edf->...ef", x, p["wi_up"],
                   preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * u * w[..., None]).astype(x.dtype)
    return jnp.einsum("...ef,efd->...d", a, p["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def shared_expert(p, x):
    g = jnp.einsum("...d,do->...o", x, p["shared_gate"],
                   preferred_element_type=jnp.float32)
    y = layers.mlp_fwd(p["shared"], x)
    return (y.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(x.dtype)


def held_moe(p, x, *, top_k, first=0):
    """The held experts' part plus the shared expert; x: (..., d).
    Returns (y, per-token picks of each held expert (..., n_held))."""
    with jax.named_scope("moe_router"):
        w, picks = held_routing(p, x, top_k=top_k, first=first)
    with jax.named_scope("moe_experts"):
        y = held_experts(p, x, w)
    with jax.named_scope("moe_shared"):
        y = y + shared_expert(p, x)
    return y, picks
