"""The plain float32 reference model, computed layer by layer.

It imports nothing of the program.  It draws the same weights from the
same seed by the same recipe (a float32 normal per matrix, scaled, in
the dtype the configuration serves), then runs every sequence of a
sample from position 0 in float32 at the highest matmul precision,
one layer at a time, so that only one layer's weights are ever held.

``mode="fp8"`` runs the control: the same computation with every weight
matmul in float8 (see ``numerics``).
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from harness.cost import layer_kinds
from harness.numerics import F32, draw, mm, rmsnorm
from harness.spec import load_mixer

_ROWS = 1                 # sequences per block of the output head


def _dtype(layout):
    return jnp.dtype(layout["act_dtype"])


def _layer_fn(kind: str, layout: dict, mode: str):
    mixer = load_mixer(kind)
    m = layout["mixers"][kind]
    d, eps, dtype = layout["d_model"], layout["norm_eps"], _dtype(layout)

    def layer(key, x):
        ks = jax.random.split(key, 4)
        x = x + mixer.forward(mixer.init(ks[0], d, m, dtype),
                              rmsnorm(x, 1.0, eps), m, mode)
        if layout["ffn"] == "dense":
            f = layout["d_ff"]
            k1, k2, k3 = jax.random.split(ks[1], 3)
            h = rmsnorm(x, 1.0, eps)
            gate = mm("btd,df->btf", h, draw(k1, (d, f), d ** -0.5, dtype),
                      mode)
            up = mm("btd,df->btf", h, draw(k2, (d, f), d ** -0.5, dtype),
                    mode)
            x = x + mm("btf,fd->btd", jax.nn.silu(gate) * up,
                       draw(k3, (f, d), f ** -0.5, dtype), mode)
        elif layout["ffn"] != "none":
            raise NotImplementedError(f"ffn {layout['ffn']!r}")
        return x

    return jax.jit(layer, donate_argnums=(1,))


@lru_cache(maxsize=None)
def _fns(layout_key: str, mode: str):
    import json
    layout = json.loads(layout_key)
    kinds = sorted(set(layer_kinds(layout)))
    d, V, eps = layout["d_model"], layout["vocab"], layout["norm_eps"]
    dtype = _dtype(layout)
    tied = layout["tie_embeddings"]

    def embed(k_embed, tokens):
        table = draw(k_embed, (V, d), d ** -0.5, dtype)
        return jnp.take(table, tokens, axis=0)

    def head(k_embed, k_head, x, lookup):
        """Row max, argmax and the logits at ``lookup`` tokens, for every
        position; the (T, V) logits exist for one sequence at a time."""
        w = (draw(k_embed, (V, d), d ** -0.5, dtype).T if tied
             else draw(k_head, (d, V), d ** -0.5, dtype))

        def rows(args):
            xb, lb = args
            logits = mm("btd,dv->btv", rmsnorm(xb, 1.0, eps), w, mode)
            return (jnp.max(logits, -1), jnp.argmax(logits, -1),
                    jnp.take_along_axis(logits, lb, -1))

        B, T = x.shape[:2]
        xs = x.reshape((B // _ROWS, _ROWS, T, d))
        ls = lookup.reshape((B // _ROWS, _ROWS) + lookup.shape[1:])
        mx, am, lk = jax.lax.map(rows, (xs, ls))
        return (mx.reshape(B, T), am.reshape(B, T),
                lk.reshape(lookup.shape))

    return ({k: _layer_fn(k, layout, mode) for k in kinds},
            jax.jit(embed), jax.jit(head))


def run(layout: dict, seed: int, tokens: np.ndarray, lookup: np.ndarray,
        mode: str = "f32"):
    """tokens: (B, T) int32, each row a sequence from position 0 (padding
    after its end is harmless: every layer is causal).  lookup: (B, T, K)
    token ids.  Returns numpy (row max (B, T), argmax (B, T), logits at
    lookup (B, T, K)) of the logits at every position."""
    import json
    layers, embed, head = _fns(json.dumps(layout, sort_keys=True), mode)
    key = jax.random.PRNGKey(seed)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    lkeys = jax.random.split(k_layers, layout["n_layers"])
    with jax.default_matmul_precision("highest"):
        x = embed(k_embed, jnp.asarray(tokens, jnp.int32)).astype(F32)
        for kind, k in zip(layer_kinds(layout), lkeys):
            x = layers[kind](k, x)
        out = head(k_embed, k_head, x, jnp.asarray(lookup, jnp.int32))
    return tuple(np.asarray(o) for o in out)
