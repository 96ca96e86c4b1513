"""Operations and bytes of a configuration, from its shapes alone.

Model FLOPs count 2 per weight that multiplies a token, plus each mixer's
own state or attention arithmetic; they depend on the configuration and
not on how the program computes it.  Bytes are the least a decode step
must move through HBM: every weight once, each live slot's recurrent
state read and written, and each live slot's cached keys and values read.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from harness.spec import load_mixer


@lru_cache(maxsize=None)
def _mixer(kind: str):
    return load_mixer(kind)


def layer_kinds(layout: dict):
    pat = layout["pattern"]
    return [pat[i % len(pat)] for i in range(layout["n_layers"])]


def _itemsize(name: str) -> int:
    return np.dtype(name).itemsize if name != "bfloat16" else 2


class Cost:
    def __init__(self, layout: dict):
        self.layout = layout
        self.kinds = layer_kinds(layout)
        d = layout["d_model"]
        self.d = d
        self.act = _itemsize(layout["act_dtype"])
        self.state_item = _itemsize(layout["state_dtype"])
        ffn = 3 * d * layout["d_ff"] if layout["ffn"] == "dense" else 0
        norms = d * (2 if layout["ffn"] != "none" else 1)
        mx = {k: _mixer(k) for k in set(self.kinds)}
        m = layout["mixers"]
        self.layer_matmul = [mx[k].matmul_params(d, m[k]) + ffn
                             for k in self.kinds]
        self.layer_params = [mx[k].params(d, m[k]) + ffn + norms
                             for k in self.kinds]
        V = layout["vocab"]
        self.embed_params = d * V
        self.head_params = 0 if layout["tie_embeddings"] else d * V
        self._mx = mx

    # ----------------------------------------------------------- params
    @property
    def params(self) -> int:
        """Every parameter the program holds (embedding, head, layers,
        final norm)."""
        return (sum(self.layer_params) + self.head_params
                + self.embed_params + self.d)

    @property
    def matmul_params(self) -> int:
        """Weights that multiply each token, the output head included."""
        return sum(self.layer_matmul) + self.embed_params

    @property
    def weight_bytes(self) -> int:
        """Weights a decode step streams from HBM: the layers and the
        output projection; of an untied embedding table it gathers one
        row per slot (counted with the slots)."""
        return (sum(self.layer_params) + self.d + self.embed_params) \
            * self.act

    # ------------------------------------------------------------ flops
    def token_flops(self, ctx: int) -> float:
        """Model FLOPs of one token that sees ``ctx`` positions
        (itself included)."""
        m = self.layout["mixers"]
        mixer = sum(self._mx[k].token_flops(m[k], ctx) for k in self.kinds)
        return 2.0 * self.matmul_params + mixer

    def prompt_flops(self, length: int) -> float:
        """Model FLOPs of a prompt of ``length`` tokens from an empty
        state: the attention term grows with the position."""
        m = self.layout["mixers"]
        total = 2.0 * self.matmul_params * length
        for k in self.kinds:
            f1 = self._mx[k].token_flops(m[k], 1)
            f2 = self._mx[k].token_flops(m[k], 2)
            slope = f2 - f1                        # per position seen
            base = f1 - slope
            total += base * length + slope * length * (length + 1) / 2
        return total

    # ------------------------------------------------------------ bytes
    @property
    def state_bytes(self) -> int:
        """Fixed recurrent state of one slot, all layers."""
        m = self.layout["mixers"]
        return sum(self._mx[k].state_bytes(m[k], self.state_item, self.act)
                   for k in self.kinds)

    @property
    def kv_bytes_per_position(self) -> int:
        m = self.layout["mixers"]
        return sum(self._mx[k].kv_bytes_per_position(m[k], self.act)
                   for k in self.kinds)

    def decode_step_bytes(self, live: int, ctx_total: int) -> float:
        """Least HBM traffic of one decode step over ``live`` slots whose
        contexts add up to ``ctx_total`` positions."""
        return (self.weight_bytes + live * self.d * self.act
                + 2.0 * live * self.state_bytes
                + ctx_total * self.kv_bytes_per_position)

    def decode_step_flops(self, live: int, ctx_total: int) -> float:
        m = self.layout["mixers"]
        per_ctx = sum(self._mx[k].token_flops(m[k], 2)
                      - self._mx[k].token_flops(m[k], 1) for k in self.kinds)
        base = self.token_flops(1) - per_ctx
        return live * base + per_ctx * ctx_total
