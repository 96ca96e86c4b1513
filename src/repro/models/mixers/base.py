"""SequenceMixer protocol + declarative persistent-state cache specs.

The paper's architectural claim is that every subquadratic mixer is the same
workload: a fixed-size persistent state touched once per token.  This module
is that claim as an interface.  A mixer kind is one class implementing

  init_params(key, cfg, dtype)     -> parameter pytree
  train(params, cfg, x)            -> (B, T, d) mixed output
  prefill(params, cfg, x, cache)   -> ((B, T, d) out, new cache)
  decode(params, cfg, x_t, cache)  -> ((B, d) out, new cache)
  decode_stacked(params, cfg, x_t, stacked, i, live=None)
                                   -> decode of layer i of a layer-stacked
                                      cache, updated in place (default:
                                      slice, ``decode``, write back whole)
  cache_spec(cfg, batch, max_len)  -> CacheSpec (declarative state layout)
  init_cache(cfg, batch, max_len)  -> cache pytree (default: spec zeros)

plus declarative class attributes consumed by the serving engine, the
sharding planner and the intensity model:

  kind          registry name (the string used in ArchConfig.pattern)
  is_attention  softmax-attention family (KV cache instead of fixed state)
  quadratic     O(T) decode state (unwindowed full attention)
  state_passes  HBM round-trips over the persistent state per decoded token
                on a naive (non-persistent) backend: reads + writes
  decode_counters  counters its ``decode_counted`` reports per step

``CacheSpec`` mirrors the runtime cache pytree with ``ArraySpec`` leaves, so
slot buffers, byte budgets and roofline terms are all derived from one
declaration instead of per-kind formulas scattered across the codebase.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Shape/dtype/role of one cache leaf.

    role: "state"  — fixed-size recurrent state (S matrices, conv carries,
                     RG-LRU vectors): the paper's persistent state;
          "window" — context-sized buffers read once per token (KV caches,
                     rolling SWA windows);
          "meta"   — bookkeeping scalars (sequence lengths), not counted in
                     byte budgets.
    """
    shape: Tuple[int, ...]
    dtype: Any
    role: str = "state"

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.shape)) * np.dtype(self.dtype).itemsize

    def stack(self, reps: int) -> "ArraySpec":
        return ArraySpec((reps,) + tuple(self.shape), self.dtype, self.role)


def _spec_leaves(tree):
    return [l for l in jax.tree.leaves(tree) if isinstance(l, ArraySpec)]


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """A pytree of ArraySpec leaves mirroring the runtime cache structure."""
    tree: Any

    def leaves(self):
        return _spec_leaves(self.tree)

    def zeros(self):
        """Materialize the cache buffers this spec describes (all-zero init
        is part of the contract: slot admit may skip clearing freed slots)."""
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, jnp.dtype(s.dtype)), self.tree)

    def shape_dtype(self):
        """The spec as a jax.ShapeDtypeStruct pytree (for jit.lower etc.)."""
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
            self.tree)

    def stack(self, reps: int) -> "CacheSpec":
        """Add a leading layer-stack dim to every leaf (scanned layouts)."""
        return CacheSpec(jax.tree.map(lambda s: s.stack(reps), self.tree))

    def _role_bytes(self, role: str) -> int:
        return sum(l.nbytes for l in self.leaves() if l.role == role)

    @property
    def state_bytes(self) -> int:
        """Fixed-size persistent recurrent state (paper Eq. 8 budget)."""
        return self._role_bytes("state")

    @property
    def window_bytes(self) -> int:
        """Context-sized buffers (KV / rolling windows)."""
        return self._role_bytes("window")

    @property
    def nbytes(self) -> int:
        """Total buffer bytes (including meta) — the HBM footprint."""
        return sum(l.nbytes for l in self.leaves())


class SequenceMixer:
    """Base class for registered mixer kinds.  Subclasses override the
    classmethods; every method takes the full ArchConfig so adding a mixer
    never requires threading new per-kind kwargs through the model."""

    kind: str = ""
    is_attention: bool = False
    quadratic: bool = False
    state_passes: int = 2          # naive backend: 1 read + 1 write
    # declarative capability: True iff prefill_chunk implements the
    # per-token validity mask (ragged fixed-size chunks).  The serving
    # executor's masked planner requires it from every kind in the
    # pattern and falls back to pow2 tail plans otherwise — a kind
    # registered without masking still serves, it just pays the larger
    # compile cache.
    supports_ragged_prefill: bool = False
    # True iff prefill_chunk additionally accepts a per-row (B,) valid_len
    # vector (each batch row ragged at its own boundary).  The batched
    # multi-prompt staging path (one fused prefill program over all staged
    # prompts per tick) requires it from every kind in the pattern; the
    # executor falls back to per-prompt dispatch otherwise.  Implies
    # supports_ragged_prefill.
    supports_batched_ragged_prefill: bool = False
    # names of the counters ``decode_counted`` reports per decode step
    # (summed over the kind's layers by ``lm.decode_steps(count=True)``);
    # a kind without counters never computes any
    decode_counters: Tuple[str, ...] = ()

    @classmethod
    def init_params(cls, key, cfg, dtype):
        raise NotImplementedError(cls.kind)

    @classmethod
    def train(cls, params, cfg, x):
        raise NotImplementedError(cls.kind)

    @classmethod
    def prefill(cls, params, cfg, x, cache):
        raise NotImplementedError(cls.kind)

    @classmethod
    def prefill_chunk(cls, params, cfg, x, cache, valid_len=None):
        """Process one prompt chunk *continuing from* ``cache`` (the serving
        engine's chunked/overlapped prefill calls this once per chunk).

        Default: ``prefill`` — correct for any mixer whose prefill resumes
        from the cache state and is position-independent, which is every
        recurrent kind (the state pytree *is* the position).  Mixers whose
        prefill depends on absolute position or ignores the incoming cache
        (RoPE attention over a KV cache) must override this to continue at
        the cached position.

        ``valid_len`` (optional scalar int32) marks a *ragged* chunk padded
        to its static size: only the first valid_len tokens are real.  An
        implementation must leave the cache exactly as if only the valid
        prefix had been processed (padded output rows may be garbage).
        Every built-in kind supports it; the default implementation cannot
        (plain ``prefill`` would fold padding into the state), so masked
        chunks are rejected here rather than silently corrupting state.
        """
        if valid_len is not None:
            raise NotImplementedError(
                f"mixer kind {cls.kind!r} does not support ragged "
                f"(valid_len-masked) prefill chunks — override "
                f"prefill_chunk to mask padded positions")
        return cls.prefill(params, cfg, x, cache)

    @classmethod
    def decode(cls, params, cfg, x_t, cache):
        raise NotImplementedError(cls.kind)

    @classmethod
    def decode_counted(cls, params, cfg, x_t, cache, live):
        """``decode`` plus this kind's ``decode_counters`` over the rows
        where ``live`` ((B,) bool) is set: returns (out, cache,
        {name: int32 scalar})."""
        raise NotImplementedError(cls.kind)

    @classmethod
    def decode_stacked(cls, params, cfg, x_t, stacked, i, live=None):
        """``decode`` (``decode_counted`` with ``live``, where the kind
        counts) of layer ``i`` of ``stacked``, this kind's cache with a
        leading layer axis on every leaf, written back into the stack, so
        the decode tick carries one buffer through its layer loop and
        updates it in place.  Returns (out, stacked, {name: int32}), the
        counters empty where none were asked for.

        Default: slice layer i and write every leaf back whole, which is
        what a step does to state-role leaves.  A kind with window-role
        leaves (context-sized buffers a step changes one row of)
        overrides this to write only that row."""
        cache = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            stacked)
        counts = {}
        if live is not None and cls.decode_counters:
            out, cache, counts = cls.decode_counted(params, cfg, x_t, cache,
                                                    live)
        else:
            out, cache = cls.decode(params, cfg, x_t, cache)
        stacked = jax.tree.map(
            lambda a, c: jax.lax.dynamic_update_index_in_dim(a, c, i, 0),
            stacked, cache)
        return out, stacked, counts

    @classmethod
    def cache_spec(cls, cfg, batch: int, max_len: int) -> CacheSpec:
        raise NotImplementedError(cls.kind)

    @classmethod
    def init_cache(cls, cfg, batch: int, max_len: int):
        return cls.cache_spec(cfg, batch, max_len).zeros()

    @classmethod
    def checkpoint_spec(cls, cfg, batch: int, max_len: int) -> CacheSpec:
        """Per-slot rollback image for speculative decode: the state copy
        the verify program restores a slot from when its draft suffix is
        rejected (one extra state copy per slot, the cost ROADMAP calls
        out).  Default: the full ``cache_spec`` — decode mutates every
        leaf destructively (a rolling-window KV insert overwrites the
        wrapped position; length meta alone cannot recover it), so a
        partial checkpoint would be unsound.  A mixer whose decode
        provably leaves some leaves untouched may narrow this, but the
        tree *structure* must stay identical to ``cache_spec`` — the
        verify program's conditional commit selects between run-ahead and
        committed trees leaf-by-leaf."""
        return cls.cache_spec(cfg, batch, max_len)

    # ---- analytical decode model (consumed by core.intensity) ----------

    @classmethod
    def decode_flops(cls, cfg, seq: int) -> float:
        """Per-token mixer FLOPs at decode (batch 1)."""
        raise NotImplementedError(cls.kind)

    @classmethod
    def decode_token_bytes(cls, cfg) -> float:
        """Per-token activation I/O (q/k/v/o projections etc.)."""
        raise NotImplementedError(cls.kind)

    @classmethod
    def param_count(cls, cfg) -> int:
        """Mixer parameter count per layer (sharding/footprint planning)."""
        raise NotImplementedError(cls.kind)
