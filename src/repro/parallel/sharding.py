"""Sharding rules: params / batches / decode caches -> PartitionSpecs.

Axes:
  * batch (DP)        -> ("pod", "data") when the pod axis exists
  * tensor (TP/EP)    -> "model"   (attention & GDN heads, FFN hidden,
                                    MoE experts, vocab)
  * FSDP/ZeRO         -> "data" additionally shards the non-model dim of
                          every large matrix + optimizer moments (enabled
                          automatically for archs whose per-device footprint
                          would exceed HBM; see `needs_fsdp`)
  * SP                -> long-context prefill shards the sequence dim on
                          "data" (activations only; handled by GSPMD from
                          the batch spec when batch < data axis)

Decode caches: batch on DP when it covers the axis; otherwise the *context*
dim is sharded on "model" (flash-decode split-K: each device scans 1/16 of
the KV cache) and linear-state archs shard heads on "model" (the paper's
head parallelism, scaled out).
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


# ---------------------------------------------------------------- helpers

def mesh_axis(mesh: Mesh, name: str) -> bool:
    return name in mesh.axis_names


def dp_axes(mesh: Mesh):
    return ("pod", "data") if mesh_axis(mesh, "pod") else ("data",)


def axis_size(mesh: Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", None) or getattr(p, "name", None)
            or getattr(p, "idx", p)) for p in path)


def fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Make a spec valid for jit in/out shardings: every annotated dim must
    divide evenly.  Non-dividing axes are dropped; a dropped 'model' (TP)
    axis is re-placed on the last free dim it divides (e.g. head_dim when
    the head count is odd, vocab -> d_model for prime vocabs), so tensor
    parallelism is preserved wherever the shapes allow."""
    axes = list(spec) + [None] * (len(shape) - len(spec))
    dropped = []
    for i, ax in enumerate(axes):
        if ax is None:
            continue
        if shape[i] % axis_size(mesh, ax) != 0:
            dropped.append(ax)
            axes[i] = None
    for ax in dropped:
        for i in range(len(shape) - 1, -1, -1):
            if axes[i] is None and shape[i] % axis_size(mesh, ax) == 0 \
                    and shape[i] > 1:
                axes[i] = ax
                break
    return P(*axes)


# ---------------------------------------------------------------- params

def param_spec(path: str, shape, fsdp: bool) -> P:
    """Partition spec for one parameter leaf, by key-path pattern."""
    F = "data" if fsdp else None
    M = "model"

    def pick(*axes):
        # drop annotations that don't divide cleanly enough to be useful
        return P(*axes)

    # --- embeddings / head
    if path.endswith("embed/table"):
        return pick(M, F)                        # vocab-parallel
    if path.endswith("lm_head/w"):
        return pick(F, M)

    # --- norms, scalars, gates
    if re.search(r"(norm\d?|final_norm)/scale", path) or path.endswith("/b"):
        return P(None)
    if re.search(r"(A_log|dt_bias|Lambda|/D)$", path):
        return pick(M)

    # --- MoE (expert-parallel on model)
    if "/moe/" in path:
        if path.endswith("router"):
            return P(None, None)
        if path.endswith(("wi_gate", "wi_up")):
            return pick(M, F, None)              # (E, D, F)
        if path.endswith("wo"):
            return pick(M, None, F)              # (E, F, D)

    # --- dense MLP
    if "/mlp/" in path:
        if path.endswith(("wi_gate", "wi_up")):
            return pick(F, M)
        if path.endswith("wo"):
            return pick(M, F)

    # --- attention / GDN mixers
    if "/mixer/" in path:
        if path.endswith(("wq", "wk", "wv")):
            return pick(F, M, None)              # (D, H, hd): heads on TP
        if path.endswith("wo"):
            return pick(M, None, F)              # (H, hd, D)
        if path.endswith(("w_alpha", "w_beta")):
            return pick(F, M)
        # ssm projections
        if path.endswith(("w_z", "w_x")):
            return pick(F, M)                    # d_inner on TP
        if path.endswith(("w_B", "w_C")):
            return pick(F, None)                 # head-shared: replicated
        if path.endswith("w_dt"):
            return pick(F, M)
        if re.search(r"conv_x/w$", path):
            return P(None, M)
        if re.search(r"conv_[BC]/w$", path):
            return P(None, None)
        # rglru — gate matmuls are column-parallel (output W sharded, full
        # input gathered once): row-parallel here made every gate a psum of
        # the full (B, T, W) activation (EXPERIMENTS.md §Perf i5)
        if path.endswith(("in_x", "in_y")):
            return pick(F, M)
        if path.endswith(("w_a", "w_x")):
            return pick(None, M)
        if re.search(r"conv/w$", path):
            return P(None, M)
        if path.endswith("out"):
            return pick(M, F)
        if path.endswith("out_proj"):
            return pick(M, F)
    if path.endswith("out_proj"):
        return pick(M, F)

    return P()                                   # replicate by default


def _prepend_stack_dim(spec: P) -> P:
    """Layer-stacked params get a leading (repeats,) dim: unsharded."""
    return P(None, *spec)


def params_specs(cfg: ArchConfig, params_shape, fsdp: bool, mesh: Mesh):
    """Pytree of PartitionSpec matching a params (shape-)pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        ps = path_str(path)
        spec = param_spec(ps, leaf.shape, fsdp)
        if ps.startswith("groups/"):
            spec = _prepend_stack_dim(spec)
        # sanity: never annotate more axes than the leaf has dims
        if len(spec) > len(leaf.shape):
            spec = P(*list(spec)[: len(leaf.shape)])
        specs.append(fit_spec(spec, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def needs_fsdp(cfg: ArchConfig, mesh: Mesh, hbm_budget_gb: float = 10.0
               ) -> bool:
    """Shard params/moments over data too when TP alone won't fit HBM.

    Rough estimate: bytes/param = 2 (bf16 param) + 2 (bf16 grad)
    + 10 (adam m+v fp32 ... conservatively fp32) sharded model-axis only.
    """
    n_params = estimate_params(cfg)
    per_dev = n_params * (2 + 2 + 10) / axis_size(mesh, "model")
    return per_dev > hbm_budget_gb * 1e9


def estimate_params(cfg: ArchConfig) -> int:
    from repro.models.mixers import get_mixer
    d, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    total = V * d * (1 if cfg.tie_embeddings else 2)
    kinds = cfg.layer_kinds
    for kind in kinds:
        # per-mixer parameter counts are declared by the registry
        total += get_mixer(kind).param_count(cfg)
        if cfg.ffn in ("dense",):
            total += 3 * d * cfg.d_ff
        if cfg.ffn in ("moe", "moe+dense"):
            total += 3 * d * cfg.d_ff * cfg.moe_experts + d * cfg.moe_experts
        if cfg.ffn == "moe+dense":
            total += 3 * d * (cfg.d_ff_dense or cfg.d_ff)
    return int(total)


# ---------------------------------------------------------------- batches

def batch_specs(mesh: Mesh, batch_shape: dict) -> dict:
    dp = dp_axes(mesh)
    specs = {}
    for k, v in batch_shape.items():
        nd = len(v.shape)
        specs[k] = fit_spec(P(dp, *([None] * (nd - 1))), v.shape, mesh)
    return specs


# ---------------------------------------------------------------- caches

def cache_specs(cfg: ArchConfig, mesh: Mesh, caches_shape, batch: int):
    """Decode/prefill cache shardings (see module docstring)."""
    dp = dp_axes(mesh)
    dp_ok = batch % axis_size(mesh, dp) == 0
    BD = dp if dp_ok else None

    def leaf_spec(path, leaf):
        ps = path_str(path)
        shape = leaf.shape           # leading dim = layer-stack repeats
        nd = len(shape)
        if ps.endswith("/k") or ps.endswith("/v"):
            # KVCache (R, B, Hkv, S, hd): shard context dim on model
            return P(None, BD, None, "model", None)
        if ps.endswith("length"):
            return P(None, BD)
        if ps.endswith("/S"):
            # linear state (R, B, Hv, dk, dv) — SSD's (R, B, H, hd, ds):
            # heads on model (paper's head-parallelism); dim 3
            # additionally on data at tiny batch
            if dp_ok:
                return P(None, BD, "model", None, None)
            return P(None, None, "model", "data", None)
        if ps.endswith("/h"):
            return P(None, BD, "model")
        if "conv" in ps:
            return P(None, BD, None, "model") if nd == 4 else \
                P(*([None] * nd))
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_shape)
    specs = [fit_spec(leaf_spec(p, l), l.shape, mesh) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------- serving

def slot_specs(cfg: ArchConfig, mesh: Mesh, caches_shape, max_slots: int):
    """Serving slot-buffer shardings: the engine's cache pytree with the
    *slot* axis (dim 1, after the layer-stack repeats) on "data" and the
    paper's head parallelism scaled out on "model" — GDN/SSM state heads
    and the attention KV context dim, exactly the decode-cache rules
    above (``cache_specs`` with batch = slots)."""
    return cache_specs(cfg, mesh, caches_shape, max_slots)


def checkpoint_specs(cfg: ArchConfig, mesh: Mesh, ckpt_shape,
                     max_slots: int):
    """Speculative-decode checkpoint-buffer shardings: the rollback image
    is leaf-for-leaf a slot-cache copy (``lm.checkpoint_specs`` defaults
    every mixer's checkpoint to its full cache spec), so it shards under
    exactly the slot rules — slot axis on "data", state heads / KV
    context on "model".  Keeping the placements identical is what lets
    the verify program's conditional commit (select between run-ahead and
    committed trees) and the caches↔checkpoint buffer ping-pong stay
    communication-free: both trees of every pair live on the same
    devices, same layout."""
    return cache_specs(cfg, mesh, ckpt_shape, max_slots)


def staging_specs(slot_spec_tree):
    """Staging-buffer shardings derived from the slot specs: the staging
    pytree is the same cache layout at slot-count 1, so the slot ("data")
    annotation is cleared while every other axis (state heads / KV context
    on "model") keeps the *same* placement — the slot scatter then moves
    data only along the slot axis, never resharding heads."""
    def drop_slot(spec: P) -> P:
        axes = list(spec)
        if len(axes) > 1:
            axes[1] = None
        return P(*axes)
    return jax.tree.map(drop_slot, slot_spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def sampler_specs(mesh: Mesh, sampler_shape, max_slots: int):
    """Per-slot sampler arrays ((S,) / (S, 2) leaves): slot axis on the DP
    axes when it divides, replicated otherwise (never re-placed — a PRNG
    key's lane dim must not be split across devices)."""
    dp = dp_axes(mesh)
    dp_ok = max_slots % axis_size(mesh, dp) == 0
    return jax.tree.map(
        lambda v: P(dp if dp_ok else None,
                    *([None] * (len(v.shape) - 1))), sampler_shape)


def token_slot_spec(mesh: Mesh, max_slots: int) -> P:
    """The (S,) last-token vector: slot axis on DP when it divides."""
    dp = dp_axes(mesh)
    return P(dp) if max_slots % axis_size(mesh, dp) == 0 else P(None)


# ---------------------------------------------------------------- apply

def make_shardings(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def replicated(mesh: Mesh, tree):
    """Fully-replicated NamedSharding pytree matching ``tree``'s leaves."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
