"""qnext_moe mixer kind — Qwen3-Next's sparse MoE as a stateless sublayer:
a softmax router over all ``cfg.moe_experts``, top ``cfg.moe_top_k``
renormalized, the part of the result of the ``cfg.moe_experts_held``
experts this chip holds (dropless), and a gated shared expert; wrapping
``repro.models.moe.held_moe``.

It keeps no cache (its spec is the empty tree, which the slot scatter,
gather and paging carry through as nothing), so padded prefill rows need
no mask.  Its decode reports two counters over the live slots
(``decode_counters``): top-k assignments that land on held experts, and
held experts that at least one live token picked.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import moe
from repro.models.mixers import register
from repro.models.mixers.base import CacheSpec, SequenceMixer


@register
class HeldExpertMoE(SequenceMixer):
    kind = "qnext_moe"
    supports_ragged_prefill = True
    supports_batched_ragged_prefill = True
    state_passes = 0
    decode_counters = ("moe_held_assignments", "moe_held_experts_hit")

    @classmethod
    def held(cls, cfg):
        return cfg.moe_experts_held or cfg.moe_experts

    @classmethod
    def init_params(cls, key, cfg, dtype):
        return moe.init_held_moe(key, cfg.d_model, cfg.d_ff,
                                 cfg.moe_experts, cls.held(cfg),
                                 cfg.moe_shared_d_ff, dtype)

    @classmethod
    def train(cls, params, cfg, x):
        return moe.held_moe(params, x, top_k=cfg.moe_top_k)[0]

    @classmethod
    def prefill(cls, params, cfg, x, cache):
        return cls.train(params, cfg, x), cache

    @classmethod
    def prefill_chunk(cls, params, cfg, x, cache, valid_len=None):
        return cls.prefill(params, cfg, x, cache)

    @classmethod
    def decode(cls, params, cfg, x_t, cache):
        return cls.prefill(params, cfg, x_t, cache)

    @classmethod
    def decode_counted(cls, params, cfg, x_t, cache, live):
        y, picks = moe.held_moe(params, x_t, top_k=cfg.moe_top_k)
        picks = picks * live[:, None].astype(picks.dtype)
        return y, cache, {
            "moe_held_assignments": jnp.sum(picks),
            "moe_held_experts_hit": jnp.sum(jnp.any(picks > 0, axis=0)
                                            .astype(jnp.int32))}

    @classmethod
    def cache_spec(cls, cfg, batch, max_len):
        return CacheSpec(())

    @classmethod
    def decode_flops(cls, cfg, seq):
        return 0.0                 # no state: its matmuls are weights

    @classmethod
    def decode_token_bytes(cls, cfg):
        return 2 * cfg.d_model * jnp.dtype(cfg.act_dtype).itemsize

    @classmethod
    def param_count(cls, cfg):
        d = cfg.d_model
        return (3 * d * cfg.d_ff * cls.held(cfg) + d * cfg.moe_experts
                + 3 * d * cfg.moe_shared_d_ff + d)
