"""qnext_attn mixer kind — Qwen3-Next's gated attention: a sigmoid output
gate per head, RMSNorm on each query and key head, rotary embedding on
the first ``cfg.rotary_dim`` dims of each head; ``attn`` otherwise (KV
cache, ragged chunks, XLA decode), wrapping ``repro.models.attention``."""
from __future__ import annotations

from repro.models import attention
from repro.models.mixers import register
from repro.models.mixers.attn import Attention


@register
class GatedAttention(Attention):
    kind = "qnext_attn"

    @classmethod
    def _rotary_dim(cls, cfg):
        return cfg.rotary_dim or None

    @classmethod
    def init_params(cls, key, cfg, dtype):
        return attention.init_gated_attention(key, cfg.d_model, cfg.hq_eff,
                                              cfg.hkv_eff, cfg.head_dim,
                                              dtype)

    @classmethod
    def param_count(cls, cfg):
        return (super().param_count(cfg)
                + cfg.d_model * cfg.n_heads * cfg.head_dim
                + 2 * cfg.head_dim)
