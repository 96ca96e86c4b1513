"""qnext_gdn mixer kind — Qwen3-Next's Gated DeltaNet layer: the decayed-
read recurrence, a short conv over q|k|v whose carry is cached, and a
gated RMSNorm before the output projection, wrapping the ``qnext_gdn_*``
functions of ``repro.models.gdn_layer``.  XLA path only (the Pallas
kernels implement the paper's Algorithm 1)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import gdn_layer
from repro.models.mixers import register
from repro.models.mixers.base import ArraySpec, CacheSpec
from repro.models.mixers.gdn import GatedDeltaNet


@register
class QNextGatedDeltaNet(GatedDeltaNet):
    kind = "qnext_gdn"

    @classmethod
    def _conv_channels(cls, cfg):
        return (2 * cfg.gdn_k_heads + cfg.gdn_v_heads) * cfg.gdn_head_dim

    @classmethod
    def init_params(cls, key, cfg, dtype):
        return gdn_layer.init_qnext_gdn(key, cfg.d_model, cfg.gdn_k_heads,
                                        cfg.gdn_v_heads, cfg.gdn_head_dim,
                                        cfg.gdn_conv_width, dtype)

    @classmethod
    def train(cls, params, cfg, x):
        return gdn_layer.qnext_gdn_train(params, x)

    @classmethod
    def prefill(cls, params, cfg, x, cache):
        return gdn_layer.qnext_gdn_prefill(params, x, cache)

    @classmethod
    def prefill_chunk(cls, params, cfg, x, cache, valid_len=None):
        return gdn_layer.qnext_gdn_prefill(params, x, cache,
                                           valid_len=valid_len)

    @classmethod
    def decode(cls, params, cfg, x_t, cache):
        return gdn_layer.qnext_gdn_decode(params, x_t, cache)

    @classmethod
    def cache_spec(cls, cfg, batch, max_len):
        hd = cfg.gdn_head_dim
        return CacheSpec(gdn_layer.GDNConvState(
            S=ArraySpec((batch, cfg.gdn_v_heads, hd, hd),
                        jnp.dtype(cfg.state_dtype), "state"),
            conv=ArraySpec((batch, cfg.gdn_conv_width - 1,
                            cls._conv_channels(cfg)),
                           jnp.dtype(cfg.act_dtype), "state")))

    @classmethod
    def param_count(cls, cfg):
        # + the z projection, the conv and the gated norm
        return (super().param_count(cfg)
                + cfg.d_model * cfg.gdn_v_heads * cfg.gdn_head_dim
                + cfg.gdn_conv_width * cls._conv_channels(cfg)
                + cfg.gdn_head_dim)
