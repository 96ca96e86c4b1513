"""attn / swa mixer kinds — softmax attention over a (possibly rolling)
KV cache, wrapping ``repro.models.attention``."""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import attention
from repro.models.mixers import register
from repro.models.mixers.base import ArraySpec, CacheSpec, SequenceMixer


def _head_mask(cfg):
    if not cfg.n_heads_pad and not cfg.n_kv_heads_pad:
        return None
    return jnp.asarray(cfg.head_mask())


@register
class Attention(SequenceMixer):
    kind = "attn"
    is_attention = True
    supports_ragged_prefill = True
    supports_batched_ragged_prefill = True   # per-row (B,) valid_len
    quadratic = True           # O(T) KV — no fixed-size persistent state
    state_passes = 0

    @classmethod
    def _window(cls, cfg):
        return None

    @classmethod
    def _rotary_dim(cls, cfg):
        return None

    @classmethod
    def init_params(cls, key, cfg, dtype):
        return attention.init_attention(key, cfg.d_model, cfg.hq_eff,
                                        cfg.hkv_eff, cfg.head_dim, dtype)

    @classmethod
    def train(cls, params, cfg, x):
        return attention.attn_train(params, x, rope_theta=cfg.rope_theta,
                                    window=cls._window(cfg),
                                    use_flash_kernel=cfg.use_flash_kernel,
                                    head_mask=_head_mask(cfg),
                                    rotary_dim=cls._rotary_dim(cfg))

    @classmethod
    def prefill(cls, params, cfg, x, cache):
        return attention.attn_prefill(params, x, cache,
                                      rope_theta=cfg.rope_theta,
                                      window=cls._window(cfg),
                                      head_mask=_head_mask(cfg),
                                      rotary_dim=cls._rotary_dim(cfg))

    @classmethod
    def prefill_chunk(cls, params, cfg, x, cache, valid_len=None):
        # positions and visibility continue from cache.length (the base-class
        # default would restart RoPE at 0 and drop the cached KV); ragged
        # chunks skip the rolling insert of padded positions and advance
        # length by valid_len only
        return attention.attn_prefill_chunk(params, x, cache,
                                            rope_theta=cfg.rope_theta,
                                            window=cls._window(cfg),
                                            head_mask=_head_mask(cfg),
                                            valid_len=valid_len,
                                            rotary_dim=cls._rotary_dim(cfg))

    @classmethod
    def decode(cls, params, cfg, x_t, cache):
        return attention.attn_decode_xla(params, x_t, cache,
                                         rope_theta=cfg.rope_theta,
                                         window=cls._window(cfg),
                                         head_mask=_head_mask(cfg),
                                         rotary_dim=cls._rotary_dim(cfg))

    @classmethod
    def decode_stacked(cls, params, cfg, x_t, stacked, i, live=None):
        # K and V are window-role leaves: a step writes one row per slot
        # (swa: at its rolling slot, length mod window)
        out, stacked = attention.attn_decode_stacked(
            params, x_t, stacked, i, rope_theta=cfg.rope_theta,
            head_mask=_head_mask(cfg), rotary_dim=cls._rotary_dim(cfg))
        return out, stacked, {}

    @classmethod
    def cache_spec(cls, cfg, batch, max_len):
        w = cls._window(cfg)
        size = max_len if w is None else min(w, max_len)
        dtype = jnp.dtype(cfg.act_dtype)
        kv = (batch, cfg.hkv_eff, size, cfg.head_dim)
        return CacheSpec(attention.KVCache(
            k=ArraySpec(kv, dtype, "window"),
            v=ArraySpec(kv, dtype, "window"),
            length=ArraySpec((batch,), jnp.int32, "meta")))

    @classmethod
    def decode_flops(cls, cfg, seq):
        w = cls._window(cfg)
        eff = seq if w is None else min(w, seq)
        return 2.0 * cfg.hq_eff * cfg.head_dim * eff * 2   # qk^T and pv

    @classmethod
    def decode_token_bytes(cls, cfg):
        w = jnp.dtype(cfg.act_dtype).itemsize
        return (2 * cfg.hq_eff * cfg.head_dim
                + 2 * cfg.hkv_eff * cfg.head_dim) * w

    @classmethod
    def param_count(cls, cfg):
        d = cfg.d_model
        return (d * cfg.head_dim * (cfg.n_heads + 2 * cfg.n_kv_heads)
                + cfg.n_heads * cfg.head_dim * d)


@register
class SlidingWindowAttention(Attention):
    kind = "swa"
    quadratic = False          # rolling window: O(window) state

    @classmethod
    def _window(cls, cfg):
        return cfg.window
