"""GQA / sliding-window attention with blockwise (flash-style) compute.

Three entry points per layer:
  * ``attn_train``   — full-sequence causal (optionally windowed) attention,
                       blockwise online-softmax scan over KV blocks: never
                       materializes the (T, T) score matrix (required for the
                       32k prefill and 4k train shapes at production batch).
  * ``attn_prefill`` — attn_train + returns the populated KV cache.
  * ``attn_decode``  — one new token against the cache. Pure-JAX einsum path
                       (GSPMD-shardable over batch / heads / cache length) or
                       the Pallas flash-decode kernel (`use_pallas`).

KV cache layout: (B, Hkv, Tmax, hd) + scalar lengths (B,).  For SWA archs the
cache is a rolling buffer of ``window`` positions (O(1) memory at 500k ctx).

Gated attention (Qwen3-Next, ``init_gated_attention``): the parameters
add ``wg``, a second query-shaped projection whose sigmoid gates each
head's output before ``wo``, and ``q_norm``/``k_norm``, an RMSNorm over
each query and key head before the rotary embedding.  Every entry point
applies them when the parameters hold them; ``rotary_dim`` rotates only
the first dims of each head (partial rotary).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import layers


class KVCache(NamedTuple):
    k: jax.Array          # (B, Hkv, Tmax, hd)
    v: jax.Array          # (B, Hkv, Tmax, hd)
    length: jax.Array     # (B,) int32 — tokens seen so far (may exceed Tmax
                          # for rolling SWA caches)


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim,
                   dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads, head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_kv_heads, head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_kv_heads, head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads, head_dim, d_model))
               * ((n_heads * head_dim) ** -0.5)).astype(dtype),
    }


def init_gated_attention(key, d_model, n_heads, n_kv_heads, head_dim,
                         dtype=jnp.float32):
    """``init_attention`` (same keys) plus the output gate's projection
    and the per-head q/k norms."""
    p = init_attention(key, d_model, n_heads, n_kv_heads, head_dim, dtype)
    p["wg"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                 (d_model, n_heads, head_dim))
               * d_model ** -0.5).astype(dtype)
    p["q_norm"] = layers.init_rmsnorm(head_dim)
    p["k_norm"] = layers.init_rmsnorm(head_dim)
    return p


def _qk_norm(p, q, k):
    if "q_norm" not in p:
        return q, k
    return (layers.rmsnorm_fwd(p["q_norm"], q),
            layers.rmsnorm_fwd(p["k_norm"], k))


def _gate(p, x, o):
    """o * sigmoid(x wg) per head, where the parameters have a gate.
    x: (..., d); o: (..., H, hd)."""
    if "wg" not in p:
        return o
    g = jnp.einsum("...d,dhk->...hk", x, p["wg"],
                   preferred_element_type=jnp.float32)
    return (o.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(o.dtype)


def _qkv(p, x, positions, rope_theta, rotary_dim=None):
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"]).astype(x.dtype)
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"]).astype(x.dtype)
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"]).astype(x.dtype)
    q, k = _qk_norm(p, q, k)
    q = layers.apply_rope(q, positions, rope_theta, rotary_dim)
    k = layers.apply_rope(k, positions, rope_theta, rotary_dim)
    return q, k, v


def blockwise_attention(q, k, v, *, window=None, block_kv=512):
    """Causal (optionally sliding-window) attention, scanned over KV blocks.

    q: (B, T, Hq, hd); k, v: (B, T, Hkv, hd).  Returns (B, T, Hq, hd).
    Memory per scan step: O(T * block_kv) scores instead of O(T^2).
    """
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    bkv = min(block_kv, T)
    n_blocks = T // bkv
    assert T % bkv == 0

    qg = q.reshape(B, T, Hkv, G, hd)
    kb = k.reshape(B, n_blocks, bkv, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, bkv, Hkv, hd).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(T)

    # remat the per-block step: without this, the backward pass stacks every
    # block's (B, T, H, bkv) score tensor as a saved residual — measured at
    # 38 GB/layer/device on the train_4k cells (see EXPERIMENTS.md §Perf i1).
    @jax.checkpoint
    def step(carry, inp):
        m, l, acc = carry                    # (B,T,Hkv,G) / same / (...,hd)
        kv_idx, k_blk, v_blk = inp           # k_blk: (B, bkv, Hkv, hd)
        # bf16 inputs with fp32 accumulation — no materialized fp32 k/v
        s = scale * jnp.einsum("bthgd,bshd->bthgs", qg, k_blk,
                               preferred_element_type=jnp.float32)
        kv_pos = kv_idx * bkv + jnp.arange(bkv)
        mask = q_pos[:, None] >= kv_pos[None, :]            # causal
        if window is not None:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum(
            "bthgs,bshd->bthgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, T, Hkv, G), -1e30, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, T, Hkv, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_blocks), kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, T, Hq, hd).astype(q.dtype)


def _apply_head_mask(o, head_mask):
    """Zero the TP-padding heads (see ArchConfig.head_mask) — keeps padded
    attention mathematically identical to the unpadded model."""
    if head_mask is None:
        return o
    shape = (1,) * (o.ndim - 2) + (o.shape[-2], 1)
    return o * head_mask.reshape(shape).astype(o.dtype)


def attn_train(p, x, *, rope_theta=10000.0, window=None, block_kv=512,
               use_flash_kernel=False, head_mask=None, rotary_dim=None):
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k, v = _qkv(p, x, positions, rope_theta, rotary_dim)
    if use_flash_kernel:
        # Pallas fused path: scores stay in VMEM, HBM traffic O(B·T·H·d)
        from repro.kernels import ops
        from repro.kernels.flash_attn import flash_attention
        o = flash_attention(q, k, v, min(512, T), min(512, T), window,
                            ops.interpret_mode())
    else:
        o = blockwise_attention(q, k, v, window=window, block_kv=block_kv)
    o = _apply_head_mask(_gate(p, x, o), head_mask)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"]).astype(x.dtype)


def attn_prefill(p, x, cache: KVCache, *, rope_theta=10000.0, window=None,
                 block_kv=512, head_mask=None, rotary_dim=None):
    """Run full attention over the prompt and populate the cache.

    Assumes all sequences share length T (ragged prompts are left-padded by
    the serving engine).  Rolling SWA caches keep the last `size` tokens.
    """
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k, v = _qkv(p, x, positions, rope_theta, rotary_dim)
    o = blockwise_attention(q, k, v, window=window, block_kv=block_kv)
    size = cache.k.shape[2]
    kh = k.transpose(0, 2, 1, 3)          # (B, Hkv, T, hd)
    vh = v.transpose(0, 2, 1, 3)
    if T >= size:
        # keep the last `size` tokens, arranged so token p sits at slot
        # p mod size (required by the rolling insert in _cache_insert).
        new_k = jnp.roll(kh[:, :, -size:, :], T % size, axis=2)
        new_v = jnp.roll(vh[:, :, -size:, :], T % size, axis=2)
    else:
        new_k = jax.lax.dynamic_update_slice(
            cache.k, kh.astype(cache.k.dtype), (0, 0, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            cache.v, vh.astype(cache.v.dtype), (0, 0, 0, 0))
    new_cache = KVCache(new_k.astype(cache.k.dtype),
                        new_v.astype(cache.v.dtype),
                        cache.length + T)
    o = _apply_head_mask(_gate(p, x, o), head_mask)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"]).astype(x.dtype)
    return out, new_cache


def attn_prefill_chunk(p, x, cache: KVCache, *, rope_theta=10000.0,
                       window=None, head_mask=None, valid_len=None,
                       rotary_dim=None):
    """Process one prompt chunk *continuing from* the cache.

    Unlike ``attn_prefill`` (which assumes a fresh cache and positions
    starting at 0), this attends the chunk's queries against the cached KV
    *and* the in-chunk causal prefix, with RoPE positions offset by
    ``cache.length`` — the building block of the serving engine's chunked
    prefill.  Exactly equivalent to decoding the chunk token by token:
    a pre-chunk cache slot is visible to query at position ``pos`` iff it
    is occupied and its token is among the ``size`` most recent at ``pos``
    (the rolling buffer holds exactly those, so this matches what serial
    `attn_decode_xla` calls would see).

    ``valid_len`` (optional scalar or per-row (B,) int32) marks a ragged
    chunk padded to C: only the first valid_len tokens of each row are
    real.  Padded positions are
    **not** inserted into the rolling buffer (a wrapped-slot write would
    overwrite still-visible valid tokens) and ``length`` advances by
    ``valid_len`` only; their k/v never reach a valid query's scores
    (in-chunk visibility is causal, and every padded position sits after
    every valid one).  Output rows at padded positions are garbage —
    callers ignore them.

    x: (B, C, d_model) with C <= cache size (the rolling scatter writes
    each chunk token to a distinct slot).  Returns (out (B, C, d), cache).
    """
    B, C, _ = x.shape
    size = cache.k.shape[2]
    if C > size:
        raise ValueError(f"prefill chunk of {C} tokens exceeds the rolling "
                         f"KV buffer ({size}); lower the chunk size")
    pos = cache.length[:, None] + jnp.arange(C)[None, :]       # (B, C)
    q, k, v = _qkv(p, x, pos, rope_theta, rotary_dim)
    Hq, hd = q.shape[2], q.shape[3]
    Hkv = cache.k.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, Hkv, G, hd).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,C,hd)

    # --- scores vs the pre-chunk cache -------------------------------
    # slot t holds absolute position p_t = the largest p < length with
    # p = t (mod size); it is visible to query i iff occupied and within
    # the `size` most recent positions at pos_i (serial-decode rule).
    t_idx = jnp.arange(size)
    L = cache.length[:, None]                                  # (B, 1)
    p_t = (L - 1) - jnp.mod(L - 1 - t_idx[None, :], size)      # (B, size)
    occupied = t_idx[None, :] < L
    vis = occupied[:, None, :] & (p_t[:, None, :]
                                  > pos[:, :, None] - size)    # (B, C, size)
    s_cache = scale * jnp.einsum("bhgcd,bhtd->bhgct", qg, cache.k,
                                 preferred_element_type=jnp.float32)
    s_cache = jnp.where(vis[:, None, None, :, :], s_cache, -1e30)

    # --- in-chunk causal scores --------------------------------------
    # with C <= size every in-chunk position is within the most-recent
    # window of every later query, so the mask is plain causal
    kc = k.transpose(0, 2, 1, 3)                               # (B,Hkv,C,hd)
    vc = v.transpose(0, 2, 1, 3)
    s_chunk = scale * jnp.einsum("bhgcd,bhjd->bhgcj", qg, kc,
                                 preferred_element_type=jnp.float32)
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    s_chunk = jnp.where(causal[None, None, None, :, :], s_chunk, -1e30)

    # --- two-part online-softmax combine ------------------------------
    # The cache and chunk score blocks are softmaxed separately and merged
    # flash-style instead of concatenated: under a mesh the cache's context
    # dim is sharded on "model" (split-K decode) while the in-chunk scores
    # are replicated, and a concatenate along that mixed-sharded axis is
    # exactly the kind of resharding GSPMD handles worst (the -1e30 mask
    # values get mangled through the halo padding); the per-block
    # max/sum/weighted-sum reductions below partition cleanly.
    m_cache = jnp.max(s_cache, axis=-1)                        # (B,Hkv,G,C)
    e_cache = jnp.exp(s_cache - m_cache[..., None])
    l_cache = jnp.sum(e_cache, axis=-1)
    o_cache = jnp.einsum("bhgct,bhtd->bhgcd",
                         e_cache.astype(cache.v.dtype), cache.v,
                         preferred_element_type=jnp.float32)
    m_chunk = jnp.max(s_chunk, axis=-1)
    e_chunk = jnp.exp(s_chunk - m_chunk[..., None])
    l_chunk = jnp.sum(e_chunk, axis=-1)
    o_chunk = jnp.einsum("bhgcj,bhjd->bhgcd", e_chunk.astype(vc.dtype), vc,
                         preferred_element_type=jnp.float32)
    m = jnp.maximum(m_cache, m_chunk)
    # a fully-masked block has m_* = -1e30 => weight exp(-1e30 - m) == 0,
    # so its (garbage) unnormalized sums never contribute
    w_cache = jnp.exp(m_cache - m)
    w_chunk = jnp.exp(m_chunk - m)
    l = w_cache * l_cache + w_chunk * l_chunk
    o = (w_cache[..., None] * o_cache + w_chunk[..., None] * o_chunk) \
        / jnp.maximum(l, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, C, Hq, hd).astype(x.dtype)
    o = _apply_head_mask(_gate(p, x, o), head_mask)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"]).astype(x.dtype)

    # --- rolling insert of the chunk (distinct slots since C <= size) -
    slots = jnp.mod(pos, size)                                 # (B, C)
    if valid_len is not None:
        # padded positions must not touch the buffer: in the rolling phase
        # their wrapped slot aliases a still-visible valid token.  Routing
        # them to the out-of-bounds slot `size` with mode="drop" makes the
        # scatter skip them entirely.  valid_len is a scalar or a per-row
        # (B,) vector (batched staging) — both reshape to (B or 1, 1).
        vl = jnp.reshape(jnp.asarray(valid_len, jnp.int32), (-1, 1))
        slots = jnp.where(jnp.arange(C)[None, :] < vl, slots, size)
    new_k = jax.vmap(lambda ck, kk, sl: ck.at[:, sl, :].set(
        kk.astype(ck.dtype), mode="drop"))(cache.k, kc, slots)
    new_v = jax.vmap(lambda cv, vv, sl: cv.at[:, sl, :].set(
        vv.astype(cv.dtype), mode="drop"))(cache.v, vc, slots)
    adv = C if valid_len is None else valid_len
    return out, KVCache(new_k, new_v, cache.length + adv)


def _cache_insert(cache: KVCache, k_t, v_t):
    """Insert one token at the rolling position. k_t: (B, Hkv, hd)."""
    size = cache.k.shape[2]
    slot = jnp.mod(cache.length, size)    # (B,) rolling slot (no-op when
                                          # size == max_len since length < size)
    b_idx = jnp.arange(cache.k.shape[0])
    new_k = cache.k.at[b_idx, :, slot, :].set(k_t.astype(cache.k.dtype))
    new_v = cache.v.at[b_idx, :, slot, :].set(v_t.astype(cache.v.dtype))
    return KVCache(new_k, new_v, cache.length + 1)


def attn_decode_xla(p, x_t, cache: KVCache, *, rope_theta=10000.0,
                    window=None, head_mask=None, rotary_dim=None):
    """One-token decode, pure-JAX (GSPMD-shardable einsum over the cache).

    x_t: (B, d_model). Returns (out (B, d_model), new_cache).
    """
    B, d_model = x_t.shape
    pos = cache.length                    # (B,)
    q = jnp.einsum("bd,dhk->bhk", x_t, p["wq"]).astype(x_t.dtype)
    k = jnp.einsum("bd,dhk->bhk", x_t, p["wk"]).astype(x_t.dtype)
    v = jnp.einsum("bd,dhk->bhk", x_t, p["wv"]).astype(x_t.dtype)
    q, k = _qk_norm(p, q, k)
    q = layers.apply_rope(q[:, None], pos[:, None], rope_theta,
                          rotary_dim)[:, 0]
    k = layers.apply_rope(k[:, None], pos[:, None], rope_theta,
                          rotary_dim)[:, 0]
    cache = _cache_insert(cache, k, v)

    size = cache.k.shape[2]
    Hq = q.shape[1]
    Hkv = cache.k.shape[1]
    G = Hq // Hkv
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    # mixed-precision einsums with fp32 accumulation: upcasting the cache
    # (`.astype(f32)`) materializes a full fp32 copy of the KV cache every
    # token — measured at ~50% of the decode memory term (§Perf i7)
    qg = q.reshape(B, Hkv, G, hd)
    s = scale * jnp.einsum("bhgd,bhtd->bhgt", qg, cache.k,
                           preferred_element_type=jnp.float32)
    # valid positions: slot t holds a token iff t < length (linear phase) or
    # always (rolling phase, length > size).  Window masking is implicit in
    # the rolling buffer size.
    t_idx = jnp.arange(size)
    valid = t_idx[None, :] < cache.length[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    p_att = jnp.exp(s - pmax)
    p_att = p_att / jnp.maximum(jnp.sum(p_att, -1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgt,bhtd->bhgd", p_att.astype(cache.v.dtype), cache.v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, Hq, hd).astype(x_t.dtype)
    o = _apply_head_mask(_gate(p, x_t, o), head_mask)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"]).astype(x_t.dtype)
    return out, cache


def attn_decode_pallas(p, x_t, cache: KVCache, *, rope_theta=10000.0,
                       window=None, block_t=256):
    """One-token decode through the Pallas flash-decode kernel."""
    from repro.kernels import ops
    pos = cache.length
    q = jnp.einsum("bd,dhk->bhk", x_t, p["wq"]).astype(x_t.dtype)
    k = jnp.einsum("bd,dhk->bhk", x_t, p["wk"]).astype(x_t.dtype)
    v = jnp.einsum("bd,dhk->bhk", x_t, p["wv"]).astype(x_t.dtype)
    q = layers.apply_rope(q[:, None], pos[:, None], rope_theta)[:, 0]
    k = layers.apply_rope(k[:, None], pos[:, None], rope_theta)[:, 0]
    cache = _cache_insert(cache, k, v)
    # raw token count: the kernel owns the occupancy clamp to the buffer
    o = ops.attn_decode(q, cache.k, cache.v, cache.length,
                        block_t=min(block_t, cache.k.shape[2]))
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"]).astype(x_t.dtype)
    return out, cache
