"""Unified hybrid causal LM driving all assigned architectures.

A model is a cycled ``pattern`` of mixer kinds (any kind registered in
``repro.models.mixers`` — attn / swa / gdn / ssm / rglru / gdn_naive / ...)
plus a per-layer FFN (dense / moe / moe+dense / none).  Layers are grouped
into (pattern, repeats) groups and executed with ``lax.scan`` over stacked
parameters — compile time stays O(pattern) instead of O(n_layers) for the
60-layer archs, and remat wraps each scanned block.

Mixer dispatch is a registry lookup: this module never names a mixer kind.
Adding a kind is one module in ``repro.models.mixers`` implementing the
``SequenceMixer`` protocol; caches are materialized from each mixer's
declarative ``cache_spec`` (see ``cache_specs`` below), which is the same
source of truth the serving engine and the intensity model consume.

Entry points:
  init_lm(key, cfg)                         -> params
  forward_hidden(params, cfg, tokens|embeds)-> (B, T, d) final hidden
  loss_fn(params, cfg, batch)               -> scalar loss, metrics  (chunked CE)
  cache_specs(cfg, batch, max_len)          -> CacheSpec (declarative, stacked)
  init_caches(cfg, batch, max_len)          -> decode caches (per group, stacked)
  prefill(params, cfg, tokens|embeds, caches)-> (last-token logits, caches)
  prefill_chunk(params, cfg, caches, ...)   -> one prompt chunk, resumed from
                                               the caches (no logits)
  prefill_chunk_scan(params, cfg, caches, ..)-> n equal chunks in one scan
  prefill_sample(params, cfg, caches, sampler, sample_fn, ...)
                                            -> final chunk + fused first-token
                                               draw (on-device admit)
  decode_step(params, cfg, token, caches)   -> (logits, caches)
  decode_steps(params, cfg, tokens, caches, k, sampler, sample_fn)
                                            -> k fused decode+sample steps
                                               (one host sync per k tokens)
  decode_counter_names(cfg)                 -> the counters
                                               ``decode_steps(count=True)``
                                               reports per step

The cached paths name their layers for the profiler: each mixer call runs
under ``jax.named_scope("mixer_<kind>")``, each FFN under ``"ffn"``, and
the final norm, head and sampler under ``"head_sample"``.  A scope only
labels the operations (their HLO ``op_name``); the computation is the
same.  A mixer kind may nest scopes of its own (``qnext_moe``:
``moe_router``, ``moe_experts``, ``moe_shared``).

VLM / audio archs: the modality frontend is a stub per the assignment —
``embeds`` (precomputed patch/frame embeddings, (B, T, d_model)) are fed
directly in place of token embeddings.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers, moe
from repro.models.mixers import CacheSpec, get_mixer


def _constrain(x, dp_axes):
    """Pin the batch dim of activations to the DP axes (GSPMD propagation
    otherwise drops batch sharding through gathers/microbatch reshapes)."""
    if dp_axes is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp_axes, *([None] * (x.ndim - 1))))


# ---------------------------------------------------------------- grouping

def build_groups(cfg: ArchConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """[(pattern kinds, repeats)] covering cfg.n_layers."""
    L, P = cfg.n_layers, len(cfg.pattern)
    groups = []
    if L // P:
        groups.append((cfg.pattern, L // P))
    if L % P:
        groups.append((tuple(cfg.pattern[: L % P]), 1))
    return groups


# ---------------------------------------------------------------- init

def _init_layer(key, kind: str, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    p = {"norm1": layers.init_rmsnorm(cfg.d_model),
         "mixer": get_mixer(kind).init_params(ks[0], cfg, dtype)}
    if cfg.ffn != "none":
        p["norm2"] = layers.init_rmsnorm(cfg.d_model)
        if cfg.ffn in ("dense",):
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        if cfg.ffn in ("moe", "moe+dense"):
            p["moe"] = moe.init_moe(ks[2], cfg.d_model, cfg.d_ff,
                                    cfg.moe_experts, dtype)
        if cfg.ffn == "moe+dense":
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model,
                                       cfg.d_ff_dense or cfg.d_ff, dtype)
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_lm(key, cfg: ArchConfig):
    dtype = jnp.dtype(cfg.act_dtype)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": layers.init_embedding(k_embed, cfg.vocab, cfg.d_model,
                                       dtype),
        "final_norm": layers.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                  * cfg.d_model ** -0.5).astype(dtype)}
    groups = build_groups(cfg)
    layer_keys = iter(jax.random.split(k_layers, cfg.n_layers))
    gparams = []
    for kinds, reps in groups:
        per_pos: List[List[Any]] = [[] for _ in kinds]
        for _ in range(reps):
            for i, kind in enumerate(kinds):
                per_pos[i].append(_init_layer(next(layer_keys), kind, cfg,
                                              dtype))
        # stack one position at a time, dropping its per-layer arrays as
        # it goes: at full width, keeping them all alive until the last
        # stack holds twice the weights in device memory
        gparams.append([_stack(per_pos.pop(0)) for _ in kinds])
    params["groups"] = gparams
    return params


# ---------------------------------------------------------------- layer fwd

def _ffn_fwd(cfg: ArchConfig, lp, x, decode: bool):
    if cfg.ffn == "none":
        return x, 0.0
    h = layers.rmsnorm_fwd(lp["norm2"], x, cfg.norm_eps)
    aux = 0.0
    y = 0.0
    if "moe" in lp:
        if decode:
            y = y + moe.moe_decode(lp["moe"], h, top_k=cfg.moe_top_k)
        else:
            ym, aux = moe.moe_fwd(lp["moe"], h, top_k=cfg.moe_top_k,
                                  group_size=cfg.moe_group_size,
                                  capacity_factor=cfg.moe_capacity_factor)
            y = y + ym
    if "mlp" in lp:
        y = y + layers.mlp_fwd(lp["mlp"], h)
    return x + y, aux


def _layer_train(kind, cfg: ArchConfig, lp, x):
    h = layers.rmsnorm_fwd(lp["norm1"], x, cfg.norm_eps)
    x = x + get_mixer(kind).train(lp["mixer"], cfg, h)
    x, aux = _ffn_fwd(cfg, lp, x, decode=False)
    return x, aux


# ---------------------------------------------------------------- train fwd

def forward_hidden(params, cfg: ArchConfig, tokens=None, embeds=None,
                   dp_axes=None):
    """Returns (final hidden (B, T, d), total MoE aux loss)."""
    x = embeds if embeds is not None else layers.embed_fwd(params["embed"],
                                                           tokens)
    x = _constrain(x.astype(jnp.dtype(cfg.act_dtype)), dp_axes)
    aux_total = jnp.float32(0.0)
    groups = build_groups(cfg)
    for (kinds, reps), gp in zip(groups, params["groups"]):

        def block(x, lp_slice, kinds=kinds):
            aux = jnp.float32(0.0)
            for i, kind in enumerate(kinds):
                x, a = _layer_train(kind, cfg, lp_slice[i], x)
                x = _constrain(x, dp_axes)
                aux = aux + a
            return x, aux

        if cfg.remat:
            block = jax.checkpoint(block)

        x, auxs = jax.lax.scan(block, x, gp)
        aux_total = aux_total + jnp.sum(auxs)
    return x, aux_total


def _logits(params, cfg: ArchConfig, h):
    if cfg.tie_embeddings:
        return layers.logits_fwd(params["embed"], h)
    return jax.lax.dot_general(
        h, params["lm_head"]["w"], (((h.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def loss_fn(params, cfg: ArchConfig, batch, *, t_chunk=1024, z_loss=1e-4,
            aux_weight=0.01, dp_axes=None):
    """Chunked-over-T cross entropy (never materializes (B, T, V) fp32)."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    labels = batch["labels"]
    h, aux = forward_hidden(params, cfg, tokens, embeds, dp_axes=dp_axes)
    B, T, _ = h.shape
    tc = min(t_chunk, T)
    n = T // tc

    def chunk_loss(hc, lc):
        logits = _logits(params, cfg, hc)
        return layers.cross_entropy(logits, lc, z_loss=z_loss)

    if n <= 1:
        ce = chunk_loss(h, labels)
    else:
        hc = h[:, : n * tc].reshape(B, n, tc, -1).transpose(1, 0, 2, 3)
        lc = labels[:, : n * tc].reshape(B, n, tc).transpose(1, 0, 2)
        losses = jax.lax.map(jax.checkpoint(lambda args: chunk_loss(*args)),
                             (hc, lc))
        ce = jnp.mean(losses)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------- caches

def cache_specs(cfg: ArchConfig, batch: int, max_len: int) -> CacheSpec:
    """Declarative spec of the full decode-cache pytree, in the stacked
    per-group layout that ``prefill``/``decode_step`` scan over: leaves are
    (repeats, batch, ...).  The serving engine sizes its slot buffers and
    byte budgets from this; ``init_caches`` materializes it."""
    groups_spec = []
    for kinds, reps in build_groups(cfg):
        per_pos = []
        for kind in kinds:
            spec = get_mixer(kind).cache_spec(cfg, batch, max_len)
            per_pos.append(spec.stack(reps).tree)
        groups_spec.append(per_pos)
    return CacheSpec(groups_spec)


def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    """Stacked per-group caches matching the scanned param layout."""
    return cache_specs(cfg, batch, max_len).zeros()


def checkpoint_specs(cfg: ArchConfig, batch: int, max_len: int) -> CacheSpec:
    """Declarative spec of the speculative-decode rollback image, stacked
    like ``cache_specs``.  Built from each mixer's ``checkpoint_spec`` (the
    registry propagates the one-extra-state-copy-per-slot cost to the
    engine, the sharding planner and the intensity model without engine
    edits); for every built-in kind it equals ``cache_specs`` because
    decode mutates each cache leaf destructively."""
    groups_spec = []
    for kinds, reps in build_groups(cfg):
        per_pos = []
        for kind in kinds:
            spec = get_mixer(kind).checkpoint_spec(cfg, batch, max_len)
            per_pos.append(spec.stack(reps).tree)
        groups_spec.append(per_pos)
    return CacheSpec(groups_spec)


# ---------------------------------------------------------------- prefill / decode

def decode_counter_names(cfg: ArchConfig) -> Tuple[str, ...]:
    """The decode counters of ``cfg``'s mixer kinds (``decode_counters``),
    in a fixed order; empty for a model whose kinds count nothing."""
    return tuple(sorted({n for k in cfg.pattern
                         for n in get_mixer(k).decode_counters}))


def _run_cached(params, cfg: ArchConfig, x, caches, mode: str,
                dp_axes=None, valid_len=None, live=None):
    """The layers over ``caches``.  With ``live`` (decode only) the kinds
    that have decode counters report them over the live rows, summed over
    layers, and a third value {name: int32} is returned.

    Prefill and chunk scan each group's stacked caches as ``xs`` and
    return the new ones as ``ys``.  Decode carries them through the layer
    scan instead and each mixer updates layer r of its stack in place
    (``decode_stacked``): a fresh ``ys`` would be copied whole into the
    caller's carry (``decode_steps``' scan) at every step."""
    decode = mode == "decode"
    groups = build_groups(cfg)
    new_caches = []
    names = decode_counter_names(cfg) if live is not None else ()
    counts = {n: jnp.zeros((), jnp.int32) for n in names}
    for (kinds, reps), gp, gc in zip(groups, params["groups"], caches):

        def block(carry, sl, kinds=kinds):
            if decode:
                x, counts, c_slice = carry
                r, lp_slice = sl
            else:
                x, counts = carry
                lp_slice, c_slice = sl
            new_c = []
            for i, kind in enumerate(kinds):
                lp = lp_slice[i]
                mixer = get_mixer(kind)
                h = layers.rmsnorm_fwd(lp["norm1"], x, cfg.norm_eps)
                with jax.named_scope(f"mixer_{kind}"):
                    if mode == "prefill":
                        mix, nc = mixer.prefill(lp["mixer"], cfg, h,
                                                c_slice[i])
                    elif mode == "chunk":
                        mix, nc = mixer.prefill_chunk(lp["mixer"], cfg, h,
                                                      c_slice[i],
                                                      valid_len=valid_len)
                    else:
                        mix, nc, c = mixer.decode_stacked(
                            lp["mixer"], cfg, h, c_slice[i], r, live)
                        if c:
                            counts = {n: counts[n] + c.get(n, 0)
                                      for n in counts}
                x = x + mix
                with jax.named_scope("ffn"):
                    x, _ = _ffn_fwd(cfg, lp, x, decode=decode)
                x = _constrain(x, dp_axes)
                new_c.append(nc)
            if decode:
                return (x, counts, new_c), None
            return (x, counts), new_c

        if decode:
            (x, counts, ncs), _ = jax.lax.scan(
                block, (x, counts, list(gc)),
                (jnp.arange(reps, dtype=jnp.int32), gp))
        else:
            (x, counts), ncs = jax.lax.scan(block, (x, counts), (gp, gc))
        new_caches.append(ncs)
    return (x, new_caches) if live is None else (x, new_caches, counts)


def prefill(params, cfg: ArchConfig, caches, tokens=None, embeds=None,
            dp_axes=None):
    """Process the prompt; returns (last-token logits (B, V) fp32, caches)."""
    x = embeds if embeds is not None else layers.embed_fwd(params["embed"],
                                                           tokens)
    x = _constrain(x.astype(jnp.dtype(cfg.act_dtype)), dp_axes)
    x, caches = _run_cached(params, cfg, x, caches, "prefill",
                            dp_axes=dp_axes)
    x = layers.rmsnorm_fwd(params["final_norm"], x[:, -1], cfg.norm_eps)
    return _logits(params, cfg, x), caches


def prefill_chunk(params, cfg: ArchConfig, caches, tokens=None, embeds=None,
                  dp_axes=None, valid_len=None):
    """Process one prompt chunk *continuing from* ``caches``.

    Unlike ``prefill`` this never computes logits (interior chunks don't
    need them — the lm head on every chunk would be pure waste) and every
    mixer resumes from its cache state (attention continues RoPE/visibility
    at the cached position via ``prefill_chunk``).  Returns (final hidden
    (B, C, d), caches); feed the last chunk to ``prefill_sample`` for the
    logits + fused first-token draw.

    ``valid_len`` (optional scalar or per-row (B,) int32) marks a
    *ragged* chunk padded to its static size C: only the first valid_len
    tokens of each row are real.  Every
    mixer masks the padding so the returned caches are exactly those of
    the unpadded prefix — one fixed-size masked program replaces the
    whole family of tail-sized programs.  Hidden rows at padded positions
    are garbage; callers must only read rows < valid_len.
    """
    x = embeds if embeds is not None else layers.embed_fwd(params["embed"],
                                                           tokens)
    x = _constrain(x.astype(jnp.dtype(cfg.act_dtype)), dp_axes)
    return _run_cached(params, cfg, x, caches, "chunk", dp_axes=dp_axes,
                       valid_len=valid_len)


def prefill_chunk_scan(params, cfg: ArchConfig, caches, tokens=None,
                       embeds=None, dp_axes=None, valid_lens=None):
    """``lax.scan`` of ``prefill_chunk`` over equal-size prompt chunks.

    tokens: (B, n, C) int32 / embeds: (B, n, C, d) — n chunks of C tokens
    each, processed in order with the caches threaded through the scan, so
    one compiled program covers n chunks of prefill (the serving executor
    compiles one such program per scan length n).  Returns caches.

    ``valid_lens`` (optional (n,) or (n, B) int32): per-chunk valid-token
    counts for ragged prompts padded into the fixed (n, C) layout — a
    chunk with valid_lens[i] == 0 is a pure no-op on the caches, so one
    scan shape covers any number of trailing placeholder chunks.  The
    (n, B) form carries a *per-row* count per scan step (the batched
    multi-prompt staging path): the scan unstacks the leading axis, so
    each step's chunk sees a (B,) valid_len vector.
    """
    xs = tokens if tokens is not None else embeds
    xs = jnp.moveaxis(xs, 1, 0)                    # (n, B, C[, d])
    if valid_lens is not None:
        xs = (xs, jnp.asarray(valid_lens, jnp.int32))

    def body(caches, inp):
        chunk, vl = inp if valid_lens is not None else (inp, None)
        if tokens is not None:
            _, caches = prefill_chunk(params, cfg, caches, tokens=chunk,
                                      dp_axes=dp_axes, valid_len=vl)
        else:
            _, caches = prefill_chunk(params, cfg, caches, embeds=chunk,
                                      dp_axes=dp_axes, valid_len=vl)
        return caches, None

    caches, _ = jax.lax.scan(body, caches, xs)
    return caches


def prefill_sample(params, cfg: ArchConfig, caches, sampler, sample_fn,
                   tokens=None, embeds=None, dp_axes=None, valid_len=None):
    """Final prompt chunk with the fused admit head: one dispatch computes
    the chunk, the last-token logits and the first sampled token, and
    advances the sampler state (key split, budget decrement, EOS/budget
    done flag) — no host ``sample_np`` draw on the admit hot path.

    ``sampler``/``sample_fn`` as in ``decode_steps`` (the serving executor
    passes a 1-row ``repro.serving.sampling`` state and its ``sample``).
    ``valid_len`` marks a ragged final chunk: the admit logits come from
    the last *valid* position, not the last row of the padded chunk.  It
    may be a per-row (B,) vector (batched multi-prompt admit) — each row
    reads its own last valid position; a valid_len=0 placeholder row is
    clamped to position 0 (its token is garbage and the caller's admit
    mask discards it).  Returns (token (B,), sampler, caches).
    """
    x, caches = prefill_chunk(params, cfg, caches, tokens=tokens,
                              embeds=embeds, dp_axes=dp_axes,
                              valid_len=valid_len)
    if valid_len is None:
        h_last = x[:, -1]
    else:
        vl = jnp.asarray(valid_len, jnp.int32)
        if vl.ndim == 0:
            h_last = jax.lax.dynamic_slice_in_dim(x, vl - 1, 1,
                                                  axis=1)[:, 0]
        else:
            idx = jnp.maximum(vl - 1, 0)[:, None, None]        # (B, 1, 1)
            h_last = jnp.take_along_axis(x, idx, axis=1)[:, 0]
    with jax.named_scope("head_sample"):
        h = layers.rmsnorm_fwd(params["final_norm"], h_last, cfg.norm_eps)
        tok, sampler = sample_fn(sampler, _logits(params, cfg, h))
    return tok.astype(jnp.int32), sampler, caches


def decode_step(params, cfg: ArchConfig, tokens_t, caches, dp_axes=None,
                live=None):
    """One decode step. tokens_t: (B,) int32. Returns (logits (B, V), caches);
    with ``live`` ((B,) bool) also the decode counters over the live rows
    ({name: int32}, see ``decode_counter_names``)."""
    x = layers.embed_fwd(params["embed"], tokens_t)
    x = _constrain(x.astype(jnp.dtype(cfg.act_dtype)), dp_axes)
    out = _run_cached(params, cfg, x, caches, "decode", dp_axes=dp_axes,
                      live=live)
    x, caches = out[:2]
    with jax.named_scope("head_sample"):
        x = layers.rmsnorm_fwd(params["final_norm"], x, cfg.norm_eps)
        logits = _logits(params, cfg, x)
    return (logits, caches) if live is None else (logits, caches, out[2])


def _greedy_sample(sampler, logits):
    """Default on-device sampler: argmax, state untouched (never done)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), sampler


def decode_steps(params, cfg: ArchConfig, tokens, caches, k: int,
                 sampler=None, sample_fn=None, dp_axes=None, count=False):
    """``k`` fused decode+sample steps in one ``lax.scan``.

    This is the device-resident decode hot loop: the recurrent state,
    the sampled tokens and the finished flags all stay on device for
    ``k`` consecutive tokens, so a caller (the serving engine) syncs
    with the host once per ``k`` tokens instead of once per token —
    the serving-layer analogue of the paper's keep-state-resident
    argument, and the building block for speculative / multi-device
    decode.

    ``sampler`` is any pytree carrying a ``"done"`` (B,) bool leaf;
    ``sample_fn(sampler, logits) -> ((B,) int32 tokens, sampler)``
    draws the next token batch and advances the done flags (see
    ``repro.serving.sampling.sample``).  Omitting both gives greedy
    argmax with no termination.  Slots whose ``done`` flag is set
    before a step are masked: they re-feed their last token (their
    slot cache advances with garbage, which is fine — admit rewrites
    the whole slot) and that step is marked invalid for them.

    Returns ``(toks (k, B) int32, valid (k, B) bool, tokens (B,),
    caches, sampler)`` — ``toks[j]`` is the token batch from step j,
    ``valid[j]`` whether each slot was still live going into step j.
    With ``count`` a sixth value follows: {name: (k,) int32}, each step's
    decode counters over the slots live going into it
    (``decode_counter_names``).
    """
    if sample_fn is None:
        sample_fn = _greedy_sample
    if sampler is None:
        sampler = {"done": jnp.zeros(tokens.shape, bool)}

    def step(carry, _):
        toks, cs, st = carry
        live = ~st["done"]
        if count:
            logits, cs, counts = decode_step(params, cfg, toks, cs,
                                             dp_axes=dp_axes, live=live)
        else:
            logits, cs = decode_step(params, cfg, toks, cs, dp_axes=dp_axes)
        with jax.named_scope("head_sample"):
            nxt, st = sample_fn(st, logits)
            nxt = jnp.where(live, nxt, toks)
        out = (nxt, live, counts) if count else (nxt, live)
        return (nxt, cs, st), out

    (tokens, caches, sampler), out = jax.lax.scan(
        step, (tokens, caches, sampler), None, length=k)
    return (*out[:2], tokens, caches, sampler, *out[2:])


def verify_steps(params, cfg: ArchConfig, draft_params, draft_cfg,
                 tokens, drafts, caches, draft_caches, sampler, sample_fn,
                 dp_axes=None):
    """Speculative verify: score K drafted tokens per slot against the
    target model and commit per-slot state only for emitted positions.

    One teacher-forced ``lax.scan`` over K+1 positions feeds the slot's
    last emitted token followed by its K draft tokens through
    ``decode_step`` — the *same* arithmetic as non-speculative decode, so
    every emitted token (and the state it leaves behind) is bitwise what
    the plain tick would have produced.  (A chunkwise-prefill verify
    would be one parallel program, but GDN's chunkwise UT transform is a
    numerically different factorization from the fused decode step, so
    it could never be bitwise-lossless; ``core.gdn.prefill_sequential``
    is the existing precedent for scanning the decode step instead.)

    Position j samples the target token t_j with the slot's own key
    stream (``sample_fn(sampler, logits, active)`` — a masked sampler
    like ``sampling.sample_where`` that only advances rows where
    ``active``); the slot keeps accepting while t_j equals the draft
    token it is about to feed next.  Because draft and target share the
    (seed, rid)-folded key at every position, coupled rejection sampling
    collapses to that token-equality check for greedy *and* stochastic
    slots.  A slot emits m ∈ {1..K+1} tokens (its correction or bonus
    token last) and zero if it entered the tick done.

    Rollback is the conditional commit: the scan carries a run-ahead
    cache tree *and* a committed tree, selecting run-ahead into the
    commit only at active positions, so a slot whose entire draft is
    rejected ends the tick with bitwise-unchanged committed state — no
    replay pass.  ``draft_params``/``draft_caches`` run the same inputs
    through the draft model so its per-slot state tracks the emitted
    prefix (the committed draft tree is what the next draft pass starts
    from).

    tokens: (B,) last emitted per slot; drafts: (K, B) int32 (K may be
    0: a verify-only tick degenerates to one plain decode step).
    Returns ``(toks (K+1, B), valid (K+1, B), tokens (B,), caches,
    draft_caches, run, draft_run, sampler)`` where ``caches`` /
    ``draft_caches`` are the committed trees and ``run`` / ``draft_run``
    the run-ahead finals (the executor keeps them as the next tick's
    checkpoint scratch buffers).
    """
    k = drafts.shape[0]
    inp = jnp.concatenate([tokens[None], drafts.astype(jnp.int32)], axis=0)
    # token position j must match the input fed at j+1 to keep accepting;
    # the last position has no successor (its emission is the free bonus
    # token when all K drafts were accepted)
    nxt = jnp.concatenate([drafts.astype(jnp.int32),
                           jnp.full_like(tokens[None], -1)], axis=0)

    def commit_where(emit, new, old):
        def sel(n, o):
            m = emit.reshape((1, emit.shape[0]) + (1,) * (n.ndim - 2))
            return jnp.where(m, n, o)
        return jax.tree.map(sel, new, old)

    def step(carry, xs):
        run, drun, com, dcom, st, acc, last = carry
        tin, d = xs
        active = acc & ~st["done"]
        logits, run = decode_step(params, cfg, tin, run, dp_axes=dp_axes)
        _, drun = decode_step(draft_params, draft_cfg, tin, drun,
                              dp_axes=dp_axes)
        tok, st = sample_fn(st, logits, active)
        tok = jnp.where(active, tok.astype(jnp.int32), last)
        com = commit_where(active, run, com)
        dcom = commit_where(active, drun, dcom)
        # stop at the first mismatch — and at EOS/budget exhaustion, even
        # when the draft guessed the EOS token (the slot is done; feeding
        # further drafts would emit past its end)
        acc = active & (tok == d) & ~st["done"]
        return (run, drun, com, dcom, st, acc, tok), (tok, active)

    init = (caches, draft_caches, caches, draft_caches, sampler,
            jnp.ones(tokens.shape, bool), tokens)
    (run, drun, com, dcom, sampler, _, last), (toks, valid) = jax.lax.scan(
        step, init, (inp, nxt))
    return toks, valid, last, com, dcom, run, drun, sampler
