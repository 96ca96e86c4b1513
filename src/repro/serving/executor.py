"""Device executor: the serving engine's jitted donated-buffer programs.

This is the device half of the scheduler/executor split.  Everything that
touches an accelerator buffer lives here; everything that touches a
``Request`` lives in ``repro.serving.scheduler``.  The executor owns

  * the **slot buffers** — every layer's recurrent state / KV cache with a
    leading slot axis, the per-slot sampler arrays and the per-slot last
    tokens, all donated through every tick so XLA updates them in place
    (the TPU analogue of the paper's BRAM-resident state);
  * the **staging ring** — under the default **batched** staging
    (``prefill_batching``), ONE ``(staging_depth, ...)`` cache pytree
    whose rows are the staged prompts, plus a ``staging_depth``-row
    sampler state and per-row first tokens: every tick fuses ALL staged
    prompts into at most one fixed-shape ``(staging_depth,
    _MAX_SCAN_CHUNKS, prefill_chunk)`` scan + one admit dispatch with
    per-row ``valid_lens`` (rows/chunks past a prompt's end are bitwise
    no-op placeholders), and finished rows enter their slots through ONE
    multi-row scatter — dispatches per tick are O(1) in queue depth.
    The per-prompt fallback (pow2 plans, MoE FFNs, mixer kinds without
    per-row masks) keeps ``staging_depth`` single-sequence cache pytrees
    plus 1-row sampler states that chunked prefill streams into while
    the resident slots keep decoding, each scattered into a real slot
    only once its staging completes (the serving-layer version of the
    paper's prepare/compute/store overlap; a ring deeper than 1 lets
    several queued requests prefill ahead under saturation);
  * the **programs** — one jitted, donated program per static shape:
    - ``decode(k)``: the ``lm.decode_steps`` fused decode+sample scan, one
      program per bucketed tick length k (budget-aware ticks pick the
      smallest bucket covering the max remaining per-slot budget);
    - ``stage_chunk_scan`` / ``stage_chunk`` / ``stage_admit``: chunked
      prefill into a staging cache.  Under the default **masked planner**
      (``plan_mode="masked"``) a prompt dispatches at most TWO distinct
      program shapes: full chunks run m-at-a-time under one ``lax.scan``
      (one m per prompt, trailing slots masked out with per-chunk
      ``valid_len`` = 0), and the ragged tail is ONE fixed-size
      ``prefill_chunk``-sized chunk whose padded positions are masked by
      the per-token validity threading (kernels zero k/v/β/log-gate, the
      rolling KV insert drops padded slots) — the final state is provably
      that of the unpadded prompt, and the admit draw reads the logits of
      the last *valid* token.  ``plan_mode="pow2"`` keeps the PR-3
      power-of-two tail decomposition (no padding, no masking) as the
      comparison baseline.  The tail/admit program fuses the first-token
      draw on device (``lm.prefill_sample``), so admit never ships logits
      to the host; ring buffers share programs (same shapes);
    - ``scatter(slot, buf)``: one donated ``dynamic_update_slice`` over
      the whole staging pytree + sampler row + first token into ``slot``.

  Every program is compiled lazily on first use and cached by its static
  shape; ``compiled_programs()`` reports the live cache per family.  The
  masked planner bounds the prefill families at O(1) shapes per prompt
  (≤ _MAX_SCAN_CHUNKS scan lengths + 1 admit shape ever); the pow2
  baseline needs O(log chunk) tail programs on top.  Every program of a
  family shares one name, ``serve_<family>`` (``decode``,
  ``prefill_scan``, ``prefill_chunk``, ``admit``, ``scatter``,
  ``gather``, ``staging_zeros`` and the speculative ``draft``,
  ``verify``, ``draft_prefill``), so a device trace shows its XLA module
  as ``jit_serve_<family>`` whatever the static shape.  The decode
  dispatch and every host read of a program's result run under
  ``serve:`` spans (``repro.serving.spans``) carrying the scheduler's
  tick.

**Mesh sharding.**  With ``mesh`` set (a ``("data", "model")`` device
mesh, see ``launch/mesh.py``), every buffer above is allocated with a
``NamedSharding`` derived from the existing sharding rules in
``parallel/sharding.py``: the slot axis on "data" (slot-axis data
parallelism), GDN/SSM state heads and the attention KV context dim on
"model" (the paper's 2→16 head-parallelism design axis scaled out over
devices), params TP-sharded by ``params_specs``, sampler rows and last
tokens slot-sharded on "data".  Every program is compiled with explicit
``in_shardings``/``out_shardings`` under that mesh, so the whole k-step
tick stays ONE SPMD program — there is no per-token cross-device sync
beyond the collectives GSPMD inserts inside it, and donated buffers keep
their placement across ticks.
"""
from __future__ import annotations

import functools
import time
import warnings
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.serving import sampling, spans


class PlanStep(NamedTuple):
    """One prefill dispatch.

    kind   : "scan" (m full chunks under one lax.scan) | "chunk" (one
             interior tail sub-chunk, pow2 mode only) | "admit" (final
             chunk + fused first-token draw).
    size   : the program's static shape — chunk count m for "scan",
             token count for "chunk"/"admit".
    tokens : valid prompt tokens consumed by this step (== the slice the
             scheduler feeds it; < the program capacity when masked).
    valid  : per-token validity threaded into the programs — "scan": an
             (m,)-tuple of per-chunk valid lengths (trailing 0-entries
             are placeholder chunks), "admit": the valid token count of
             the fixed-size tail; None = unmasked (pow2 baseline).
    """
    kind: str
    size: int
    tokens: int
    valid: Optional[Any] = None


# cap on chunks per scan dispatch: a single scan step is one program on the
# tick thread, so unbounded m would stall resident decode slots for nearly
# the whole prompt — bounding it keeps the overlap granular (and bounds
# the compile cache to scan programs of m in 1..4)
_MAX_SCAN_CHUNKS = 4


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1)


class SwappedState(NamedTuple):
    """Host-side image of one request's device residency — what slot
    oversubscription pages out.  Every mixer's recurrent state is a
    constant-shape block described by ``cache_spec``, so the image is a
    fixed-size record, not a paged-KV block table:

    caches  : numpy pytree of ``(repeats, 1, ...)`` leaves — recurrent
              state + rolling KV window + position meta of every layer
              group, in exactly the staging layout the slot scatter
              admits from;
    sampler : 1-row sampler state (PRNG key mid-stream, remaining
              budget, done flag — see ``sampling.slice_row``);
    token   : (1,) int32 — the last emitted token, the next decode
              input.
    """
    caches: Any
    sampler: Dict[str, np.ndarray]
    token: np.ndarray

    @property
    def nbytes(self) -> int:
        """Bytes this image moves across the host boundary per swap."""
        leaves = (jax.tree.leaves(self.caches)
                  + list(self.sampler.values()) + [self.token])
        return int(sum(np.asarray(x).nbytes for x in leaves))


class PendingSwap:
    """Ledger entry for one in-flight asynchronous swap-out: the gathered
    device arrays (staging layout — they ARE the gather buffer, pinned
    alive by this record while ``copy_to_host_async`` drains them to host
    in the background) plus the gather-ring ticket ``buf`` that bounds
    how many drains may be outstanding.  ``DeviceExecutor.harvest``
    materializes the record into a ``SwappedState`` and only then
    returns the ticket — a draining buffer is never reused pre-harvest.
    """

    __slots__ = ("buf", "st", "row", "tok", "nbytes")

    def __init__(self, buf: int, st, row, tok):
        self.buf = buf
        self.st, self.row, self.tok = st, row, tok
        self.nbytes = int(sum(x.nbytes for x in
                              jax.tree.leaves((st, row, tok))))
        # start the background D2H drain; the later harvest device_get
        # then finds the host copy already (or mostly) resident
        for leaf in jax.tree.leaves((st, row, tok)):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()

    def ready(self) -> bool:
        """True when every gathered array's transfer has completed (the
        harvest device_get will not block).  Conservatively True when
        the backend lacks ``is_ready`` — the harvest still overlapped at
        least one full tick of compute."""
        return all(leaf.is_ready() for leaf in
                   jax.tree.leaves((self.st, self.row, self.tok))
                   if hasattr(leaf, "is_ready"))


def _gather_fn(caches, sampler, tokens, slot):
    """Slot gather — the inverse of ``_scatter_fn``: slice slot
    ``slot``'s cache column, sampler row and last token out of the slot
    buffers into the staging layout, and freeze the vacated slot's done
    flag so the remaining ticks treat it as inert.  The caches are
    read-only; only the sampler is donated (for the freeze)."""
    st = jax.tree.map(
        lambda f: jax.lax.dynamic_slice_in_dim(f, slot, 1, axis=1),
        caches)
    row = sampling.slice_row(sampler, slot)
    tok = jax.lax.dynamic_slice(tokens, (slot,), (1,))
    return st, row, tok, sampling.freeze_slot(sampler, slot)


def _bgather_fn(bstaging, bsampler, btoks, row):
    """Staging-row gather for the batched ring: slice row ``row``'s
    staged caches, admit-advanced sampler row and first token into the
    (repeats, 1, ...) staging layout ``restore_slot`` re-admits from.
    Pure read — the row is release-zeroed by the next multi-row
    scatter (the scheduler marks it dirty)."""
    st = jax.tree.map(
        lambda f: jax.lax.dynamic_slice_in_dim(f, row, 1, axis=1),
        bstaging)
    return (st, sampling.slice_row(bsampler, row),
            jax.lax.dynamic_slice(btoks, (row,), (1,)))


def _bscatter_fn(caches, sampler, tokens, bstaging, bsampler, btoks,
                 slots, release):
    """Multi-row scatter: admit every finished staging row in ONE
    dispatch.  ``slots`` is a (D,) int32 map from staging row to target
    slot, with the sentinel ``max_slots`` (out of bounds, dropped by
    ``mode="drop"``) for rows not admitting; ``release`` is a (D,) bool
    mask of rows to zero afterwards (admitted rows plus rows whose
    request finished at admit) so a released row is clean for the next
    ``bstage_begin``.  Distinct real slots per call is the scheduler's
    invariant — the scatter never sees duplicates."""
    caches = jax.tree.map(
        lambda f, o: f.at[:, slots].set(o.astype(f.dtype), mode="drop"),
        caches, bstaging)
    sampler = {
        k: v.at[slots].set(bsampler[k].astype(v.dtype), mode="drop")
        for k, v in sampler.items()}
    tokens = tokens.at[slots].set(btoks.astype(tokens.dtype), mode="drop")
    d = release.shape[0]
    bstaging = jax.tree.map(
        lambda o: jnp.where(release.reshape((1, d) + (1,) * (o.ndim - 2)),
                            jnp.zeros_like(o), o),
        bstaging)
    return caches, sampler, tokens, bstaging


def _scatter_fn(caches, sampler, tokens, staging, row, tok, slot):
    """Write the staging cache pytree, sampler row and first token into
    slot ``slot``.  Cache leaves are (repeats, slots, ...) vs
    (repeats, 1, ...); ``slot`` is traced so the whole-pytree scatter
    compiles once and runs in place (donated)."""
    caches = jax.tree.map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), slot, axis=1),
        caches, staging)
    sampler = {
        k: jax.lax.dynamic_update_slice_in_dim(
            v, row[k].astype(v.dtype), slot, axis=0)
        for k, v in sampler.items()}
    tokens = jax.lax.dynamic_update_slice(
        tokens, tok.astype(tokens.dtype), (slot,))
    return caches, sampler, tokens


class _Program:
    """One jitted serving program.  Its first call compiles it (or loads
    it from the persistent cache) under a ``serve:compile`` span."""

    __slots__ = ("fn", "name", "warm")

    def __init__(self, fn, name: str):
        self.fn, self.name, self.warm = fn, name, False

    def __call__(self, *args):
        if self.warm:
            return self.fn(*args)
        with spans.span("compile", program=self.name):
            out = self.fn(*args)
        self.warm = True
        return out


class DeviceExecutor:
    """Owns the device buffers and jitted programs of one decode engine."""

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int,
                 max_len: int, decode_block: int, prefill_chunk: int = 16,
                 mesh: Optional[Mesh] = None, staging_depth: int = 2,
                 plan_mode: str = "masked",
                 prefill_batching: Optional[bool] = None,
                 draft_cfg: Optional[ArchConfig] = None, draft_params=None,
                 k_draft: int = 4, async_paging: bool = False,
                 gather_ring: int = 2):
        if staging_depth < 1:
            raise ValueError(
                f"staging_depth must be >= 1, got {staging_depth}")
        if gather_ring < 1:
            raise ValueError(
                f"gather_ring must be >= 1, got {gather_ring}")
        if plan_mode not in ("masked", "pow2"):
            raise ValueError(f"plan_mode must be 'masked' or 'pow2', "
                             f"got {plan_mode!r}")
        # explicit validation — prefill_chunk is any size >= 1 (the masked
        # planner never assumes a power of two), but it must fit the
        # context buffers: a silently-clamped over-long chunk would hide a
        # misconfiguration
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if prefill_chunk > max_len:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} exceeds max_len={max_len}: "
                f"a prefill chunk can never hold more tokens than the "
                f"context buffers — lower prefill_chunk or raise max_len")
        if plan_mode == "masked":
            # masked plans need every mixer kind in the pattern to
            # implement the per-token validity mask; a kind registered
            # without it (third-party mixers) still serves — it just
            # falls back to the pow2 tail plans and pays the larger
            # compile cache
            from repro.models.mixers import get_mixer
            unsupported = sorted({k for k in cfg.pattern
                                  if not get_mixer(k)
                                  .supports_ragged_prefill})
            if unsupported:
                warnings.warn(
                    f"mixer kind(s) {unsupported} do not implement "
                    f"ragged (valid_len-masked) prefill chunks — falling "
                    f"back to plan_mode='pow2'; set "
                    f"supports_ragged_prefill = True after masking "
                    f"prefill_chunk to get fixed-shape plans",
                    RuntimeWarning)
                plan_mode = "pow2"
        # batched multi-prompt prefill: fuse every staged prompt into ONE
        # fixed-shape program per dispatch (per-row valid_lens; rows past
        # a prompt's end are bitwise no-op placeholder chunks).  Auto
        # (None) turns it on whenever it is provably bitwise-safe:
        #   * masked plans only — batching IS per-row masking;
        #   * every mixer kind must accept a per-row (B,) valid_len
        #     (supports_batched_ragged_prefill);
        #   * no MoE FFN: moe_fwd's expert-capacity queue is a cumsum
        #     over the whole (rows x tokens) dispatch group, so batched
        #     rows would compete for capacity and diverge bitwise from
        #     per-prompt dispatch.
        # An explicit True warns and falls back when a gate fails.
        batching_blocked = None
        if plan_mode != "masked":
            batching_blocked = ("batched staging rides on masked "
                                "(valid_len) chunks; plan_mode is "
                                f"{plan_mode!r}")
        elif cfg.ffn in ("moe", "moe+dense"):
            batching_blocked = (
                "MoE expert-capacity dispatch couples rows within a "
                "batch (cumsum queue positions over the whole group), "
                "so batched prefill cannot be bitwise-identical to "
                "per-prompt dispatch")
        else:
            from repro.models.mixers import get_mixer
            unbatched = sorted({k for k in cfg.pattern
                                if not get_mixer(k)
                                .supports_batched_ragged_prefill})
            if unbatched:
                batching_blocked = (
                    f"mixer kind(s) {unbatched} do not support per-row "
                    f"(B,) valid_len prefill chunks (set "
                    f"supports_batched_ragged_prefill = True after "
                    f"generalizing the mask)")
        if prefill_batching and batching_blocked:
            warnings.warn(f"prefill_batching disabled: {batching_blocked}",
                          RuntimeWarning)
        self.prefill_batching = (batching_blocked is None
                                 if prefill_batching is None
                                 else bool(prefill_batching)
                                 and batching_blocked is None)
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.decode_block = decode_block
        self.mesh = mesh
        self.staging_depth = staging_depth
        self.plan_mode = plan_mode
        # chunks scatter into rolling KV buffers, whose size is
        # min(window, max_len) — one chunk must not wrap a buffer, so the
        # chunk is clamped to the smallest rolling window (documented
        # invariant of attn_prefill_chunk, checked there too)
        limit = min(max_len, cfg.window) if cfg.window else max_len
        self.prefill_chunk = min(prefill_chunk, limit)

        # spec-driven slot buffers: shapes, dtypes and byte budgets all
        # come from the mixers' declarative cache specs
        self.spec = lm.cache_specs(cfg, max_slots, max_len)
        slot_spec = lm.cache_specs(cfg, 1, max_len)
        self.state_bytes_per_slot = slot_spec.state_bytes
        self.window_bytes_per_slot = slot_spec.window_bytes
        self.cache_bytes = self.spec.nbytes
        # spec-derived swap budget: what one swapped request moves across
        # the host boundary each direction (cache column + sampler row +
        # last token) — benchmarks report swap µs/MB against this
        samp1 = jax.eval_shape(lambda: sampling.init_state(1))
        self.swap_bytes_per_slot = slot_spec.nbytes + int(sum(
            np.dtype(x.dtype).itemsize * int(np.prod(x.shape))
            for x in jax.tree.leaves(samp1))) + 4

        self._build_shardings(params)
        self.params = (params if mesh is None else
                       jax.device_put(params, self._sh_params))
        self.caches = self._zeros(self.spec, self._sh_caches)
        self.tokens = self._put(jnp.zeros((max_slots,), jnp.int32),
                                self._sh_tokens)
        self.sampler = self._put(sampling.init_state(max_slots),
                                 self._sh_sampler)

        # ---- speculative decode (draft model slot + rollback buffers) --
        # The swap image (swap_bytes_per_slot) deliberately excludes ALL
        # of the buffers below: draft caches are rebuilt from the consumed
        # token stream at swap-in (draft_prefill_slot) and checkpoints are
        # scratch that never survives a verify boundary, so a speculative
        # engine's swapped state stays interchangeable with a
        # non-speculative engine's.
        self.speculative = draft_cfg is not None
        self.k_draft = k_draft
        if self.speculative:
            if k_draft < 1:
                raise ValueError(f"k_draft must be >= 1, got {k_draft}")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft model must share the target vocab "
                    f"({draft_cfg.vocab} != {cfg.vocab}) — draft proposals "
                    f"are token ids the target verifies")
            from repro.models.mixers import get_mixer
            unsupported = sorted({k for k in draft_cfg.pattern
                                  if not get_mixer(k)
                                  .supports_ragged_prefill})
            if unsupported:
                raise ValueError(
                    f"draft mixer kind(s) {unsupported} do not support "
                    f"ragged (valid_len-masked) prefill chunks — the "
                    f"draft state rebuild at slot activation runs one "
                    f"fixed-shape masked chunk scan")
            self.draft_cfg = draft_cfg
            # rollback images, straight from the mixers' declarative
            # checkpoint specs (default: one full extra state copy per
            # slot); the registry propagates any narrower per-kind
            # declaration here, to the sharding planner and to the
            # intensity model without engine edits
            self.ckpt_spec = lm.checkpoint_specs(cfg, max_slots, max_len)
            self.dspec = lm.cache_specs(draft_cfg, max_slots, max_len)
            self.dckpt_spec = lm.checkpoint_specs(draft_cfg, max_slots,
                                                  max_len)
            self.checkpoint_bytes_per_slot = lm.checkpoint_specs(
                cfg, 1, max_len).nbytes
            self.draft_bytes_per_slot = (
                lm.cache_specs(draft_cfg, 1, max_len).nbytes
                + lm.checkpoint_specs(draft_cfg, 1, max_len).nbytes)
            self.speculative_bytes = (self.ckpt_spec.nbytes
                                      + self.dspec.nbytes
                                      + self.dckpt_spec.nbytes)
            if mesh is None:
                self._sh_dparams = self._sh_dcaches = None
                self._sh_ckpt = self._sh_dckpt = None
            else:
                from repro.parallel import sharding as rules
                self._sh_ckpt = rules.make_shardings(
                    mesh, rules.checkpoint_specs(
                        cfg, mesh, self.ckpt_spec.shape_dtype(), max_slots))
                self._sh_dcaches = rules.make_shardings(
                    mesh, rules.slot_specs(draft_cfg, mesh,
                                           self.dspec.shape_dtype(),
                                           max_slots))
                self._sh_dckpt = rules.make_shardings(
                    mesh, rules.checkpoint_specs(
                        draft_cfg, mesh, self.dckpt_spec.shape_dtype(),
                        max_slots))
                self._sh_dparams = (
                    self._sh_params if draft_params is params else
                    rules.make_shardings(
                        mesh, rules.params_specs(draft_cfg, draft_params,
                                                 False, mesh)))
            self.draft_params = (
                self.params if draft_params is params else
                (draft_params if mesh is None else
                 jax.device_put(draft_params, self._sh_dparams)))
            self.dcaches = self._zeros(self.dspec, self._sh_dcaches)
            self.ckpt = self._zeros(self.ckpt_spec, self._sh_ckpt)
            self.dckpt = self._zeros(self.dckpt_spec, self._sh_dckpt)
            # draft prompt-prefill geometry: one fixed (1, n, C) masked
            # chunk scan covers any consumed-token count <= max_len, with
            # the SAME chunk size as the target's staged prefill so a
            # self-draft's rebuilt state hits the same chunk boundaries
            dlimit = (min(max_len, draft_cfg.window) if draft_cfg.window
                      else max_len)
            self._dchunk = min(self.prefill_chunk, dlimit)
            self._dchunks = -(-max_len // self._dchunk)
            self._draft_p: Dict[int, object] = {}
            self._verify_p: Dict[int, object] = {}
            self._dprefill_p = None

        # staging ring (prefill overlap targets); the sampler rows are
        # produced by the fused admit program, not materialized up front
        self._staging_zeros = self._jit(
            "staging_zeros", lambda: lm.init_caches(cfg, 1, max_len),
            out_sh=self._sh_staging)
        self.staging: List[Any] = [self._staging_zeros()
                                   for _ in range(staging_depth)]
        self._staging_clean = [True] * staging_depth
        self._staging_args: List[Optional[tuple]] = [None] * staging_depth
        self.staging_row: List[Any] = [None] * staging_depth
        self.staging_tok: List[Optional[jax.Array]] = [None] * staging_depth

        # lazily-built program caches, keyed by static shape
        # (+ masked flag for the prefill families — a masked program takes
        # the validity array as an extra operand)
        self._decode_p: Dict[int, object] = {}
        self._scan_p: Dict[Tuple[int, bool, bool], object] = {}
        self._chunk_p: Dict[Tuple[int, bool], object] = {}
        self._admit_p: Dict[Tuple[int, bool, bool], object] = {}
        # batched staging (lazy): one (staging_depth, ...) cache pytree, a
        # staging_depth-row sampler and per-row first tokens, plus the
        # batched program caches — allocated on the first batched call so
        # engines running the per-prompt path pay nothing.  The batched
        # scan always runs at the fixed shape (D, _MAX_SCAN_CHUNKS, C)
        # (rows with fewer chunks pad with valid_len = 0 placeholders), so
        # the whole batched family is ≤ 2 programs per input kind + one
        # multi-row scatter — the paper's fixed-iteration datapath.
        self._batched_ready = False
        self._bscan_p: Dict[bool, object] = {}
        self._badmit_p: Dict[bool, object] = {}
        self._bscatter_p = None
        # state-paging gathers (lazy — engines that never swap pay nothing)
        self._gather_p = None
        self._bgather_p = None
        # async-paging gather ring: ``gather_ring`` tickets bound how many
        # swap-outs may drain D2H concurrently.  The gathered arrays (the
        # _gather_p outputs are fresh, never-donated buffers) double as
        # the ring's storage, so the ledger is just the ticket ids: a
        # ticket leaves ``_gather_free`` at dispatch and returns only at
        # ``harvest`` — XLA cannot recycle a draining buffer because the
        # PendingSwap holds the only live reference until then.
        self.async_paging = bool(async_paging)
        self.gather_ring = gather_ring
        self._gather_free: Deque[int] = deque(range(gather_ring))
        self._gather_pending: Dict[int, PendingSwap] = {}
        # the scheduler's tick, carried by the decode spans; and the host
        # seconds spent blocked in the programs' result reads (the
        # ``serve:*.sync`` spans)
        self.tick = 0
        self.sync_s = 0.0
        # the decode counters of the model's mixer kinds, and the last
        # decode tick's values per step ({} for a model that counts none)
        self._counters = lm.decode_counter_names(cfg)
        self.decode_counts: Dict[str, List[int]] = {}
        # donate only the slot buffers: the staging pytree's (repeats, 1,
        # ...) leaves have no same-shape output to alias (XLA would warn)
        self._scatter_p = self._jit(
            "scatter", _scatter_fn, donate=(0, 1, 2),
            in_sh=(self._sh_caches, self._sh_sampler, self._sh_tokens,
                   self._sh_staging, self._sh_row, self._sh_rep,
                   self._sh_rep),
            out_sh=(self._sh_caches, self._sh_sampler, self._sh_tokens))

    # --------------------------------------------------------- shardings
    def _build_shardings(self, params):
        """Derive every buffer's NamedSharding from the rules in
        ``parallel/sharding.py`` (None placeholders when no mesh)."""
        if self.mesh is None:
            (self._sh_params, self._sh_caches, self._sh_staging,
             self._sh_sampler, self._sh_tokens, self._sh_row,
             self._sh_rep, self._sh_toks2d) = (None,) * 8
            return
        from repro.parallel import sharding as rules
        mesh = self.mesh
        if self.max_slots % rules.axis_size(mesh, rules.dp_axes(mesh)):
            warnings.warn(
                f"max_slots={self.max_slots} does not divide the data axis "
                f"({rules.axis_size(mesh, rules.dp_axes(mesh))}); the slot "
                f"axis cannot shard evenly (fit_spec will replicate it or "
                f"re-place 'data' on a state dim, losing the bitwise "
                f"stream guarantee) — pad slots with "
                f"ServingTopology.pad_slots", RuntimeWarning)
        cache_ps = rules.slot_specs(self.cfg, mesh, self.spec.shape_dtype(),
                                    self.max_slots)
        self._sh_caches = rules.make_shardings(mesh, cache_ps)
        self._sh_staging = rules.make_shardings(
            mesh, rules.staging_specs(cache_ps))
        self._sh_params = rules.make_shardings(
            mesh, rules.params_specs(self.cfg, params, False, mesh))
        samp = jax.eval_shape(lambda: sampling.init_state(self.max_slots))
        self._sh_sampler = rules.make_shardings(
            mesh, rules.sampler_specs(mesh, samp, self.max_slots))
        tok_spec = rules.token_slot_spec(mesh, self.max_slots)
        self._sh_tokens = NamedSharding(mesh, tok_spec)
        self._sh_row = rules.replicated(mesh, samp)
        self._sh_rep = NamedSharding(mesh, P())
        self._sh_toks2d = NamedSharding(mesh, P(None, *tok_spec))

    def _jit(self, family: str, fn, *, donate=(), in_sh=None,
             out_sh=None) -> _Program:
        """jit with explicit in/out shardings when running under a mesh
        (every program is one SPMD program over the whole mesh), plain
        jit otherwise.  The program is named ``serve_<family>``, so its
        XLA module (and its events in a device trace) is
        ``jit_serve_<family>`` whatever its static shape."""
        name = f"serve_{family}"

        @functools.wraps(fn)
        def program(*args):
            return fn(*args)
        program.__name__ = program.__qualname__ = name
        kw = {}
        if self.mesh is not None and in_sh is not None:
            kw["in_shardings"] = in_sh
        if self.mesh is not None and out_sh is not None:
            kw["out_shardings"] = out_sh
        return _Program(jax.jit(program, donate_argnums=donate, **kw), name)

    def _zeros(self, spec, shardings):
        if self.mesh is None:
            return spec.zeros()
        return jax.jit(spec.zeros, out_shardings=shardings)()

    def _put(self, tree, shardings):
        return tree if self.mesh is None else jax.device_put(tree,
                                                             shardings)

    def _rep_sh(self, n: int):
        """in_shardings entry for n replicated (scalar/host) args."""
        return (self._sh_rep,) * n

    # ------------------------------------------------------------- plans
    def plan_prefill(self, length: int) -> List[PlanStep]:
        """Decompose a prompt of ``length`` tokens into dispatch steps.

        **masked** (default): at most TWO distinct program shapes per
        prompt.  Full chunks run under ONE scan shape m = the balanced
        chunk count ≤ ``_MAX_SCAN_CHUNKS`` (the last dispatch pads with
        valid_len = 0 placeholder chunks — exact no-ops on the caches),
        and the ragged tail is ONE fixed-size masked admit chunk (its
        padded positions carry valid_len, so the admit logits come from
        the last real token).  The compile cache is bounded at
        ``_MAX_SCAN_CHUNKS`` scan shapes + 1 admit shape *total across
        all prompt lengths*.

        **pow2** (baseline): the PR-3 decomposition — power-of-two scan
        counts, power-of-two unmasked tail sub-chunks, the last being the
        fused-sample admit.  No padding, but O(log chunk) tail programs.

        Both planners dispatch the same valid tokens through the same
        per-chunk math, so token streams agree (pinned by
        ``tests/test_ragged_prefill.py``).
        """
        if length < 1:
            raise ValueError(f"cannot prefill an empty prompt ({length})")
        C = self.prefill_chunk
        tail = (length - 1) % C + 1
        n_full = (length - tail) // C
        steps: List[PlanStep] = []
        if self.plan_mode == "pow2":
            while n_full:
                m = min(_pow2_floor(n_full), _MAX_SCAN_CHUNKS)
                steps.append(PlanStep("scan", m, m * C))
                n_full -= m
            while tail:
                s = _pow2_floor(tail)
                steps.append(PlanStep("chunk", s, s))
                tail -= s
            last = steps[-1]
            steps[-1] = PlanStep("admit", last.size, last.tokens)
            return steps
        if n_full:
            # one scan shape per prompt: the balanced chunk count needs
            # the fewest placeholder chunks for the dispatch count the
            # _MAX_SCAN_CHUNKS cap forces (e.g. 5 full chunks -> two
            # dispatches of m=3, one placeholder, not 4+1)
            n_disp = -(-n_full // _MAX_SCAN_CHUNKS)
            m = -(-n_full // n_disp)
            left = n_full
            for _ in range(n_disp):
                r = min(left, m)
                steps.append(PlanStep("scan", m, r * C,
                                      (C,) * r + (0,) * (m - r)))
                left -= r
        steps.append(PlanStep("admit", C, tail, tail))
        return steps

    # ----------------------------------------------------------- staging
    def stage_begin(self, buf: int, *, seed: int, rid: int,
                    temperature: float, top_k: int, top_p: float,
                    eos_id, budget: int):
        """Reset ring buffer ``buf``'s staging cache and record the
        request's sampling parameters.  The 1-row sampler state itself is
        built *inside* the fused admit program (key folded from
        (seed, rid) there, so the draw stream is independent of slot
        placement, staging buffer and tick length) — building it
        host-side would cost ~17 tiny dispatches per admit."""
        if not self._staging_clean[buf]:
            self.staging[buf] = self._staging_zeros()
        self._staging_clean[buf] = False
        self._staging_args[buf] = (
            np.int32(seed), np.int32(rid), np.float32(temperature),
            np.int32(top_k), np.float32(top_p),
            np.int32(-1 if eos_id is None else eos_id), np.int32(budget))
        self.staging_row[buf] = None
        self.staging_tok[buf] = None

    def _as_chunk(self, chunk, lead_shape, pad_to: int = 0):
        """Flat prompt slice -> device chunk.  (n,) int tokens or (n, d)
        float embeds (the stub VLM/audio frontends), zero-padded to
        ``pad_to`` tokens when the slice is ragged, reshaped to the
        program's chunk layout."""
        chunk = np.asarray(chunk)
        if pad_to > chunk.shape[0]:
            pad = np.zeros((pad_to - chunk.shape[0],) + chunk.shape[1:],
                           chunk.dtype)
            chunk = np.concatenate([chunk, pad])
        if chunk.dtype.kind == "f":
            x = jnp.asarray(chunk, jnp.dtype(self.cfg.act_dtype))
            return x.reshape(*lead_shape, x.shape[-1]), True
        return jnp.asarray(chunk, jnp.int32).reshape(lead_shape), False

    def stage_chunk_scan(self, buf: int, chunks, valid_lens=None):
        """Advance ring buffer ``buf`` by m chunks in one dispatch.

        chunks: flat tokens (or (n, d) embeds) — m * C of them unmasked,
        or ``sum(valid_lens)`` for a masked dispatch (``valid_lens`` an
        (m,)-tuple of per-chunk valid counts; the slice is zero-padded
        into the fixed (m, C) layout and each chunk's padding is masked
        by the per-token validity threading — a 0-entry is a placeholder
        chunk that leaves the caches untouched)."""
        C = self.prefill_chunk
        masked = valid_lens is not None
        m = len(valid_lens) if masked else len(chunks) // C
        x, is_embeds = self._as_chunk(chunks, (1, m, C),
                                      pad_to=m * C if masked else 0)
        prog = self._scan_p.get((m, is_embeds, masked))
        if prog is None:
            kw = "embeds" if is_embeds else "tokens"
            if masked:
                prog = self._jit(
                    "prefill_scan",
                    lambda p, t, vl, c, kw=kw: lm.prefill_chunk_scan(
                        p, self.cfg, c, valid_lens=vl, **{kw: t}),
                    donate=(3,),
                    in_sh=(self._sh_params, self._sh_rep, self._sh_rep,
                           self._sh_staging),
                    out_sh=self._sh_staging)
            else:
                prog = self._jit(
                    "prefill_scan",
                    lambda p, t, c, kw=kw: lm.prefill_chunk_scan(
                        p, self.cfg, c, **{kw: t}),
                    donate=(2,),
                    in_sh=(self._sh_params, self._sh_rep, self._sh_staging),
                    out_sh=self._sh_staging)
            self._scan_p[(m, is_embeds, masked)] = prog
        if masked:
            vl = jnp.asarray(np.asarray(valid_lens, np.int32))
            self.staging[buf] = prog(self.params, x, vl, self.staging[buf])
        else:
            self.staging[buf] = prog(self.params, x, self.staging[buf])

    def stage_chunk(self, buf: int, chunk):
        """Advance ring buffer ``buf`` by one interior tail sub-chunk
        (no logits; pow2 plans only — the masked planner's tail is a
        single fixed-size admit chunk)."""
        s = len(chunk)
        x, is_embeds = self._as_chunk(chunk, (1, s))
        prog = self._chunk_p.get((s, is_embeds))
        if prog is None:
            kw = "embeds" if is_embeds else "tokens"
            prog = self._jit(
                "prefill_chunk",
                lambda p, t, c, kw=kw: lm.prefill_chunk(
                    p, self.cfg, c, **{kw: t})[1],
                donate=(2,),
                in_sh=(self._sh_params, self._sh_rep, self._sh_staging),
                out_sh=self._sh_staging)
            self._chunk_p[(s, is_embeds)] = prog
        self.staging[buf] = prog(self.params, x, self.staging[buf])

    def stage_admit(self, buf: int, chunk, valid_len=None) -> jax.Array:
        """Final chunk + fused on-device first-token draw: one dispatch
        builds the request's sampler row (``sampling.admit_row``), prefills
        the chunk, samples the first token and advances the row (key split,
        budget decrement, EOS/budget done flag).  Returns the (1,) token
        array (still on device — the scheduler syncs it when it stamps
        TTFT) and leaves the advanced row for the slot scatter.

        With ``valid_len`` set the chunk is the masked planner's
        fixed-size tail: the slice is zero-padded to ``prefill_chunk``
        tokens and the programs read the admit logits from the last
        *valid* position."""
        masked = valid_len is not None
        s = self.prefill_chunk if masked else len(chunk)
        x, is_embeds = self._as_chunk(chunk, (1, s),
                                      pad_to=s if masked else 0)
        prog = self._admit_p.get((s, is_embeds, masked))
        if prog is None:
            kw = "embeds" if is_embeds else "tokens"

            if masked:
                def _admit(p, t, c, vl, seed, rid, temp, top_k, top_p,
                           eos, budget, kw=kw):
                    row = sampling.admit_row(seed, rid, temp, top_k, top_p,
                                             eos, budget)
                    return lm.prefill_sample(p, self.cfg, c, row,
                                             sampling.sample, valid_len=vl,
                                             **{kw: t})
                n_rep = 8
            else:
                def _admit(p, t, c, seed, rid, temp, top_k, top_p, eos,
                           budget, kw=kw):
                    row = sampling.admit_row(seed, rid, temp, top_k, top_p,
                                             eos, budget)
                    return lm.prefill_sample(p, self.cfg, c, row,
                                             sampling.sample, **{kw: t})
                n_rep = 7

            prog = self._jit(
                "admit", _admit, donate=(2,),
                in_sh=((self._sh_params, self._sh_rep, self._sh_staging)
                       + self._rep_sh(n_rep)
                       if self.mesh is not None else None),
                out_sh=((self._sh_rep, self._sh_row, self._sh_staging)
                        if self.mesh is not None else None))
            self._admit_p[(s, is_embeds, masked)] = prog
        extra = ((np.int32(valid_len),) if masked else ())
        self.staging_tok[buf], self.staging_row[buf], self.staging[buf] = \
            prog(self.params, x, self.staging[buf],
                 *extra, *self._staging_args[buf])
        return self.staging_tok[buf]

    def scatter(self, slot: int, buf: int):
        """Scatter ring buffer ``buf``'s completed staging cache + sampler
        row + first token into slot ``slot`` (one donated dispatch), then
        reset that ring buffer."""
        self.caches, self.sampler, self.tokens = self._scatter_p(
            self.caches, self.sampler, self.tokens, self.staging[buf],
            self.staging_row[buf], self.staging_tok[buf], jnp.int32(slot))
        self.staging[buf] = self._staging_zeros()
        self._staging_clean[buf] = True
        self.staging_row[buf] = None
        self.staging_tok[buf] = None

    # ------------------------------------------------- batched staging
    def _ensure_batched(self):
        """Allocate the batched staging buffers + multi-row scatter on
        first use: ONE (staging_depth, ...) cache pytree (every staged
        prompt is a row), a staging_depth-row sampler state holding the
        advanced admit rows, and the (staging_depth,) first tokens.  Under
        a mesh the row axis shards on "data" exactly like the slot axis
        (``slot_specs`` with batch = staging_depth)."""
        if self._batched_ready:
            return
        D = self.staging_depth
        self.bspec = lm.cache_specs(self.cfg, D, self.max_len)
        if self.mesh is None:
            self._sh_bstaging = self._sh_bsampler = self._sh_btoks = None
        else:
            from repro.parallel import sharding as rules
            mesh = self.mesh
            ps = rules.slot_specs(self.cfg, mesh, self.bspec.shape_dtype(),
                                  D)
            if D % rules.axis_size(mesh, rules.dp_axes(mesh)):
                # a non-dividing row count must not re-place DP axes on a
                # state dim (cache_specs' tiny-batch rule): distributed
                # state reductions would break the bitwise batching
                # guarantee — replicate the rows instead, keeping only the
                # "model" (head / KV context) placement
                dp = set(rules.dp_axes(mesh))

                def _drop_dp(s):
                    return P(*[None if (a in dp or (isinstance(a, tuple)
                                                    and set(a) & dp))
                               else a for a in s])
                ps = jax.tree.map(_drop_dp, ps,
                                  is_leaf=lambda x: isinstance(x, P))
            self._sh_bstaging = rules.make_shardings(mesh, ps)
            samp = jax.eval_shape(lambda: sampling.init_state(D))
            self._sh_bsampler = rules.make_shardings(
                mesh, rules.sampler_specs(mesh, samp, D))
            self._sh_btoks = NamedSharding(
                mesh, rules.token_slot_spec(mesh, D))
        self.bstaging = self._zeros(self.bspec, self._sh_bstaging)
        self.bsampler = self._put(sampling.init_state(D),
                                  self._sh_bsampler)
        self.btoks = self._put(jnp.zeros((D,), jnp.int32), self._sh_btoks)
        # host mirror of per-row sampling parameters (written by
        # bstage_begin, shipped whole into every batched admit dispatch;
        # rows not admitting carry stale values the admit mask discards)
        self._bargs = {
            "rid": np.zeros((D,), np.int32),
            "temperature": np.zeros((D,), np.float32),
            "top_k": np.zeros((D,), np.int32),
            "top_p": np.ones((D,), np.float32),
            "eos_id": np.full((D,), -1, np.int32),
            "budget": np.ones((D,), np.int32),
        }
        self._bseed = np.int32(0)
        self._bscatter_p = self._jit(
            "scatter", _bscatter_fn, donate=(0, 1, 2, 3),
            in_sh=(self._sh_caches, self._sh_sampler, self._sh_tokens,
                   self._sh_bstaging, self._sh_bsampler, self._sh_btoks,
                   self._sh_rep, self._sh_rep),
            out_sh=(self._sh_caches, self._sh_sampler, self._sh_tokens,
                    self._sh_bstaging))
        self._batched_ready = True

    def bstage_begin(self, row: int, *, seed: int, rid: int,
                     temperature: float, top_k: int, top_p: float,
                     eos_id, budget: int):
        """Record a request's sampling parameters for staging row ``row``
        (host-only — no dispatch).  The row's staging caches are already
        zero: rows are release-zeroed inside the multi-row scatter, so
        beginning a row never costs a device program."""
        self._ensure_batched()
        self._bseed = np.int32(seed)
        self._bargs["rid"][row] = rid
        self._bargs["temperature"][row] = temperature
        self._bargs["top_k"][row] = top_k
        self._bargs["top_p"][row] = top_p
        self._bargs["eos_id"][row] = -1 if eos_id is None else eos_id
        self._bargs["budget"][row] = budget

    def bstage_chunk_scan(self, entries):
        """Advance several staging rows by their next full chunks in ONE
        fixed-shape dispatch.

        entries: list of ``(row, flat_chunk, take)`` — ``take`` full
        chunks (take * C tokens, or (take * C, d) embeds) for row
        ``row``.  Every dispatch runs the same (D, _MAX_SCAN_CHUNKS, C)
        program: rows taking fewer chunks (and rows with no entry) pad
        with valid_len = 0 placeholder chunks, which are bitwise no-ops
        on their caches — the fixed five-phase iteration regardless of
        occupancy."""
        D, C, M = self.staging_depth, self.prefill_chunk, _MAX_SCAN_CHUNKS
        self._ensure_batched()
        first = np.asarray(entries[0][1])
        is_embeds = first.dtype.kind == "f"
        vl = np.zeros((M, D), np.int32)
        if is_embeds:
            x = np.zeros((D, M, C, first.shape[-1]), first.dtype)
        else:
            x = np.zeros((D, M, C), np.int32)
        for row, chunk, take in entries:
            chunk = np.asarray(chunk)
            x[row, :take] = chunk.reshape((take, C) + chunk.shape[1:])
            vl[:take, row] = C
        prog = self._bscan_p.get(is_embeds)
        if prog is None:
            kw = "embeds" if is_embeds else "tokens"
            prog = self._jit(
                "prefill_scan",
                lambda p, t, v, c, kw=kw: lm.prefill_chunk_scan(
                    p, self.cfg, c, valid_lens=v, **{kw: t}),
                donate=(3,),
                in_sh=(self._sh_params, self._sh_rep, self._sh_rep,
                       self._sh_bstaging),
                out_sh=self._sh_bstaging)
            self._bscan_p[is_embeds] = prog
        xj = (jnp.asarray(x, jnp.dtype(self.cfg.act_dtype)) if is_embeds
              else jnp.asarray(x))
        self.bstaging = prog(self.params, xj, jnp.asarray(vl),
                             self.bstaging)

    def bstage_admit(self, entries):
        """Final (ragged tail) chunk + fused first-token draw for several
        staging rows in ONE dispatch: builds every admitting row's sampler
        state on device (``sampling.admit_rows`` — keys folded from
        (seed, rid) exactly as the per-prompt path does, so draw streams
        are batching-invariant), prefills the fixed-size masked tail,
        samples, and merges tokens/sampler rows under the admit mask
        (rows not admitting are valid_len = 0 cache no-ops and keep their
        previous token/sampler values).

        entries: list of ``(row, flat_chunk, valid_len)`` with
        1 <= valid_len <= prefill_chunk tokens in ``flat_chunk``."""
        D, C = self.staging_depth, self.prefill_chunk
        self._ensure_batched()
        first = np.asarray(entries[0][1])
        is_embeds = first.dtype.kind == "f"
        vl = np.zeros((D,), np.int32)
        amask = np.zeros((D,), bool)
        if is_embeds:
            x = np.zeros((D, C, first.shape[-1]), first.dtype)
        else:
            x = np.zeros((D, C), np.int32)
        for row, chunk, valid in entries:
            chunk = np.asarray(chunk)
            x[row, :valid] = chunk
            vl[row] = valid
            amask[row] = True
        prog = self._badmit_p.get(is_embeds)
        if prog is None:
            kw = "embeds" if is_embeds else "tokens"

            def _badmit(p, t, c, samp, toks, v, am, seed, rid, temp,
                        top_k, top_p, eos, budget, kw=kw):
                rows = sampling.admit_rows(seed, rid, temp, top_k, top_p,
                                           eos, budget)
                tok, rows, c = lm.prefill_sample(
                    p, self.cfg, c, rows, sampling.sample, valid_len=v,
                    **{kw: t})
                toks = jnp.where(am, tok.astype(toks.dtype), toks)
                samp = {
                    k: jnp.where(
                        am.reshape((-1,) + (1,) * (w.ndim - 1)),
                        rows[k].astype(w.dtype), w)
                    for k, w in samp.items()}
                return toks, samp, c

            prog = self._jit(
                "admit", _badmit, donate=(2, 3, 4),
                in_sh=((self._sh_params, self._sh_rep, self._sh_bstaging,
                        self._sh_bsampler, self._sh_btoks)
                       + self._rep_sh(9)
                       if self.mesh is not None else None),
                out_sh=((self._sh_btoks, self._sh_bsampler,
                         self._sh_bstaging)
                        if self.mesh is not None else None))
            self._badmit_p[is_embeds] = prog
        xj = (jnp.asarray(x, jnp.dtype(self.cfg.act_dtype)) if is_embeds
              else jnp.asarray(x))
        self.btoks, self.bsampler, self.bstaging = prog(
            self.params, xj, self.bstaging, self.bsampler, self.btoks,
            jnp.asarray(vl), jnp.asarray(amask), self._bseed,
            self._bargs["rid"], self._bargs["temperature"],
            self._bargs["top_k"], self._bargs["top_p"],
            self._bargs["eos_id"], self._bargs["budget"])

    def _sync(self, phase: str, *arrays):
        """Read ``arrays`` to the host: the wait for the device, under a
        ``serve:<phase>.sync`` span and counted in ``sync_s``."""
        t0 = time.perf_counter()
        with spans.span(f"{phase}.sync", tick=self.tick):
            out = tuple(np.asarray(a) for a in arrays)
        self.sync_s += time.perf_counter() - t0
        return out

    def admit_tokens(self) -> np.ndarray:
        """The batched staging rows' first tokens, on the host (the wait
        for the batched admit)."""
        return self._sync("prefill", self.btoks)[0]

    def admit_token(self, buf: int) -> int:
        """Ring buffer ``buf``'s first token, on the host."""
        return int(self._sync("prefill", self.staging_tok[buf])[0][0])

    def bscatter(self, assigns, release_rows=()):
        """Admit every finished staging row into its slot in ONE donated
        dispatch.  assigns: list of ``(slot, row)`` pairs (distinct
        slots); release_rows: extra rows to zero without scattering
        (requests that finished at admit).  Assigned rows are always
        released — after the scatter both are clean for reuse."""
        self._ensure_batched()
        slots = np.full((self.staging_depth,), self.max_slots, np.int32)
        release = np.zeros((self.staging_depth,), bool)
        for slot, row in assigns:
            slots[row] = slot
            release[row] = True
        for row in release_rows:
            release[row] = True
        (self.caches, self.sampler, self.tokens,
         self.bstaging) = self._bscatter_p(
            self.caches, self.sampler, self.tokens, self.bstaging,
            self.bsampler, self.btoks, jnp.asarray(slots),
            jnp.asarray(release))

    # ------------------------------------------------------ state paging
    def _host_state(self, st, row, tok) -> SwappedState:
        """Fetch a gathered (staging-layout) slice to host memory.  Under
        a mesh the fetch is the all-gather to one replicated host copy —
        the swapped image is topology-free, so any engine with the same
        arch config (any mesh shape) can restore it."""
        st, row, tok = jax.device_get((st, row, tok))
        return SwappedState(caches=st, sampler=row, token=np.asarray(tok))

    def _acquire_ticket(self) -> int:
        """Claim a gather-ring ticket for one async swap-out dispatch.
        The scheduler is responsible for capacity (force-harvesting the
        oldest drain when the ring is full), so an empty ring here is a
        ledger bug, not backpressure."""
        if not self._gather_free:
            raise RuntimeError(
                f"gather ring exhausted: all {self.gather_ring} buffers "
                f"are draining — harvest a pending swap before "
                f"dispatching another gather")
        return self._gather_free.popleft()

    def gather_slot_async(self, slot: int) -> PendingSwap:
        """Dispatch the swap-out of resident slot ``slot`` without
        waiting for the D2H transfer: ONE program slices its cache
        column + sampler row + last token (the inverse of the slot
        scatter) and freezes the vacated slot's done flag; the fresh
        output arrays become a gather-ring buffer whose host copy drains
        in the background (``copy_to_host_async`` inside PendingSwap).
        The slot is reusable the moment this returns — the gathered
        values are a snapshot, so later scatters into the slot cannot
        perturb the eventual ``harvest``."""
        if self._gather_p is None:
            self._gather_p = self._jit(
                "gather", _gather_fn, donate=(1,),
                in_sh=(self._sh_caches, self._sh_sampler, self._sh_tokens,
                       self._sh_rep),
                out_sh=((self._sh_staging, self._sh_row, self._sh_rep,
                         self._sh_sampler)
                        if self.mesh is not None else None))
        buf = self._acquire_ticket()
        st, row, tok, self.sampler = self._gather_p(
            self.caches, self.sampler, self.tokens, jnp.int32(slot))
        pend = PendingSwap(buf, st, row, tok)
        self._gather_pending[buf] = pend
        return pend

    def gather_staging_async(self, buf_ring: int) -> PendingSwap:
        """Dispatch the swap-out of per-prompt ring buffer ``buf_ring``
        (a staged-ready request pausing at the admit boundary, before
        its slot scatter): the staging cache, admit-advanced sampler row
        and first token are already in staging layout — no program, the
        PendingSwap takes direct refs.  Holding them across a later
        ``stage_begin`` is safe: that path REPLACES ``staging[buf]``
        with fresh zeros, it never donates the old arrays.  The buffer
        returns to the ring dirty (``stage_begin`` re-zeros it)."""
        buf = self._acquire_ticket()
        pend = PendingSwap(buf, self.staging[buf_ring],
                           self.staging_row[buf_ring],
                           self.staging_tok[buf_ring])
        self.staging_row[buf_ring] = None
        self.staging_tok[buf_ring] = None
        self._gather_pending[buf] = pend
        return pend

    def bgather_row_async(self, row: int) -> PendingSwap:
        """Dispatch the swap-out of batched staging row ``row`` (the
        admit-boundary swap on the batched path).  Pure read — the
        caller marks the row dirty so the next multi-row scatter
        release-zeroes it; the gather outputs are fresh arrays, immune
        to that zeroing."""
        self._ensure_batched()
        if self._bgather_p is None:
            self._bgather_p = self._jit(
                "gather", _bgather_fn,
                in_sh=(self._sh_bstaging, self._sh_bsampler,
                       self._sh_btoks, self._sh_rep),
                out_sh=((self._sh_staging, self._sh_row, self._sh_rep)
                        if self.mesh is not None else None))
        buf = self._acquire_ticket()
        st, row_, tok = self._bgather_p(self.bstaging, self.bsampler,
                                        self.btoks, jnp.int32(row))
        pend = PendingSwap(buf, st, row_, tok)
        self._gather_pending[buf] = pend
        return pend

    def harvest(self, pend: PendingSwap) -> SwappedState:
        """Materialize a draining swap-out into host numpy and return
        its gather-ring ticket.  Blocks only for whatever part of the
        D2H transfer has not already drained (zero when
        ``pend.ready()``).  The PendingSwap's device refs are dropped so
        XLA can recycle the buffer."""
        if self._gather_pending.get(pend.buf) is not pend:
            raise RuntimeError(
                f"harvest of gather buffer {pend.buf} that is not "
                f"draining — double harvest or foreign PendingSwap")
        sw = self._host_state(pend.st, pend.row, pend.tok)
        pend.st = pend.row = pend.tok = None
        del self._gather_pending[pend.buf]
        self._gather_free.append(pend.buf)
        return sw

    # synchronous façade: dispatch + immediate harvest runs the exact
    # same programs on the same operands, so values are bitwise
    # identical to the async path — only the wait moves.
    def gather_slot(self, slot: int) -> SwappedState:
        """Swap a resident request out of slot ``slot``, blocking until
        its host image is materialized (``gather_slot_async`` without
        the overlap)."""
        return self.harvest(self.gather_slot_async(slot))

    def gather_staging(self, buf: int) -> SwappedState:
        """Gather per-prompt ring buffer ``buf``, blocking (see
        ``gather_staging_async``)."""
        return self.harvest(self.gather_staging_async(buf))

    def bgather_row(self, row: int) -> SwappedState:
        """Gather batched staging row ``row``, blocking (see
        ``bgather_row_async``)."""
        return self.harvest(self.bgather_row_async(row))

    def prestage_restore(self, sw: SwappedState):
        """H2D-stage a swapped image for a later ``restore_slot``: the
        device_put (re-sharded under a mesh to the staging/row/replicated
        shardings the scatter expects) happens NOW, the grant-boundary
        scatter later consumes the already-resident triple.  Safe to
        hold across ticks: ``_scatter_p`` donates only the slot buffers
        (args 0–2), never its staging operands, so a prestaged triple
        survives unrelated admits and scatters; a cancelled resume just
        drops the triple."""
        st = self._put(jax.tree.map(jnp.asarray, sw.caches),
                       self._sh_staging)
        row = self._put({k: jnp.asarray(v) for k, v in sw.sampler.items()},
                        self._sh_row)
        tok = self._put(jnp.asarray(sw.token), self._sh_rep)
        return st, row, tok

    def restore_slot(self, slot: int, sw: SwappedState, prestaged=None):
        """Swap-in: put the host-side ``SwappedState`` back on device in
        staging layout (via ``prestage_restore``, or consuming an
        already-prestaged triple) and re-admit it through the EXISTING
        slot-scatter program — the same donated dynamic_update_slice
        every fresh admit takes, so a resumed request's slot residency
        is bitwise what it was at gather time whether or not the put was
        prefetched."""
        st, row, tok = (prestaged if prestaged is not None
                        else self.prestage_restore(sw))
        self.caches, self.sampler, self.tokens = self._scatter_p(
            self.caches, self.sampler, self.tokens, st, row, tok,
            jnp.int32(slot))

    # ------------------------------------------------- speculative decode
    def spec_draft(self, k: int):
        """Propose ``k`` draft tokens per slot: ``lm.decode_steps`` on the
        draft model over throwaway cache/sampler copies (nothing donated —
        the committed draft caches and the sampler stay untouched until
        the verify, so an abandoned draft costs nothing to roll back).
        The proposals stay on device, feeding the verify program without
        a host sync; the draw stream is the slot's own (seed, rid)-folded
        key sequence — the same keys the verify's target sampler will
        consume, which is what collapses coupled rejection sampling to a
        token-equality check.  k = 0 (a verify-only tail tick) returns an
        empty proposal without dispatching."""
        if k == 0:
            return self._put(jnp.zeros((0, self.max_slots), jnp.int32),
                             self._sh_toks2d)
        prog = self._draft_p.get(k)
        if prog is None:
            prog = self._jit(
                "draft",
                lambda dp, t, dc, s, k=k: lm.decode_steps(
                    dp, self.draft_cfg, t, dc, k,
                    sampler=s, sample_fn=sampling.sample)[0],
                in_sh=(self._sh_dparams, self._sh_tokens,
                       self._sh_dcaches, self._sh_sampler),
                out_sh=self._sh_toks2d)
            self._draft_p[k] = prog
        with spans.span("draft.dispatch", tick=self.tick):
            return prog(self.draft_params, self.tokens, self.dcaches,
                        self.sampler)

    def spec_verify(self, k: int, dtoks):
        """Score a pending k-token draft with ``lm.verify_steps`` and
        commit each slot's state exactly through its emitted prefix — the
        single host sync of a speculative tick (up to k+1 tokens per
        slot).  The checkpoint buffers are donated rollback scratch: the
        program's run-ahead finals land in them, so ``caches``/``ckpt``
        (and their draft twins) ping-pong roles every tick and the
        rollback costs no allocation.  Returns host (k+1, S) toks/valid
        in exactly ``decode``'s layout."""
        prog = self._verify_p.get(k)
        if prog is None:
            def _verify(p, dp, dtoks, tokens, caches, ckpt, dcaches,
                        dckpt, samp):
                del ckpt, dckpt     # donated scratch; outputs alias them
                toks, valid, last, com, dcom, run, drun, st = \
                    lm.verify_steps(p, self.cfg, dp, self.draft_cfg,
                                    tokens, dtoks, caches, dcaches, samp,
                                    sampling.sample_where)
                return toks, valid, last, com, run, dcom, drun, st

            prog = self._jit(
                "verify", _verify, donate=(3, 4, 5, 6, 7, 8),
                in_sh=(self._sh_params, self._sh_dparams, self._sh_toks2d,
                       self._sh_tokens, self._sh_caches, self._sh_ckpt,
                       self._sh_dcaches, self._sh_dckpt, self._sh_sampler),
                out_sh=((self._sh_toks2d, self._sh_toks2d,
                         self._sh_tokens, self._sh_caches, self._sh_ckpt,
                         self._sh_dcaches, self._sh_dckpt,
                         self._sh_sampler)
                        if self.mesh is not None else None))
            self._verify_p[k] = prog
        with spans.span("decode.dispatch", tick=self.tick):
            (toks, valid, self.tokens, self.caches, self.ckpt,
             self.dcaches, self.dckpt, self.sampler) = prog(
                self.params, self.draft_params, dtoks, self.tokens,
                self.caches, self.ckpt, self.dcaches, self.dckpt,
                self.sampler)
        return self._sync("decode", toks, valid)

    def draft_prefill_slot(self, slot: int, tokens_1d):
        """Rebuild slot ``slot``'s draft-model state from the request's
        consumed token stream (prompt + all emitted tokens except the
        last, which is the next decode input) — called at every slot
        activation: fresh admit and swap-in alike.  This is why the swap
        image carries no draft state: ONE fixed-shape program (a masked
        (1, n, C) chunk scan from zero state + a donated slot insert)
        reconstructs it, with the same chunk size as the target's staged
        prefill so a self-draft rebuild hits the same chunk boundaries.
        Streams longer than max_len keep the trailing max_len tokens
        (draft quality only — the target never sees this state)."""
        toks = np.asarray(tokens_1d, np.int32).reshape(-1)[-self.max_len:]
        if toks.size == 0:
            raise ValueError("draft_prefill_slot needs >= 1 consumed "
                             "token (prompts are never empty)")
        C, n = self._dchunk, self._dchunks
        flat = np.zeros((n * C,), np.int32)
        flat[:toks.size] = toks
        vls = np.zeros((n,), np.int32)
        full, tail = divmod(toks.size, C)
        vls[:full] = C
        if tail:
            vls[full] = tail
        prog = self._dprefill_p
        if prog is None:
            def _dprefill(dp, t, vl, dcaches, slot):
                c1 = lm.init_caches(self.draft_cfg, 1, self.max_len)
                c1 = lm.prefill_chunk_scan(dp, self.draft_cfg, c1,
                                           tokens=t, valid_lens=vl)
                return jax.tree.map(
                    lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                        f, o.astype(f.dtype), slot, axis=1),
                    dcaches, c1)

            prog = self._jit(
                "draft_prefill", _dprefill, donate=(3,),
                in_sh=(self._sh_dparams, self._sh_rep, self._sh_rep,
                       self._sh_dcaches, self._sh_rep),
                out_sh=self._sh_dcaches)
            self._dprefill_p = prog
        self.dcaches = prog(self.draft_params,
                            jnp.asarray(flat.reshape(1, n, C)),
                            jnp.asarray(vls), self.dcaches,
                            jnp.int32(slot))

    # ----------------------------------------------------------- metrics
    def compiled_programs(self) -> Dict[str, int]:
        """Live jitted-program cache sizes per family.

        This is the observable the masked planner exists for: with
        ``plan_mode="masked"`` the prefill families stay at ≤
        ``_MAX_SCAN_CHUNKS`` scan shapes + 1 admit shape across *all*
        prompt lengths (and ≤ 2 shapes are ever dispatched for any single
        prompt); the pow2 baseline grows O(log chunk) tail programs on
        top.  Asserted by ``tests/test_ragged_prefill.py`` and reported
        through ``Scheduler.metrics()``."""
        prefill = (len(self._scan_p) + len(self._chunk_p)
                   + len(self._admit_p) + len(self._bscan_p)
                   + len(self._badmit_p))
        spec = (len(self._draft_p) + len(self._verify_p)
                + (1 if self._dprefill_p is not None else 0)
                if self.speculative else 0)
        return {
            "decode": len(self._decode_p),
            "prefill_scan": len(self._scan_p) + len(self._bscan_p),
            "prefill_chunk": len(self._chunk_p),
            "prefill_admit": len(self._admit_p) + len(self._badmit_p),
            "prefill": prefill,
            "speculative": spec,
            # + the slot scatter, + the multi-row scatter once built,
            # + the state-paging gathers once built
            "total": (len(self._decode_p) + prefill + spec + 1
                      + (1 if self._batched_ready else 0)
                      + (1 if self._gather_p is not None else 0)
                      + (1 if self._bgather_p is not None else 0)),
        }

    # ------------------------------------------------------------- ticks
    def decode(self, k: int):
        """One fused k-step decode+sample tick over all slots; the single
        host sync reads the (k, slots) token/validity arrays, and with
        them, for a model whose mixer kinds count (``lm.decode_counter_
        names``), each step's counters into ``decode_counts``."""
        names = self._counters
        prog = self._decode_p.get(k)
        if prog is None:
            out_sh = None
            if self.mesh is not None:
                out_sh = (self._sh_toks2d, self._sh_toks2d, self._sh_tokens,
                          self._sh_caches, self._sh_sampler)
                if names:
                    out_sh += ({n: self._sh_rep for n in names},)
            prog = self._jit(
                "decode",
                lambda p, t, c, s, k=k: lm.decode_steps(
                    p, self.cfg, t, c, k,
                    sampler=s, sample_fn=sampling.sample,
                    count=bool(names)),
                donate=(2, 3),
                in_sh=(self._sh_params, self._sh_tokens, self._sh_caches,
                       self._sh_sampler),
                out_sh=out_sh)
            self._decode_p[k] = prog
        with spans.span("decode.dispatch", tick=self.tick):
            out = prog(self.params, self.tokens, self.caches, self.sampler)
            toks, valid, self.tokens, self.caches, self.sampler = out[:5]
        if not names:
            return self._sync("decode", toks, valid)
        toks, valid, *counts = self._sync(
            "decode", toks, valid, *(out[5][n] for n in names))
        self.decode_counts = {n: c.tolist() for n, c in zip(names, counts)}
        return toks, valid
