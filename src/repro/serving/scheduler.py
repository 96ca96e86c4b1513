"""Host scheduler: request lifecycle, slot assignment and tick policy.

This is the host half of the scheduler/executor split (the device half —
slot/staging buffers and every jitted program — is
``repro.serving.executor.DeviceExecutor``).  The scheduler never touches a
device buffer directly; it decides *what* to dispatch and *when*:

  1. **submit** validates a request (sampling parameters, token budget,
     prompt length vs ``max_len`` — an over-long prompt would wrap the
     rolling window caches mid-prompt and silently corrupt them) and
     appends it to a FIFO queue.
  2. **staging admit** (overlapped, the default): queued requests prefill
     *chunk by chunk* into the executor's staging ring at tick
     boundaries.  While free slots exist this is work-conserving (same
     admits as the serialized baseline); once every slot is busy, up to
     ``staging_depth`` head-of-queue requests still prefill ahead of any
     free slot — one chunk dispatch per staged request per tick — emit
     their first tokens (the final chunk fuses the draw on device — no
     host ``sample_np``), and are held staged-ready until slots free
     (scattered in FIFO order).  TTFT is stamped when that token is
     device-confirmed (synced to the host), not when the dispatch is
     queued.  With ``overlap=False`` the same programs run back-to-back
     behind a free slot (the serialized baseline — token streams are
     bitwise identical, only timing moves).
  3. **tick** (`step`): one fused decode+sample scan over all slots.  The
     tick length is **budget-aware**: the smallest power-of-two bucket
     (capped at ``decode_block``) covering the largest remaining per-slot
     budget, so the tail ticks of a batch of short budgets stop burning
     masked steps — bucketing bounds the compile cache.
  4. finished slots (device EOS/budget flags) are freed at tick boundaries.

**State paging (slot oversubscription).**  The paper's core claim is
that a *fixed-size* persistent state is what makes linear-attention
decode accelerable; the serving analog of on-chip capacity is the slot
count.  Because every mixer's state is a constant-shape block described
by ``cache_spec``, an idle request's whole device residency (recurrent
state + rolling KV window + sampler row + last token) gathers into one
host-side ``SwappedState`` record — no block tables, no paged KV.
``pause(rid)`` swaps a request out wherever it is in the lifecycle
(SWAPPED), ``resume(rid)`` queues it for a slot grant (RESUMING),
``preempt()`` evicts the lowest-priority active request with automatic
resume, and ``swap_policy`` runs an idle-lease and/or priority-pressure
sweep each tick.  Swap-in re-admits through the EXISTING slot-scatter
program, and the sampler row round-trips the PRNG key mid-stream, so a
preempted-and-resumed stream is bitwise the uninterrupted one
(``tests/test_state_paging.py``).  Freed-slot grants alternate between
the resume queue and staged-ready fresh admits (both FIFO) so neither
class starves; an engine can thus hold arbitrarily more live sessions
than ``max_slots`` (capped by ``max_live_requests``).

With ``mesh`` set, the executor allocates every buffer with NamedShardings
(slot axis on "data", state heads / KV context on "model") and compiles
every program with explicit in/out shardings — the scheduler logic is
topology-blind; only the buffers underneath it are distributed.

Wall-clock metrics (TTFT, latency, throughput) are stamped per request;
``metrics()`` aggregates them plus the decode-only µs/token that
``benchmarks/bench_serving.py`` sweeps.

Tracing is always on.  Each ``step`` is a tick with a serial id; its
phases run under ``serve:`` host spans (``repro.serving.spans``) that a
running profiler records on the device trace's clock: ``serve:step``
holds ``serve:admit`` (with ``serve:scatter``, ``serve:prefill.dispatch``
and ``serve:prefill.sync``), ``serve:decode.dispatch``,
``serve:decode.sync``, ``serve:emit`` and the paging sweeps that are on
(``serve:paging.*``).  ``metrics()`` adds a bounded log of the decode
ticks (length, live slots and summed contexts per step) and of the
prefill dispatches (program, and per row rid, start and valid tokens),
the scheduler's own host time (``sched_self_s`` over ``steps``: each step
less its ``*.sync`` waits) and the programs compiled or loaded inside
``step`` (``compiles``).
"""
from __future__ import annotations

import os
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.serving import spans, wire
from repro.serving.executor import (DeviceExecutor, PendingSwap, PlanStep,
                                    SwappedState)


# request lifecycle states (the serving.md diagram): a request is QUEUED,
# then STAGING (chunked prefill into the ring), READY (first token drawn,
# waiting for a slot), ACTIVE (slot-resident, decoding) and DONE — plus
# the paging states: SWAPPED (device image gathered to host, or paused
# straight out of the queue) and RESUMING (in the resume queue, waiting
# for a granted slot to scatter back into)
QUEUED, STAGING, READY, ACTIVE = "queued", "staging", "ready", "active"
SWAPPED, RESUMING, DONE = "swapped", "resuming", "done"
# sub-phases of a swap record under async paging / spill (the request's
# lifecycle state stays SWAPPED or RESUMING — these describe where its
# *image* is): DRAINING = gather dispatched, D2H still in flight;
# HOSTED = image is host numpy; PREFETCHED = image prestaged back on
# device awaiting a predicted grant; SPILLED = image is a wire-encoded
# file in the spool dir
DRAINING, HOSTED = "draining", "hosted"
PREFETCHED, SPILLED = "prefetched", "spilled"

# entries kept by each of the scheduler's tick and prefill logs
LOG_LEN = 8192


@dataclass
class Request:
    rid: int
    prompt: Optional[np.ndarray] = None         # (T,) int32 token ids
    prompt_embeds: Optional[np.ndarray] = None  # (T, d_model) — stub
                                                # frontends (vlm/audio)
    max_new_tokens: int = 16
    temperature: float = 0.0            # 0 => greedy
    top_k: int = 0                      # 0 => disabled
    top_p: float = 1.0                  # 1.0 => disabled
    eos_id: Optional[int] = None
    priority: int = 0                   # pressure eviction: a strictly
                                        # higher priority wins a slot
                                        # from a lower one
    output: List[int] = field(default_factory=list)
    done: bool = False
    state: str = "new"                  # lifecycle (QUEUED..DONE above)
    # wall-clock stamps (perf_counter seconds), set by the engine
    t_submit: Optional[float] = None
    t_first: Optional[float] = None     # first token device-confirmed
    t_done: Optional[float] = None
    swapped_s: float = 0.0              # total wall time swapped out
    _swapped_pre_first_s: float = 0.0   # swapped time before first token
    t_last_activity: Optional[float] = None  # lease stamp (idle policy):
                                        # set at submit/activation,
                                        # refreshed by Scheduler.touch
    _t_active: Optional[float] = None   # most recent slot activation

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit -> first token, EXCLUDING time the request spent
        swapped out before it ever reached the device (a paused-then-
        resumed queued request isn't "waiting", its client left)."""
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit - self._swapped_pre_first_s

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None or self.t_submit is None:
            return None
        return self.t_done - self.t_submit

    @property
    def active_latency_s(self) -> Optional[float]:
        """Wall latency minus swapped-out time — the denominator for
        throughput: a request that sat paused for an hour did not decode
        slowly for an hour."""
        lat = self.latency_s
        if lat is None:
            return None
        return lat - self.swapped_s

    @property
    def tokens_per_s(self) -> Optional[float]:
        lat = self.active_latency_s
        if not lat:
            return None
        return len(self.output) / lat

    @property
    def prompt_len(self) -> Optional[int]:
        if self.prompt is not None:
            return int(np.asarray(self.prompt).shape[-1])
        if self.prompt_embeds is not None:
            return int(np.asarray(self.prompt_embeds).shape[0])
        return None

    @property
    def _inputs(self):
        return self.prompt if self.prompt is not None else self.prompt_embeds


@dataclass(eq=False)      # identity semantics: entries are removed by `is`
class _Staging:
    """One in-flight staged prefill: a request bound to an executor ring
    buffer (= a batched staging row), with its chunk-plan progress and
    staged-ready flag.  The per-prompt path walks ``plan``; the batched
    path tracks ``chunks_left`` full chunks + the fixed-size masked
    ``tail`` directly (its "plan" is whatever the per-tick packer
    allocates)."""
    req: Request
    plan: List[PlanStep]
    buf: int
    plan_pos: int = 0
    prompt_pos: int = 0
    ready: bool = False
    chunks_left: int = 0      # batched path: full C-chunks not yet staged
    tail: int = 0             # batched path: valid tokens in the admit chunk
    admitted: bool = False    # batched path: admit dispatched, token pending
    pause_pending: bool = False  # pause() hit mid-prefill: swap out at
                                 # the admit boundary instead of holding
                                 # the request staged-ready


@dataclass(eq=False)
class _Swapped:
    """One swapped-out request: its host-side device image (None when it
    was paused straight out of the queue — nothing was resident to
    gather) and the wall-clock stamp the swap started at (the gather
    *dispatch*, so parked-time exclusion spans dispatch → restore
    scatter regardless of when the drain is harvested).

    Under async paging the image moves through sub-phases: ``pending``
    holds the in-flight gather (DRAINING) until a harvest materializes
    ``state``; ``prefetch`` holds a device-resident restore triple
    (PREFETCHED) staged ahead of a predicted grant; ``spool`` points at
    an on-disk wire-encoded file (SPILLED) once the watermark pushed the image
    out of memory."""
    req: Request
    state: Optional[SwappedState]
    t_swap: float
    pending: Optional[PendingSwap] = None
    prefetch: Optional[tuple] = None
    spool: Optional[str] = None

    @property
    def phase(self) -> str:
        if self.pending is not None:
            return DRAINING
        if self.prefetch is not None:
            return PREFETCHED
        if self.spool is not None:
            return SPILLED
        return HOSTED


class Scheduler:
    """Continuous-batching decode scheduler over a ``DeviceExecutor``."""

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_len: int = 256, seed: int = 0, decode_block: int = 1,
                 overlap: bool = True, prefill_chunk: int = 16,
                 budget_ticks: bool = True, mesh=None,
                 staging_depth: int = 2, plan_mode: str = "masked",
                 prefill_batching: Optional[bool] = None,
                 prefill_budget: Optional[int] = None,
                 swap_policy: str = "manual",
                 idle_swap_ms: Optional[float] = None,
                 max_live_requests: Optional[int] = None,
                 async_paging: bool = False, gather_ring: int = 2,
                 host_swap_bytes: Optional[int] = None,
                 swap_spool_dir: Optional[str] = None,
                 speculative: bool = False, draft_cfg=None,
                 draft_params=None, k_draft: int = 4,
                 adaptive_k: bool = False, role: str = "both"):
        if decode_block < 1:
            raise ValueError(f"decode_block must be >= 1, got {decode_block}")
        if prefill_budget is not None and prefill_budget < 1:
            raise ValueError(f"prefill_budget must be >= 1 token, got "
                             f"{prefill_budget}")
        if swap_policy not in ("manual", "idle", "pressure", "auto"):
            raise ValueError(f"swap_policy must be one of manual/idle/"
                             f"pressure/auto, got {swap_policy!r}")
        if swap_policy in ("idle", "auto") and idle_swap_ms is None:
            raise ValueError(f"swap_policy={swap_policy!r} sweeps idle "
                             f"leases — set idle_swap_ms")
        if idle_swap_ms is not None and idle_swap_ms < 0:
            raise ValueError(f"idle_swap_ms must be >= 0, got "
                             f"{idle_swap_ms}")
        if max_live_requests is not None and max_live_requests < 1:
            raise ValueError(f"max_live_requests must be >= 1, got "
                             f"{max_live_requests}")
        if host_swap_bytes is not None and host_swap_bytes < 0:
            raise ValueError(f"host_swap_bytes must be >= 0, got "
                             f"{host_swap_bytes}")
        if host_swap_bytes is not None and swap_spool_dir is None:
            raise ValueError("host_swap_bytes is a spill watermark — set "
                             "swap_spool_dir so cold images have "
                             "somewhere to go")
        if (draft_cfg is not None or draft_params is not None) \
                and not speculative:
            raise ValueError("draft_cfg/draft_params given without "
                             "speculative=True")
        if adaptive_k and not speculative:
            raise ValueError("adaptive_k tunes the speculative draft "
                             "length — set speculative=True")
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"role must be one of prefill/decode/both, "
                             f"got {role!r}")
        self.role = role
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.seed = seed
        self.decode_block = decode_block
        self.overlap = overlap
        self.budget_ticks = budget_ticks
        # speculative decode: default draft is the target itself
        # (self-draft — acceptance 1.0, the upper bound benchmarks use;
        # real deployments pass a trained smaller draft_cfg/draft_params)
        self.speculative = speculative
        self.k_draft = k_draft
        # acceptance-adaptive draft length: a windowed acceptance rate
        # shrinks/grows the effective k within [1, k_draft] — a bad
        # draft model collapses to verify-heavy k=1 ticks instead of
        # burning k rejected proposals per sync; streams are unaffected
        # (the shared-key verify emits the same tokens at any k)
        self.adaptive_k = bool(adaptive_k)
        self._k_eff = k_draft
        self._accept_window: Deque[tuple] = deque(maxlen=4)
        if speculative and draft_cfg is None:
            draft_cfg, draft_params = cfg, params
        self.executor = DeviceExecutor(
            cfg, params, max_slots=max_slots, max_len=max_len,
            decode_block=decode_block, prefill_chunk=prefill_chunk,
            mesh=mesh, staging_depth=staging_depth, plan_mode=plan_mode,
            prefill_batching=prefill_batching,
            draft_cfg=draft_cfg if speculative else None,
            draft_params=draft_params if speculative else None,
            k_draft=k_draft, async_paging=async_paging,
            gather_ring=gather_ring)
        # per-tick prefill token budget of the batched packer, in
        # scan-chunk units (an admit dispatch costs one unit).  The
        # default lets every staging row take a full scan + admit per
        # tick — the batched path is then never slower than the
        # per-prompt one-chunk-per-entry loop it replaces.
        C = self.executor.prefill_chunk
        from repro.serving.executor import _MAX_SCAN_CHUNKS
        self.prefill_budget = prefill_budget
        self._budget_chunks = (
            max(1, prefill_budget // C) if prefill_budget is not None
            else self.executor.staging_depth * (_MAX_SCAN_CHUNKS + 1))
        self._max_scan_chunks = _MAX_SCAN_CHUNKS
        self.free: Deque[int] = deque(range(max_slots))
        self.active: Dict[int, Request] = {}
        self.queue: Deque[Request] = deque()
        self._all: List[Request] = []
        # staging state machine: FIFO of in-flight staged prefills, one per
        # executor ring buffer (free ring indices in _free_bufs); batched
        # rows whose request finished at admit wait in _dirty_rows until a
        # multi-row scatter release-zeroes them
        self._stagings: List[_Staging] = []
        self._free_bufs: Deque[int] = deque(range(self.staging_depth))
        self._dirty_rows: set = set()
        # state paging: host store of swapped-out requests (rid-keyed —
        # submit enforces rid uniqueness among live requests) and the
        # FIFO resume queue of rids waiting for a slot grant
        self.swap_policy = swap_policy
        self.idle_swap_ms = idle_swap_ms
        self.max_live_requests = max_live_requests
        self.swapped: Dict[int, _Swapped] = {}
        self.resume_q: Deque[int] = deque()
        self._grant_resume_next = True
        # disaggregated serving: a role="prefill" engine pauses every
        # request at the admit boundary (prompt fully prefilled, first
        # token emitted, sampler row advanced) and parks the swap record
        # here until the router ships it to a decode engine
        self._handoff_q: Deque[int] = deque()
        self.handoffs_out = 0       # records shipped via withdraw_handoff
        # async paging: rids whose gather is still draining D2H, in
        # dispatch order — the force-harvest order when the gather ring
        # runs out of buffers
        self.async_paging = bool(async_paging)
        self._draining_q: Deque[int] = deque()
        # spill-to-disk tier: beyond host_swap_bytes of in-memory swapped
        # images, the coldest dormant image spills to a wire-encoded file under
        # swap_spool_dir (a spool dir with no watermark spills every
        # dormant image — watermark 0)
        self.host_swap_bytes = host_swap_bytes
        self.swap_spool_dir = swap_spool_dir
        # speculative tick pipeline: drafts for the NEXT tick are
        # dispatched at the END of step() (async JAX dispatch overlaps
        # the draft with host-side emission/admit work — the serving
        # analogue of the paper's phase pipelining), so a pending
        # (k, device draft tokens, live-rid snapshot) record spans the
        # step() boundary; pauses/preempts arriving while it is pending
        # are deferred to the verify boundary (see pause())
        self._pending = None
        self._spec_deferred: List[tuple] = []   # (rid, resume_flag)
        self.spec_ticks = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.draft_prefills = 0     # draft-state rebuild dispatches
        self.ticks = 0
        self.decode_s = 0.0         # wall time inside decode ticks (+ sync)
        self.decoded_tokens = 0     # tokens emitted by ticks (not admit)
        self.stage_dispatches = 0   # prefill-chunk programs dispatched
        self.scatter_dispatches = 0  # slot-scatter programs dispatched
        self.swap_outs = 0          # slot/staging gathers to host
        self.swap_ins = 0           # restores through the slot scatter
        self.swap_s = 0.0           # wall time inside swap transfers
        self.swap_bytes = 0         # bytes moved (both directions)
        # swap_s split: dispatch = async program/put launches + harvests
        # of already-drained transfers (work the tick loop never waits
        # on); stall = blocking waits async paging exists to hide
        # (forced/sync harvests, inline puts).  Invariant:
        # swap_s == swap_dispatch_s + swap_stall_s.
        self.swap_dispatch_s = 0.0
        self.swap_stall_s = 0.0
        # direction breakdown (gather+harvest / put / scatter — sums to
        # swap_s too; benchmarks report these in µs)
        self.swap_gather_s = 0.0
        self.swap_put_s = 0.0
        self.swap_scatter_s = 0.0
        self.swap_prefetches = 0    # restore triples prestaged ahead
        self.swap_prefetch_hits = 0  # grants that consumed a prefetch
        self.swap_prefetch_drops = 0  # prefetches cancelled un-consumed
        self.swap_harvests_overlapped = 0  # drain done before harvest
        self.swap_harvests_forced = 0      # harvest had to block
        self.spills = 0             # images written to the spool dir
        self.spill_loads = 0        # images read back on resume
        self.spill_bytes = 0        # bytes written to disk
        self._metrics_seen: set = set()  # id() of requests already
                                    # counted before reset_metrics
        # tracing: a serial tick id that reset_metrics leaves alone (the
        # spans and logs carry it); per decode tick its length and, per
        # step, the live slots and their summed contexts; per prefill
        # dispatch its program and rows (rid, start position, valid
        # tokens); the host time of ``step`` less its waits for the
        # device (``serve:*.sync``); programs compiled or loaded in step.
        # A model whose mixer kinds count (``qnext_moe``) adds each
        # step's counters to its tick's entry, by name
        self._tick = 0
        self.tick_log: Deque[dict] = deque(maxlen=LOG_LEN)
        self.prefill_log: Deque[dict] = deque(maxlen=LOG_LEN)
        self.sched_self_s = 0.0
        self.steps = 0
        self.compiles = 0
        spans.install()

    # ---------------------------------------------------- compat surface
    @property
    def spec(self):
        return self.executor.spec

    @property
    def prefill_chunk(self) -> int:
        return self.executor.prefill_chunk

    @property
    def plan_mode(self) -> str:
        return self.executor.plan_mode

    @property
    def prefill_batching(self) -> bool:
        return self.executor.prefill_batching

    @property
    def staging_depth(self) -> int:
        return self.executor.staging_depth

    @property
    def mesh(self):
        return self.executor.mesh

    @property
    def state_bytes_per_slot(self) -> int:
        return self.executor.state_bytes_per_slot

    @property
    def window_bytes_per_slot(self) -> int:
        return self.executor.window_bytes_per_slot

    @property
    def cache_bytes(self) -> int:
        return self.executor.cache_bytes

    @property
    def caches(self):
        return self.executor.caches

    @property
    def tokens(self):
        return self.executor.tokens

    @property
    def sampler(self):
        return self.executor.sampler

    @property
    def _staging(self) -> Optional[Request]:
        """Head-of-line staged request (back-compat view of the ring)."""
        return self._stagings[0].req if self._stagings else None

    # ------------------------------------------------------------ submit
    def submit(self, req: Request):
        # a decode-role engine never prefills: fresh prompts belong on a
        # prefill/both engine — it only adopts admitted state through
        # readmit_swapped (the prefill→decode handoff)
        if getattr(self, "role", "both") == "decode":
            raise ValueError(f"req {req.rid}: engine role is 'decode' — "
                             f"it accepts handoff images "
                             f"(readmit_swapped), not fresh prompts")
        # reject out-of-range sampling params up front: past this point the
        # host mirror and the device pipeline must behave identically
        if not 0.0 < req.top_p <= 1.0:
            raise ValueError(f"req {req.rid}: top_p must be in (0, 1], "
                             f"got {req.top_p}")
        if req.top_k < 0:
            raise ValueError(f"req {req.rid}: top_k must be >= 0, "
                             f"got {req.top_k}")
        if req.temperature <= 0.0 and (req.top_k > 0 or req.top_p < 1.0):
            raise ValueError(f"req {req.rid}: top_k/top_p have no effect "
                             f"at temperature<=0 (greedy); set "
                             f"temperature > 0")
        if req.max_new_tokens < 1:
            raise ValueError(f"req {req.rid}: max_new_tokens must be >= 1 "
                             f"(admit always emits the first token), got "
                             f"{req.max_new_tokens}")
        T = req.prompt_len
        if T is None:
            raise ValueError(f"req {req.rid}: needs a prompt or "
                             f"prompt_embeds")
        if T < 1:
            raise ValueError(f"req {req.rid}: empty prompt")
        if T > self.max_len:
            raise ValueError(
                f"req {req.rid}: prompt length {T} exceeds max_len "
                f"{self.max_len} — the window caches would wrap "
                f"mid-prompt and silently corrupt the context")
        if self.speculative and req.prompt is None:
            raise ValueError(
                f"req {req.rid}: prompt_embeds requests cannot run on a "
                f"speculative engine — the draft-state rebuild at slot "
                f"activation (draft_prefill_slot) replays the consumed "
                f"*token* stream, and embeds have no token ids to "
                f"replay; submit to a non-speculative engine")
        # the swap store and resume queue are keyed by rid, so a rid must
        # be unique among the engine's LIVE requests (finished rids may
        # recur — sessions reconnect)
        if req.rid in self.swapped or any(
                r.rid == req.rid and not r.done for r in self._all):
            raise ValueError(f"req {req.rid}: rid already live on this "
                             f"engine (swap bookkeeping is rid-keyed)")
        if self.max_live_requests is not None:
            live = (len(self.queue) + len(self._stagings)
                    + len(self.active) + len(self.swapped))
            if live >= self.max_live_requests:
                raise RuntimeError(
                    f"max_live_requests={self.max_live_requests} reached "
                    f"({live} live incl. swapped): admission refused — "
                    f"oversubscription caps host memory, not just slots")
        req.t_submit = time.perf_counter()
        req.t_last_activity = req.t_submit
        req.state = QUEUED
        self.queue.append(req)
        self._all.append(req)

    def withdraw(self, *, oldest: bool = False) -> Optional[Request]:
        """Remove and return a queued (not yet staging) request, or None.
        Used by the router to move backlog across engines: rebalance
        steals the *newest* (default — the head of the queue keeps its
        FIFO TTFT), drain migrates *oldest*-first so arrival order
        survives the full-queue move."""
        if not self.queue:
            return None
        req = self.queue.popleft() if oldest else self.queue.pop()
        # identity removal (Request is a dataclass; two equal-field
        # requests must not alias)
        idx = next(i for i, r in enumerate(self._all) if r is req)
        del self._all[idx]
        return req

    def readmit(self, req: Request):
        """Put a withdrawn request back at the queue tail (router's undo
        when no other engine can accept it); t_submit is preserved."""
        self.queue.append(req)
        self._all.append(req)

    def withdraw_swapped(self) -> Optional[_Swapped]:
        """Remove and return the *newest* resuming request's swap record
        (request + host-side device image), or None.  The image is plain
        host numpy in the topology-free staging layout, so the router
        can migrate a resume claim to any engine with the same arch
        config — swap-aware rebalance.  Newest-first keeps the FIFO head
        of this engine's resume queue (same rationale as ``withdraw``).

        Migration waits for harvest: a still-draining gather is
        force-harvested and a spilled image reloaded, so the record
        leaves with a complete in-memory image; a prestaged prefetch is
        device-resident on THIS engine's mesh and is dropped."""
        if not self.resume_q:
            return None
        rid = self.resume_q.pop()
        rec = self.swapped.pop(rid)
        if rec.pending is not None:
            self._harvest(rec, forced=not rec.pending.ready())
        if rec.spool is not None:
            self._load_spill(rec)
        self._drop_prefetch(rec)
        idx = next(i for i, r in enumerate(self._all)
                   if r is rec.req)
        del self._all[idx]
        return rec

    def withdraw_handoff(self) -> Optional[_Swapped]:
        """Remove and return the oldest completed-prefill swap record
        awaiting dispatch to a decode engine, or None.  Only meaningful
        on a ``role="prefill"`` engine — ``_swap_out_ready`` parks every
        admit-boundary swap it makes on the handoff queue.  Like
        ``withdraw_swapped``, the record leaves with a complete
        in-memory image (a still-draining gather is force-harvested, a
        spilled image reloaded); under async paging the D2H drain has
        normally already overlapped the prefill ticks that followed the
        swap-out, so the harvest here is a copy-out, not a stall."""
        while self._handoff_q:
            rid = self._handoff_q.popleft()
            rec = self.swapped.pop(rid, None)
            if rec is None:
                continue            # withdrawn through another path
            if rec.pending is not None:
                self._harvest(rec, forced=not rec.pending.ready())
            if rec.spool is not None:
                self._load_spill(rec)
            self._drop_prefetch(rec)
            idx = next(i for i, r in enumerate(self._all)
                       if r is rec.req)
            del self._all[idx]
            self.handoffs_out += 1
            return rec
        return None

    def readmit_swapped(self, rec: _Swapped):
        """Adopt a migrated swap record: the request joins this engine's
        resume queue and its image is restored through this engine's
        slot scatter at the next grant (re-sharded to this engine's mesh
        by ``restore_slot``)."""
        if rec.req.rid in self.swapped or any(
                r.rid == rec.req.rid and not r.done for r in self._all):
            raise ValueError(f"req {rec.req.rid}: rid already live on "
                             f"this engine")
        self._all.append(rec.req)
        self.swapped[rec.req.rid] = rec
        self.resume_q.append(rec.req.rid)
        rec.req.state = RESUMING

    @property
    def load(self) -> int:
        """Requests this engine still owes work to (router placement).
        Resuming requests claim a slot grant; dormant swapped ones cost
        only host memory and are excluded."""
        return (len(self.active) + len(self.queue) + len(self._stagings)
                + len(self.resume_q))

    # ----------------------------------------------- router-facing surface
    # Narrow read surface the Router uses instead of reaching into the
    # engine's internals — an ``EngineProxy`` mirrors exactly these from
    # its worker's status snapshots, so local engines and process-remote
    # workers are interchangeable behind the router.
    @property
    def handoffs(self) -> int:
        """Completed-prefill swap records awaiting handoff dispatch."""
        return len(self._handoff_q)

    @property
    def queue_len(self) -> int:
        return len(self.queue)

    @property
    def free_slots(self) -> int:
        return len(self.free)

    @property
    def staging_len(self) -> int:
        return len(self._stagings)

    @property
    def resume_len(self) -> int:
        return len(self.resume_q)

    @property
    def idle_capacity(self) -> int:
        """Free slots not already claimed by the engine's own backlog
        (queue, staging ring, or resume queue — a resuming request owns
        the next freed slot just as surely as a staged-ready one)."""
        return (self.free_slots - self.queue_len - self.staging_len
                - self.resume_len)

    def owns(self, rid: int) -> bool:
        """True when a live (not done) request with ``rid`` is resident
        here — queued, staging, active, resuming or swapped out."""
        return rid in self.swapped or any(
            r.rid == rid and not r.done for r in self._all)

    def done_requests(self) -> List[Request]:
        return [r for r in self._all if r.done]

    def _finished(self, req: Request, tok: int) -> bool:
        return (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id))

    # ------------------------------------------------------ state paging
    def pause(self, rid: int) -> Request:
        """Swap request ``rid`` out of device residency (its client went
        idle).  Wherever the request is in the lifecycle:

          * active       -> ONE gather program slices its cache column,
                            sampler row and last token to host; the slot
                            is freed;
          * staged-ready -> its staging row/buffer is gathered — it
                            never takes a slot;
          * mid-prefill  -> marked pause-pending: the chunk plan finishes
                            first and the swap happens at the admit
                            boundary (a partial prefill has no
                            admit-advanced sampler row to gather);
          * queued       -> removed from the queue; nothing is resident,
                            so the record's device image is None;
          * resuming     -> dropped from the resume queue back to
                            dormant (its image stays on host).

        On a speculative engine, pausing an active request while a draft
        is in flight (dispatched at the end of the previous tick) defers
        the swap to the next verify boundary — the mid-prefill deferral
        pattern applied to decode: between draft and verify the slot's
        residency is not a self-consistent image (its committed state
        trails un-verified proposals), so gathering it would capture
        state a later resume could not bitwise-continue from.  The
        request stays ACTIVE (and may emit the in-flight tick's verified
        tokens) until the next ``step`` verifies, then swaps out; a
        ``resume`` before that boundary just cancels the deferral.

        The request stays dormant until ``resume(rid)``; dormant
        requests do not block ``run_until_done``."""
        if rid in self.swapped:
            rec = self.swapped[rid]
            if rid in self.resume_q:
                self.resume_q.remove(rid)
                self._drop_prefetch(rec)    # cancelled resume: the
                # prestaged device image is dropped cleanly
                rec.req.state = SWAPPED
                return rec.req
            raise ValueError(f"req {rid} is already swapped out")
        for slot, req in self.active.items():
            if req.rid == rid:
                if self._pending is not None:
                    if not any(r == rid for r, _ in self._spec_deferred):
                        self._spec_deferred.append((rid, False))
                    return req      # swaps at the verify boundary
                return self._swap_out_active(slot)
        for st in self._stagings:
            if st.req.rid == rid:
                if st.ready:
                    self._swap_out_ready(st)
                else:
                    st.pause_pending = True
                return st.req
        for req in self.queue:
            if req.rid == rid:
                self.queue = deque(r for r in self.queue if r is not req)
                self.swapped[rid] = _Swapped(
                    req=req, state=None, t_swap=time.perf_counter())
                req.state = SWAPPED
                return req
        raise KeyError(f"no live request with rid {rid} to pause")

    def resume(self, rid: int) -> Request:
        """Bring a paused request back.  One that was swapped from the
        queue (no device image) rejoins the queue tail and re-prefills;
        one with a gathered image joins the resume queue and is swapped
        into the next granted slot — oldest-first, alternating fairly
        with staged-ready fresh admits.  A pending pause that has not
        reached its admit boundary yet is simply cancelled."""
        rec = self.swapped.get(rid)
        if rec is None:
            for i, (r, _res) in enumerate(self._spec_deferred):
                if r == rid:        # deferred mid-draft pause: cancel it
                    del self._spec_deferred[i]
                    return next(q for q in self.active.values()
                                if q.rid == rid)
            for st in self._stagings:
                if st.req.rid == rid and st.pause_pending:
                    st.pause_pending = False
                    return st.req
            raise KeyError(f"req {rid} is not swapped out")
        if rid in self.resume_q:
            raise ValueError(f"req {rid} is already resuming")
        req = rec.req
        if (rec.state is None and rec.pending is None
                and rec.spool is None):
            now = time.perf_counter()
            req.swapped_s += now - rec.t_swap
            req._swapped_pre_first_s += now - rec.t_swap
            del self.swapped[rid]
            self.queue.append(req)
            req.state = QUEUED
            req.t_last_activity = now
        else:
            self.resume_q.append(rid)
            req.state = RESUMING
        return req

    def preempt(self, rid: Optional[int] = None) -> Optional[Request]:
        """Evict an active request to host memory and queue it for
        automatic resume.  With ``rid`` the victim is explicit;
        otherwise the policy victim: lowest priority, ties broken by
        most recent slot activation (the oldest resident is evicted
        last — re-prefill/requeue work already sunk is protected).
        Returns the evicted request, or None when no slot is occupied.
        Like ``pause``, a preempt arriving while a speculative draft is
        in flight is deferred to the verify boundary (with automatic
        resume preserved)."""

        def _defer(req):
            if not any(r == req.rid for r, _ in self._spec_deferred):
                self._spec_deferred.append((req.rid, True))
            return req

        if rid is not None:
            for slot, req in self.active.items():
                if req.rid == rid:
                    if self._pending is not None:
                        return _defer(req)
                    return self._swap_out_active(slot, resume=True)
            raise KeyError(f"req {rid} is not active")
        if not self.active:
            return None
        slot = self._victim_slot()
        if self._pending is not None:
            return _defer(self.active[slot])
        return self._swap_out_active(slot, resume=True)

    def touch(self, rid: int):
        """Refresh request ``rid``'s activity lease — the idle policy
        swaps out active requests whose lease is older than
        ``idle_swap_ms``; a connected client calls this to keep its
        slot."""
        for r in self._all:
            if r.rid == rid and not r.done:
                r.t_last_activity = time.perf_counter()
                return
        raise KeyError(f"no live request with rid {rid}")

    def _victim_slot(self) -> int:
        return min(self.active,
                   key=lambda s: (self.active[s].priority,
                                  -(self.active[s]._t_active or 0.0)))

    def _ensure_gather_capacity(self):
        """Make room for one more async gather dispatch: when every
        gather-ring buffer is draining, force-harvest the oldest drain —
        the ledger guarantee that a draining buffer is never reused
        before harvest, paid for as stall instead of corruption."""
        while not self.executor._gather_free:
            self._harvest(self.swapped[self._draining_q[0]], forced=True)

    def _harvest(self, rec: _Swapped, *, forced: bool):
        """Materialize a DRAINING record's host image.  ``forced`` means
        the tick loop is blocking on it (sync path, ring pressure, or a
        grant that beat the drain) — that wait is the stall async paging
        exists to hide; an un-forced harvest found the transfer already
        complete and costs only the host-side copy-out."""
        t0 = time.perf_counter()
        rec.state = self.executor.harvest(rec.pending)
        dt = time.perf_counter() - t0
        rec.pending = None
        self._draining_q.remove(rec.req.rid)
        self.swap_s += dt
        self.swap_gather_s += dt
        if forced:
            self.swap_stall_s += dt
            self.swap_harvests_forced += 1
        else:
            self.swap_dispatch_s += dt
            self.swap_harvests_overlapped += 1

    def _harvest_sweep(self):
        """Tick-boundary harvest of every drain whose D2H transfer has
        completed — the background traffic lands without ever blocking
        decode."""
        for rid in list(self._draining_q):
            rec = self.swapped[rid]
            if rec.pending.ready():
                self._harvest(rec, forced=False)

    def flush_swaps(self):
        """Harvest ALL draining swap-outs now (tests/benches, and any
        caller that wants to inspect ``.state`` deterministically).
        Completed drains harvest as overlapped; incomplete ones stall."""
        while self._draining_q:
            rec = self.swapped[self._draining_q[0]]
            self._harvest(rec, forced=not rec.pending.ready())

    def _swap_out_active(self, slot: int, *, resume: bool = False):
        req = self.active.pop(slot)
        t0 = time.perf_counter()
        self._ensure_gather_capacity()
        pend = self.executor.gather_slot_async(slot)
        t1 = time.perf_counter()
        self.swap_s += t1 - t0
        self.swap_dispatch_s += t1 - t0
        self.swap_gather_s += t1 - t0
        self.swap_outs += 1
        self.swap_bytes += pend.nbytes
        self.free.append(slot)
        # t_swap is the DISPATCH stamp: parked-time exclusion spans
        # dispatch -> restore scatter, so overlapping the drain cannot
        # inflate reported TTFT/throughput
        rec = _Swapped(req=req, state=None, t_swap=t0, pending=pend)
        self.swapped[req.rid] = rec
        self._draining_q.append(req.rid)
        if not self.async_paging:
            self._harvest(rec, forced=True)     # sync fallback: block now
        if resume:
            self.resume_q.append(req.rid)
            req.state = RESUMING
        else:
            req.state = SWAPPED
        return req

    def _swap_out_ready(self, st: _Staging):
        """Admit-boundary swap: the request has its first token and an
        advanced sampler row, but no slot — gather the staging row
        instead of a slot column."""
        req = st.req
        t0 = time.perf_counter()
        self._ensure_gather_capacity()
        if self.executor.prefill_batching:
            pend = self.executor.bgather_row_async(st.buf)
            self._dirty_rows.add(st.buf)  # release-zeroed, then freed
        else:
            pend = self.executor.gather_staging_async(st.buf)
            self._free_bufs.append(st.buf)
        t1 = time.perf_counter()
        self.swap_s += t1 - t0
        self.swap_dispatch_s += t1 - t0
        self.swap_gather_s += t1 - t0
        self.swap_outs += 1
        self.swap_bytes += pend.nbytes
        self._stagings.remove(st)
        rec = _Swapped(req=req, state=None, t_swap=t0, pending=pend)
        self.swapped[req.rid] = rec
        self._draining_q.append(req.rid)
        if not self.async_paging:
            self._harvest(rec, forced=True)
        req.state = SWAPPED
        if self.role == "prefill":
            # disaggregation: every admit-boundary swap on a prefill
            # engine is a finished prefill whose image belongs on a
            # decode engine — park it for the router's handoff sweep
            self._handoff_q.append(req.rid)

    def _swap_in(self, rid: int, slot: int):
        rec = self.swapped.pop(rid)
        req = rec.req
        if rec.pending is not None:     # grant beat the drain
            self._harvest(rec, forced=not rec.pending.ready())
        if rec.spool is not None:
            self._load_spill(rec)
        t0 = time.perf_counter()
        if rec.prefetch is not None:
            prestaged, rec.prefetch = rec.prefetch, None
            self.swap_prefetch_hits += 1
            t1 = t0
        else:
            # inline put: the stall a prefetched grant avoids
            prestaged = self.executor.prestage_restore(rec.state)
            t1 = time.perf_counter()
            self.swap_s += t1 - t0
            self.swap_stall_s += t1 - t0
            self.swap_put_s += t1 - t0
        with spans.span("scatter", tick=self._tick):
            self.executor.restore_slot(slot, rec.state, prestaged=prestaged)
        self.scatter_dispatches += 1
        now = time.perf_counter()
        self.swap_s += now - t1
        self.swap_dispatch_s += now - t1
        self.swap_scatter_s += now - t1
        self.swap_ins += 1
        self.swap_bytes += rec.state.nbytes
        req.swapped_s += now - rec.t_swap
        self.active[slot] = req
        req.state = ACTIVE
        req._t_active = now
        req.t_last_activity = now
        self._draft_activate(slot, req)

    def _prefetch_resume(self):
        """Prestage the head resume claim's H2D put one tick ahead of a
        *predictable* grant (a slot is already free, or some active slot
        is within one tick of its budget) so the grant-boundary scatter
        consumes an already-device-resident image.  A cancelled resume
        just drops the triple (``pause``/``withdraw_swapped``)."""
        if not self.resume_q:
            return
        rec = self.swapped[self.resume_q[0]]
        if rec.prefetch is not None:
            return
        if not (self.free or any(
                r.max_new_tokens - len(r.output) <= self.decode_block
                for r in self.active.values())):
            return
        if rec.pending is not None:
            if not rec.pending.ready():
                return              # draining: let the D2H finish first
            self._harvest(rec, forced=False)
        if rec.spool is not None:
            self._load_spill(rec)
        t0 = time.perf_counter()
        rec.prefetch = self.executor.prestage_restore(rec.state)
        dt = time.perf_counter() - t0
        self.swap_s += dt
        self.swap_dispatch_s += dt
        self.swap_put_s += dt
        self.swap_prefetches += 1

    def _drop_prefetch(self, rec: _Swapped):
        if rec.prefetch is not None:
            rec.prefetch = None
            self.swap_prefetch_drops += 1

    # ---------------------------------------------------- spill-to-disk
    def _spill_path(self, rid: int) -> str:
        return os.path.join(self.swap_spool_dir, f"swap-{rid}.state")

    def _apply_spill(self):
        """Push the coldest dormant images out to the spool dir until
        in-memory swapped bytes fit under the ``host_swap_bytes``
        watermark.  Only images nothing is about to touch are eligible:
        not draining, not prefetched, not queued for resume."""
        limit = self.host_swap_bytes or 0
        while True:
            held = [r for r in self.swapped.values()
                    if r.state is not None]
            if sum(r.state.nbytes for r in held) <= limit:
                return
            cold = [r for r in held
                    if r.req.rid not in self.resume_q
                    and r.prefetch is None]
            if not cold:
                return
            self._spill(min(cold, key=lambda r: r.t_swap))

    def _spill(self, rec: _Swapped):
        """Spool-tier writer: the on-disk image is the wire encoding
        (``serving.wire`` — the SAME serializer the RPC migration path
        uses), treedef included, so nothing about a spilled session
        stays pinned in host memory."""
        os.makedirs(self.swap_spool_dir, exist_ok=True)
        path = self._spill_path(rec.req.rid)
        wire.dump_swapped(path, rec.state)
        rec.spool = path
        self.spills += 1
        self.spill_bytes += rec.state.nbytes
        rec.state = None

    def _load_spill(self, rec: _Swapped):
        """Transparent reload on resume: rebuild the ``SwappedState``
        from the spool file (bitwise — the wire codec frames every
        array with its exact dtype/shape) and delete it."""
        rec.state = wire.load_swapped(rec.spool)
        os.remove(rec.spool)
        rec.spool = None
        self.spill_loads += 1

    def _grant_resume(self) -> bool:
        """True when the next freed slot goes to the resume queue rather
        than a staged-ready fresh admit.  When both classes wait, grants
        strictly alternate — neither resumed sessions nor fresh prompts
        starve the other."""
        if not self.resume_q:
            return False
        if not (self._stagings and self._stagings[0].ready):
            return True
        return self._grant_resume_next

    def _apply_swap_policy(self):
        """Tick-boundary eviction sweep (``swap_policy != "manual"``).

        idle: an active request whose lease (``t_last_activity``) is
        older than ``idle_swap_ms`` is swapped out dormant — the serving
        analog of a chat session gone quiet; it re-enters via
        ``resume``.

        pressure: while a *strictly* higher-priority request waits
        (resume queue, staged-ready or queued) without a free slot, the
        lowest-priority active request is evicted to the resume queue.
        Strict inequality is the anti-thrash guard: equal priorities
        never displace each other."""
        now = time.perf_counter()
        if self.swap_policy in ("idle", "auto"):
            cutoff = self.idle_swap_ms / 1e3
            for slot in [s for s, r in self.active.items()
                         if now - r.t_last_activity > cutoff]:
                self._swap_out_active(slot)
        if self.swap_policy in ("pressure", "auto"):
            while self.active:
                waiting = sorted(
                    [self.swapped[r].req.priority for r in self.resume_q]
                    + [s.req.priority for s in self._stagings if s.ready]
                    + [r.priority for r in self.queue], reverse=True)
                if len(self.free) >= len(waiting):
                    break
                # highest-priority waiter not already covered by a free
                # slot; each eviction frees one, so the walk terminates
                need = waiting[len(self.free)]
                slot = self._victim_slot()
                if need <= self.active[slot].priority:
                    break
                self._swap_out_active(slot, resume=True)

    # ----------------------------------------------------------- staging
    def _stage_start(self, req: Request):
        buf = self._free_bufs.popleft()
        req.state = STAGING
        # prefill role: swap out at the admit boundary instead of holding
        # the request staged-ready — the same pause-pending machinery a
        # mid-prefill pause() uses, so the image is complete (prompt
        # consumed, first token emitted, sampler row advanced) and the
        # finished-at-admit check still completes EOS / 1-token requests
        # in place, no handoff needed
        handoff = self.role == "prefill"
        if self.executor.prefill_batching:
            # batched path: no fixed plan — the per-tick packer allocates
            # chunks; begin is host-only (rows are release-zeroed by the
            # multi-row scatter, so starting a staging costs no dispatch)
            T = req.prompt_len
            C = self.executor.prefill_chunk
            tail = (T - 1) % C + 1
            self._stagings.append(_Staging(
                req=req, plan=[], buf=buf,
                chunks_left=(T - tail) // C, tail=tail,
                pause_pending=handoff))
            self.executor.bstage_begin(
                buf, seed=self.seed, rid=req.rid,
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, eos_id=req.eos_id,
                budget=req.max_new_tokens)
            return
        self._stagings.append(_Staging(
            req=req, plan=self.executor.plan_prefill(req.prompt_len),
            buf=buf, pause_pending=handoff))
        self.executor.stage_begin(
            buf, seed=self.seed, rid=req.rid, temperature=req.temperature,
            top_k=req.top_k, top_p=req.top_p, eos_id=req.eos_id,
            budget=req.max_new_tokens)

    def _prefill(self, program: str, rows: list, dispatch, *args):
        """One prefill dispatch of ``program``, logged with its rows
        ``(rid, start position, valid tokens)`` and run under a
        ``serve:prefill.dispatch`` span."""
        self.prefill_log.append({"tick": self._tick, "program": program,
                                 "rows": rows})
        with spans.span("prefill.dispatch", tick=self._tick,
                        program=program):
            dispatch(*args)
        self.stage_dispatches += 1

    def _stage_dispatch_one(self, st: _Staging):
        step = st.plan[st.plan_pos]
        chunk = st.req._inputs[st.prompt_pos:st.prompt_pos + step.tokens]
        rows = [(st.req.rid, st.prompt_pos, step.tokens)]
        ex = self.executor
        if step.kind == "scan":
            self._prefill("prefill_scan", rows, ex.stage_chunk_scan,
                          st.buf, chunk, step.valid)
        elif step.kind == "chunk":
            self._prefill("prefill_chunk", rows, ex.stage_chunk, st.buf,
                          chunk)
        else:
            self._prefill("admit", rows, ex.stage_admit, st.buf, chunk,
                          step.valid)
        st.prompt_pos += step.tokens
        st.plan_pos += 1

    def _stage_finish(self, st: _Staging):
        """Plan complete: sync the fused first token (this is the
        device-confirmed admit — TTFT is stamped here, not when the
        dispatch was queued) and either complete the request (EOS /
        max_new_tokens=1, never occupying a slot) or hold it staged-ready
        until a slot frees."""
        req = st.req
        tok = self.executor.admit_token(st.buf)
        req.t_first = time.perf_counter()
        req.output.append(tok)
        if self._finished(req, tok):
            req.done = True
            req.state = DONE
            req.t_done = req.t_first
            self._stagings.remove(st)
            self._free_bufs.append(st.buf)
            return
        if st.pause_pending:
            self._swap_out_ready(st)    # the admit-boundary swap
            return
        st.ready = True
        req.state = READY

    def _stage_scatter(self):
        st = self._stagings.pop(0)
        slot = self.free.popleft()
        with spans.span("scatter", tick=self._tick):
            self.executor.scatter(slot, st.buf)
        self.scatter_dispatches += 1
        self._free_bufs.append(st.buf)
        self.active[slot] = st.req
        self._activate(st.req)
        self._draft_activate(slot, st.req)

    def _activate(self, req: Request):
        req.state = ACTIVE
        now = time.perf_counter()
        req._t_active = now
        req.t_last_activity = now

    def _draft_activate(self, slot: int, req: Request):
        """Rebuild the draft model's per-slot state at every slot
        activation (fresh admit and swap-in alike) by replaying the
        request's consumed tokens — prompt plus every emitted token
        except the last, which is the next decode input.  This is what
        keeps the swap image draft-free: a speculative engine's
        ``SwappedState`` is byte-identical to a non-speculative one's,
        and the draft residency is reconstructed in ONE fixed-shape
        dispatch."""
        if not self.speculative:
            return
        toks = np.asarray(req.prompt, np.int32).reshape(-1)
        if len(req.output) > 1:
            toks = np.concatenate(
                [toks, np.asarray(req.output[:-1], np.int32)])
        self.executor.draft_prefill_slot(slot, toks)
        self.draft_prefills += 1

    # --------------------------------------------------- batched staging
    def _flush_scatter(self, assigns):
        """One multi-row scatter covering every slot assignment plus the
        dirty (finished-at-admit) rows; released rows return to the free
        pool clean."""
        rows = [row for _, row in assigns]
        with spans.span("scatter", tick=self._tick):
            self.executor.bscatter(assigns, self._dirty_rows)
        self.scatter_dispatches += 1
        for row in rows:
            self._free_bufs.append(row)
        for row in self._dirty_rows:
            self._free_bufs.append(row)
        self._dirty_rows.clear()

    def _stage_finish_batch(self, sts: List[_Staging]):
        """Every request admitted by one batched dispatch syncs its first
        token from the SAME device-confirmed read and stamps the SAME
        ``t_first`` — a batch admit is one device event, so serial
        per-entry stamps would skew TTFT for all but the first row."""
        toks = self.executor.admit_tokens()         # the one host sync
        now = time.perf_counter()
        for st in sts:
            req = st.req
            tok = int(toks[st.buf])
            req.t_first = now
            req.output.append(tok)
            if self._finished(req, tok):
                req.done = True
                req.state = DONE
                req.t_done = now
                self._stagings.remove(st)
                self._dirty_rows.add(st.buf)    # zeroed at next scatter
            elif st.pause_pending:
                self._swap_out_ready(st)        # the admit-boundary swap
            else:
                st.ready = True
                req.state = READY

    def _dispatch_batched(self, budget: int) -> bool:
        """One packed prefill round: walk the staging FIFO oldest-first,
        allocating each entry up to ``budget`` scan-chunk units (an admit
        costs one unit), then fuse all allocations into at most one
        batched scan + one batched admit dispatch per input kind.  The
        walk never skips past an unfinished older entry once the budget
        runs out — head-of-line (oldest-first) allocation is the
        fairness guard: a long staged prompt always drains at full rate,
        so its dispatch count is bounded by its own chunk count no matter
        how many short prompts arrive behind it.  Interior chunks are
        C-quantized (masks cover only tails and placeholder rows), so
        each prompt's chunk decomposition — and therefore its token
        stream — is bitwise that of per-prompt dispatch."""
        scan_e: Dict[bool, list] = {}
        admit_e: Dict[bool, list] = {}
        scan_rows: Dict[bool, list] = {}
        admit_rows: Dict[bool, list] = {}
        admitted: List[_Staging] = []
        for st in self._stagings:
            if st.ready or st.admitted:
                continue
            if budget <= 0:
                break               # strict oldest-first: no skip-ahead
            is_embeds = st.req.prompt is None
            if st.chunks_left:
                take = min(st.chunks_left, self._max_scan_chunks, budget)
                C = self.executor.prefill_chunk
                chunk = st.req._inputs[st.prompt_pos:
                                       st.prompt_pos + take * C]
                scan_e.setdefault(is_embeds, []).append(
                    (st.buf, chunk, take))
                scan_rows.setdefault(is_embeds, []).append(
                    (st.req.rid, st.prompt_pos, take * C))
                st.prompt_pos += take * C
                st.chunks_left -= take
                budget -= take
            if st.chunks_left == 0 and budget > 0:
                chunk = st.req._inputs[st.prompt_pos:
                                       st.prompt_pos + st.tail]
                admit_e.setdefault(is_embeds, []).append(
                    (st.buf, chunk, st.tail))
                admit_rows.setdefault(is_embeds, []).append(
                    (st.req.rid, st.prompt_pos, st.tail))
                st.prompt_pos += st.tail
                st.admitted = True
                admitted.append(st)
                budget -= 1
        for kind, entries in scan_e.items():
            self._prefill("prefill_scan", scan_rows[kind],
                          self.executor.bstage_chunk_scan, entries)
        for kind, entries in admit_e.items():
            self._prefill("admit", admit_rows[kind],
                          self.executor.bstage_admit, entries)
        if admitted:
            self._stage_finish_batch(admitted)
        return bool(scan_e or admit_e)

    def _admit_batched(self):
        """Batched admit pipeline: per tick, at most ONE multi-row
        scatter, then new stagings (host-only), then one packed prefill
        round of at most one batched scan + one batched admit dispatch
        per input kind — dispatches per tick are O(1) in queue depth.
        While slots are free the loop drains work-conservingly (same
        admits as the serialized baseline); under saturation one round
        per tick keeps the resident slots decoding between prefill
        programs."""
        while True:
            progressed = False
            # slot grants: resume-queue swap-ins (restore through the
            # slot scatter, oldest first) interleave with the multi-row
            # scatter of head-run staged-ready requests — when both
            # classes wait, grants strictly alternate (FIFO within each)
            assigns = []
            while self.free and (self.resume_q
                                 or (self._stagings
                                     and self._stagings[0].ready)):
                if self._grant_resume():
                    self._swap_in(self.resume_q.popleft(),
                                  self.free.popleft())
                    self._grant_resume_next = False
                    progressed = True
                else:
                    st = self._stagings.pop(0)
                    slot = self.free.popleft()
                    assigns.append((slot, st.buf))
                    self.active[slot] = st.req
                    self._activate(st.req)
                    self._draft_activate(slot, st.req)
                    self._grant_resume_next = True
            if assigns:
                self._flush_scatter(assigns)
                progressed = True
            # start staging while rows allow; a dirty row blocks a start
            # only until a release-only scatter cleans it
            while (self.queue and (self.free or self.overlap)):
                if not self._free_bufs:
                    if self._dirty_rows:
                        self._flush_scatter([])
                        progressed = True
                        continue
                    break
                self._stage_start(self.queue.popleft())
                progressed = True
            # one packed prefill round; infinite budget while a slot is
            # free (work-conserving parity with the serialized baseline)
            budget = (self._budget_chunks if not self.free
                      else 1 << 30)
            if self._dispatch_batched(budget):
                progressed = True
            if not self.free and self.active:
                return              # saturated: one round per tick
            if not progressed:
                return

    def _admit(self):
        """Advance the admit pipeline at a tick boundary.

        Work-conserving: while free slots exist, queued requests prefill
        and scatter exactly as the serialized baseline does.  The overlap
        is purely additive — when every slot is busy, up to
        ``staging_depth`` head-of-queue requests *still* stream their
        chunk plans into the staging ring, **one chunk dispatch per
        staged request per tick** so the resident slots keep decoding
        between chunks, and emit their fused-sample first tokens at plan
        completion, held staged-ready until slots free (scattered in FIFO
        order).  Overlapped TTFT is therefore never structurally worse
        than serialized, and strictly better whenever a request would
        have had to wait for a slot before prefilling.

        With ``prefill_batching`` (the default when every mixer kind
        supports it) the per-entry loop is replaced by
        ``_admit_batched``: all staged prompts fuse into one batched
        program per dispatch and dispatches per tick are O(1) in queue
        depth."""
        if self.executor.prefill_batching:
            return self._admit_batched()
        yielded = set()     # stagings that already dispatched this tick
        while True:
            # resume swap-ins share freed slots with the FIFO scatter of
            # staged-ready requests (strict alternation under contention)
            if self.free and self._grant_resume():
                self._swap_in(self.resume_q.popleft(), self.free.popleft())
                self._grant_resume_next = False
                continue
            # FIFO scatter: the head staged-ready request takes the slot
            if self._stagings and self._stagings[0].ready:
                if self.free:
                    self._stage_scatter()
                    self._grant_resume_next = True
                    continue    # next queued request may start staging
            # start staging while ring buffers allow (serialized admit
            # waits for a free slot up front)
            if (self.queue and self._free_bufs
                    and (self.free or self.overlap)):
                self._stage_start(self.queue.popleft())
                continue
            st = next((s for s in self._stagings
                       if not s.ready and id(s) not in yielded), None)
            if st is None:
                return
            self._stage_dispatch_one(st)
            if st.plan_pos == len(st.plan):
                self._stage_finish(st)
            elif not self.free and self.active:
                yielded.add(id(st))     # ahead-of-slot: one chunk per tick
                                        # so the resident slots decode
                                        # between prefill chunks

    # -------------------------------------------------------------- tick
    def _tick_k(self) -> int:
        """Budget-aware tick length: smallest power-of-two bucket (capped
        at ``decode_block``) covering the largest remaining per-slot
        budget — the all-slots-finish-early tail stops burning masked
        scan steps, and bucketing bounds the program cache."""
        if not self.budget_ticks:
            return self.decode_block
        need = max(r.max_new_tokens - len(r.output)
                   for r in self.active.values())
        k = 1
        while k < need and k < self.decode_block:
            k <<= 1
        return min(k, self.decode_block)

    def _spec_k(self) -> int:
        """Budget-aware draft length: smallest power-of-two bucket (capped
        at ``k_draft``, and at the acceptance-adapted effective k when
        ``adaptive_k`` is on) covering the largest remaining budget
        *minus the verify's own guaranteed emission* — a slot with one
        token left needs no draft at all (k = 0 is a verify-only
        1-position tick)."""
        kmax = self._k_eff if self.adaptive_k else self.k_draft
        if not self.budget_ticks:
            return kmax
        need = max(r.max_new_tokens - len(r.output)
                   for r in self.active.values())
        if need <= 1:
            return 0
        k = 1
        while k < need - 1 and k < kmax:
            k <<= 1
        return min(k, kmax)

    def _adapt_k(self, accepted: int, drafted: int):
        """Acceptance-adaptive draft length: over a short window of
        draft-verify ticks, a collapsed acceptance rate halves the
        effective k (floor 1 — a verify tick always emits its own
        sample) and a high rate doubles it back (cap ``k_draft``).  Each
        adjustment clears the window so the next decision is measured at
        the new k.  Token streams are unaffected — the shared-key verify
        emits the same tokens at any k; only the drafted-but-rejected
        work per sync changes."""
        self._accept_window.append((accepted, drafted))
        if len(self._accept_window) < self._accept_window.maxlen:
            return
        d = sum(x[1] for x in self._accept_window)
        if d == 0:
            return
        rate = sum(x[0] for x in self._accept_window) / d
        if rate < 0.5 and self._k_eff > 1:
            self._k_eff = max(1, self._k_eff // 2)
            self._accept_window.clear()
        elif rate > 0.8 and self._k_eff < self.k_draft:
            self._k_eff = min(self.k_draft, self._k_eff * 2)
            self._accept_window.clear()

    def _step_speculative(self):
        """One speculative engine tick, pipelined across the step
        boundary: verify the draft dispatched at the END of the previous
        step (the tick's one host sync), emit, drain pause/preempt
        requests deferred to this verify boundary, then run the normal
        policy sweep + admit pipeline and dispatch the next draft.
        Admits, swap-ins and evictions therefore only ever happen
        *between* a verify and the next draft — a pending draft never
        straddles a slot-population change."""
        if self._pending is not None:
            k, dtoks, live = self._pending
            self._pending = None
            t0 = time.perf_counter()
            toks, valid = self.executor.spec_verify(k, dtoks)
            now = time.perf_counter()
            self.decode_s += now - t0
            self.ticks += 1
            self.spec_ticks += 1
            self.drafted_tokens += k * len(live)
            tick_accepted = 0
            with spans.span("emit", tick=self._tick):
                for slot, req in list(self.active.items()):
                    emitted = 0
                    for j in range(toks.shape[0]):
                        if not valid[j, slot]:
                            break
                        tok = int(toks[j, slot])
                        req.output.append(tok)
                        self.decoded_tokens += 1
                        emitted += 1
                        if self._finished(req, tok):
                            req.done = True
                            req.state = DONE
                            req.t_done = now
                            del self.active[slot]
                            self.free.append(slot)
                            break
                    # every emission beyond the first rode on an accepted
                    # draft token (the first is the verify's own sample)
                    tick_accepted += max(emitted - 1, 0)
            self.accepted_tokens += tick_accepted
            if self.adaptive_k and k > 0:
                self._adapt_k(tick_accepted, k * len(live))
            if self._spec_deferred:
                deferred, self._spec_deferred = self._spec_deferred, []
                for rid, res in deferred:
                    slot = next((s for s, r in self.active.items()
                                 if r.rid == rid), None)
                    if slot is not None:    # may have finished in verify
                        self._swap_out_active(slot, resume=res)
        self._boundary()
        if not self.active:
            return
        k = self._spec_k()
        t0 = time.perf_counter()
        dtoks = self.executor.spec_draft(k)     # async — no host sync
        self.decode_s += time.perf_counter() - t0
        self._pending = (k, dtoks,
                         [r.rid for r in self.active.values()])

    def _boundary(self):
        """The tick boundary before the decode dispatch: the paging
        sweeps that are on, then the admit pipeline."""
        if self.async_paging and self._draining_q:
            with spans.span("paging.harvest"):
                self._harvest_sweep()
        if self.swap_spool_dir is not None:
            with spans.span("paging.spill"):
                self._apply_spill()
        if self.swap_policy != "manual":
            with spans.span("paging.policy"):
                self._apply_swap_policy()
        with spans.span("admit", tick=self._tick):
            self._admit()
        if self.async_paging:
            with spans.span("paging.prefetch"):
                self._prefetch_resume()

    def step(self):
        """One engine tick: advance the admit pipeline (free slots fill as
        in the serialized baseline, plus up to ``staging_depth``
        ahead-of-slot staged prefills when every slot is busy), then one
        fused decode+sample scan, then emit and free — a single host sync
        for the decode block.

        Speculative engines run the draft–verify tick instead (see
        ``_step_speculative``).  Either runs under a ``serve:step`` span
        carrying a new tick id; the step's host time less its waits for
        the device adds to ``sched_self_s``, and the programs it compiled
        or loaded to ``compiles``."""
        self._tick += 1
        self.executor.tick = self._tick
        compiles, waits = spans.compiles(), self.executor.sync_s
        t0 = time.perf_counter()
        with spans.span("step", tick=self._tick):
            if self.speculative:
                self._step_speculative()
            else:
                self._step_decode()
        self.sched_self_s += (time.perf_counter() - t0
                              - (self.executor.sync_s - waits))
        self.steps += 1
        self.compiles += spans.compiles() - compiles

    def _step_decode(self):
        self._boundary()
        if not self.active:
            return
        k = self._tick_k()
        t0 = time.perf_counter()
        toks, valid = self.executor.decode(k)   # (k, S) — the one host sync
        now = time.perf_counter()
        self.decode_s += now - t0
        self.ticks += 1
        # the live slots entering each step and their summed contexts
        live, ctx = [0] * toks.shape[0], [0] * toks.shape[0]
        with spans.span("emit", tick=self._tick):
            for slot, req in list(self.active.items()):
                pos = req.prompt_len + len(req.output)
                for j in range(toks.shape[0]):
                    if not valid[j, slot]:
                        break
                    live[j] += 1
                    ctx[j] += pos + j
                    tok = int(toks[j, slot])
                    req.output.append(tok)
                    self.decoded_tokens += 1
                    if self._finished(req, tok):
                        req.done = True
                        req.state = DONE
                        req.t_done = now
                        del self.active[slot]
                        self.free.append(slot)
                        break
        self.tick_log.append({"tick": self._tick, "k": k, "live": live,
                              "ctx": ctx, **self.executor.decode_counts})

    def run_until_done(self, max_ticks: int = 10_000, *,
                       strict: bool = True) -> List[Request]:
        """Tick until queue, staging ring, slots and resume queue drain.
        Dormant swapped-out requests (paused without resume) are NOT
        pending work — the loop returns with them still parked on
        host."""
        for _ in range(max_ticks):
            if (not self.queue and not self.active and not self._stagings
                    and not self.resume_q):
                break
            self.step()
        if (self.queue or self.active or self._stagings
                or self.resume_q):
            msg = (f"run_until_done: max_ticks={max_ticks} exhausted with "
                   f"{len(self.queue)} queued, {len(self.active)} active, "
                   f"{len(self._stagings)} staging, {len(self.resume_q)} "
                   f"resuming request(s) unfinished — raise max_ticks or "
                   f"inspect the engine")
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning)
        return [r for r in self._all if r.done]

    # ----------------------------------------------------------- metrics
    def reset_metrics(self):
        """Zero the aggregate counters (benchmarks call this after a
        warm-up pass so compile time stays out of the measurement).

        The per-request window is marked by *completion*, not by
        submission: a request submitted (or paused) before the reset
        that finishes after it still counts.  The old watermark over
        ``_all`` assumed submit -> finish was one slot residency; a
        request can now sit swapped out across a reset."""
        self.ticks = 0
        self.decode_s = 0.0
        self.decoded_tokens = 0
        self.stage_dispatches = 0
        self.scatter_dispatches = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_s = 0.0
        self.swap_bytes = 0
        self.swap_dispatch_s = 0.0
        self.swap_stall_s = 0.0
        self.swap_gather_s = 0.0
        self.swap_put_s = 0.0
        self.swap_scatter_s = 0.0
        self.swap_prefetches = 0
        self.swap_prefetch_hits = 0
        self.swap_prefetch_drops = 0
        self.swap_harvests_overlapped = 0
        self.swap_harvests_forced = 0
        self.spills = 0
        self.spill_loads = 0
        self.spill_bytes = 0
        self.spec_ticks = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.draft_prefills = 0
        self.handoffs_out = 0
        self.tick_log.clear()
        self.prefill_log.clear()
        self.sched_self_s = 0.0
        self.steps = 0
        self.compiles = 0
        self._metrics_seen = {id(r) for r in self._all if r.done}

    def metrics(self) -> Dict[str, float]:
        """Aggregate serving metrics over requests completed since the
        last ``reset_metrics`` (all requests by default)."""
        done = [r for r in self._all
                if r.done and id(r) not in self._metrics_seen]
        ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
        lats = [r.latency_s for r in done if r.latency_s is not None]
        tps = [r.tokens_per_s for r in done if r.tokens_per_s is not None]
        mesh = self.executor.mesh
        progs = self.executor.compiled_programs()
        return {
            "requests": len(done),
            "tokens": sum(len(r.output) for r in done),
            "ticks": self.ticks,
            "decode_block": self.decode_block,
            "decoded_tokens": self.decoded_tokens,
            "decode_s": self.decode_s,
            "decode_us_per_token":
                self.decode_s / max(1, self.decoded_tokens) * 1e6,
            "stage_dispatches": self.stage_dispatches,
            "scatter_dispatches": self.scatter_dispatches,
            "overlap": int(self.overlap),
            "prefill_chunk": self.executor.prefill_chunk,
            "plan_mode": self.executor.plan_mode,
            "prefill_batching": int(self.executor.prefill_batching),
            "compiled_programs": progs["total"],
            "prefill_programs": progs["prefill"],
            "staging_depth": self.staging_depth,
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "swapped": len(self.swapped),
            "resuming": len(self.resume_q),
            "swap_s": self.swap_s,
            "swap_bytes": self.swap_bytes,
            "swap_us_per_mb": (self.swap_s * 1e6
                               / (self.swap_bytes / 2 ** 20)
                               if self.swap_bytes else 0.0),
            "swap_bytes_per_slot": self.executor.swap_bytes_per_slot,
            "async_paging": int(self.async_paging),
            "gather_ring": self.executor.gather_ring,
            "swap_dispatch_s": self.swap_dispatch_s,
            "swap_stall_s": self.swap_stall_s,
            "swap_gather_s": self.swap_gather_s,
            "swap_put_s": self.swap_put_s,
            "swap_scatter_s": self.swap_scatter_s,
            "swap_prefetches": self.swap_prefetches,
            "swap_prefetch_hits": self.swap_prefetch_hits,
            "swap_prefetch_drops": self.swap_prefetch_drops,
            "swap_harvests_overlapped": self.swap_harvests_overlapped,
            "swap_harvests_forced": self.swap_harvests_forced,
            "swap_overlap_ratio": (
                self.swap_harvests_overlapped
                / max(1, self.swap_harvests_overlapped
                      + self.swap_harvests_forced)),
            "spills": self.spills,
            "spill_loads": self.spill_loads,
            "spill_bytes": self.spill_bytes,
            "host_swap_bytes_held": sum(
                r.state.nbytes for r in self.swapped.values()
                if r.state is not None),
            "role": self.role,
            "handoffs": len(self._handoff_q),
            "handoffs_out": self.handoffs_out,
            "speculative": int(self.speculative),
            "k_draft": self.k_draft if self.speculative else 0,
            "adaptive_k": int(self.adaptive_k),
            "k_draft_effective":
                (self._k_eff if self.speculative and self.adaptive_k
                 else (self.k_draft if self.speculative else 0)),
            "spec_ticks": self.spec_ticks,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "acceptance_rate":
                self.accepted_tokens / max(1, self.drafted_tokens),
            "syncs_per_token": self.ticks / max(1, self.decoded_tokens),
            "draft_prefills": self.draft_prefills,
            "checkpoint_bytes_per_slot":
                (self.executor.checkpoint_bytes_per_slot
                 if self.speculative else 0),
            "draft_bytes_per_slot":
                (self.executor.draft_bytes_per_slot
                 if self.speculative else 0),
            "speculative_bytes":
                (self.executor.speculative_bytes
                 if self.speculative else 0),
            "mesh_data": int(mesh.shape["data"]) if mesh is not None else 1,
            "mesh_model": (int(mesh.shape["model"])
                           if mesh is not None else 1),
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "mean_latency_s": float(np.mean(lats)) if lats else 0.0,
            "mean_tokens_per_s": float(np.mean(tps)) if tps else 0.0,
            "steps": self.steps,
            "sched_self_s": self.sched_self_s,
            "compiles": self.compiles,
            "tick_log": list(self.tick_log),
            "prefill_log": list(self.prefill_log),
        }
