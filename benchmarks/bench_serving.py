"""Serving-engine benchmarks: host-sync overhead, TTFT under load and
cold-start compile cost.

All measurements run on the reduced CPU configs (absolute numbers are
CPU-interpreter scale; only the trend is the claim):

1. **decode-block sweep** — the engine fuses ``decode_block`` (k)
   decode+sample steps per tick into one on-device ``lax.scan`` and syncs
   with the host once per block (``lm.decode_steps``).  Sweeping k in
   {1, 4, 16} measures the per-token host round-trip cost the
   device-resident loop removes: µs/token should improve monotonically
   with k.

2. **TTFT under load** — requests are queued while every decode slot is
   busy with a long-budget request.  With ``overlap=False`` (the
   serialized baseline) a queued prompt prefills only after a slot frees,
   on the tick thread; with ``overlap=True`` it streams chunk-by-chunk
   into the staging buffer between decode ticks and emits its first token
   (fused on-device sample) *before* any slot frees.  The benchmark
   reports mean TTFT of the queued requests for both modes, asserts the
   overlapped mean is strictly better, and asserts the token streams are
   bitwise identical (overlap moves timing, never sampling).

3. **cold TTFT: masked vs pow2 chunk plans** — the first prompt a fresh
   engine serves pays jit tracing + XLA compilation for every program its
   chunk plan touches.  The masked planner dispatches at most TWO
   distinct prefill shapes per prompt (one scan + one fixed-size masked
   tail) where the pow2 baseline compiles a program per power-of-two
   tail sub-chunk, so cold TTFT (submit → first token device-confirmed,
   compiles included) drops with the program count.  The benchmark
   serves one awkward-length prompt on a fresh engine per mode
   (median-of-trials), reports both TTFTs, and asserts the masked
   planner's *prefill program count* is strictly smaller (the wall-clock
   is reported, not asserted — CI machines are noisy).

4. **burst prefill: batched vs per-prompt staging** — ``depth`` prompts
   arrive at once while every slot decodes.  The per-prompt path
   dispatches one chunk program per staged request per tick (O(depth)
   dispatches/tick); the batched packer fuses all staged prompts into
   one fixed-shape scan + one admit per tick (O(1), asserted at depth
   ∈ {1, 4, 8}).  At depth 8 the batched aggregate prefill throughput
   is asserted ≥ 1.5× the per-prompt baseline, with bitwise-identical
   token streams.

5. **slot oversubscription** — N interleaved sessions with idle gaps
   rotate through S << N slots via host-swapped state (pause/resume),
   once with synchronous paging and once with ``async_paging=True``.
   Token streams are asserted bitwise identical across both modes AND a
   dedicated-slot engine (one slot per session), per mixer kind with
   mixed greedy/stochastic sessions; swap µs/MiB is reported against
   the spec-derived per-slot byte budget, plus the swap-stall breakdown
   (gather / put / scatter µs per swap and the harvest overlap ratio).
   Async paging is asserted to spend measurably less blocked-host time
   per swap than the synchronous baseline, with overlap ratio > 0.

6. **mesh scaling** — (multi-device backends only, e.g.
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU) the
   engine's slot axis is data-parallel over the mesh: holding the
   per-device slot count fixed and growing the data axis grows tokens
   per tick at (ideally) constant tick latency.  The benchmark reports
   per-tick decode throughput at data ∈ {1, 4} and the speedup.  On
   real accelerators the speedup is asserted ≥ 1.5× at data=4; on CPU
   the "devices" are threads carved from the same cores, so the number
   is *reported as a measurement only* (documented in
   ``docs/serving.md`` — virtual devices share the host's FLOPs, which
   is exactly the situation the assertion would be meaningless in).

7. **speculative decode** — draft–verify with self-draft (acceptance ≈
   1, the upper bound) against a ``decode_block = k_draft``
   non-speculative baseline on the same mixed greedy/stochastic session
   set.  Streams are asserted bitwise identical and host syncs per
   emitted token strictly lower; acceptance rate and tokens/s are
   reported for both engines.

8. **disaggregated prefill/decode** — a mixed workload (long decode
   sessions + a storm of long-prompt prefill-only requests) served by
   two ``EngineWorker`` processes, once colocated (both workers serve
   both roles) and once disaggregated (one prefill worker pauses every
   request at the admit boundary and ships the swapped image to one
   decode worker).  Long-session decode throughput is measured with and
   without the concurrent storm; the storm-induced degradation is
   asserted *strictly lower* disaggregated than colocated (decode ticks
   never share an engine with prefill work), with all streams bitwise
   identical to a single-engine reference.

Each engine is built through ``make_engine``, which runs the warm-up
pass so jit compilation stays out of the measurement
(``reset_metrics``).  Run with ``--quick`` for the CI smoke
configuration, with a subcommand name (e.g. ``spec_decode``) to run one
benchmark, and with ``--json PATH`` to also write every emitted result
as per-subcommand machine-readable records.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit
from repro import configs
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.serving.engine import DecodeEngine, Request


def _serve(eng, n_req: int, max_new: int):
    reqs = [Request(rid=i, prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=max_new) for i in range(n_req)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)


_ARCHES = {}


def arch_setup(arch: str):
    """Reduced-CPU config + randomly-initialised params for ``arch``,
    cached so every subcommand shares one init."""
    if arch not in _ARCHES:
        cfg = configs.get_arch(arch).reduced()
        _ARCHES[arch] = (cfg, lm.init_lm(jax.random.PRNGKey(0), cfg))
    return _ARCHES[arch]


def make_engine(cfg, params, *, warm: int = 0, warm_prompt=None,
                warm_new: int = 9, warm_paging: bool = False, **kw):
    """Build a ``DecodeEngine`` and run its warm-up pass so jit
    compilation stays out of the measurement.

    ``warm`` requests of ``warm_prompt`` (default: 8 tokens) with a
    ``warm_new`` budget compile every program the measured phase
    touches — the prompt's chunk plan, the tick buckets, admit and
    scatter, and on a speculative engine the draft / verify /
    draft-prefill programs as well.  ``warm_paging`` additionally
    round-trips one pause/resume so the state-gather and swap-in
    programs compile too.  Metrics are reset before returning."""
    eng = DecodeEngine(cfg, params, **kw)
    prompt = (np.arange(1, 9, dtype=np.int32) if warm_prompt is None
              else warm_prompt)
    if warm:
        for i in range(warm):
            eng.submit(Request(rid=10_000 + i, prompt=prompt,
                               max_new_tokens=warm_new))
        eng.run_until_done()
    if warm_paging:
        w = Request(rid=10_000 + warm, prompt=prompt,
                    max_new_tokens=warm_new)
        eng.submit(w)
        eng.step()
        eng.pause(w.rid)
        eng.step()      # a speculative engine swaps at the verify boundary
        eng.resume(w.rid)
        eng.run_until_done()
    eng.reset_metrics()
    return eng


def run_block_sweep(quick: bool = False):
    archs = ("qwen3-next-gdn",) if quick else ("qwen3-next-gdn",
                                               "mamba2-1.3b")
    blocks = (1, 4) if quick else (1, 4, 16)
    max_new = 9 if quick else 17         # 1 admit token + k*ticks decode
    for arch in archs:
        cfg, params = arch_setup(arch)
        for k in blocks:
            eng = make_engine(cfg, params, warm=2, warm_new=k + 1,
                              max_slots=4, max_len=64, decode_block=k)
            _serve(eng, 8, max_new)
            m = eng.metrics()
            emit(f"serving/{arch}/k{k}", m["decode_us_per_token"],
                 f"decode_block={k};decoded_tokens={m['decoded_tokens']};"
                 f"ticks={m['ticks']};mean_ttft_ms="
                 f"{m['mean_ttft_s'] * 1e3:.1f};slots=4;reduced_cpu")


def _ttft_load(cfg, params, *, overlap: bool, n_queued: int,
               trials: int):
    """Queued-admits-while-slots-decode scenario.

    Two long-budget requests (staggered completions) occupy both slots;
    the measured requests then queue behind them.  Serialized admit can
    only prefill a queued prompt once a slot frees; overlapped admit
    prefills it ahead of any free slot and emits its first token while
    both slots are still mid-decode.  Returns (median-of-``trials`` mean
    TTFT of the queued requests, token streams of the last trial) — the
    median keeps a single noisy CI run from polluting the comparison.
    """
    prompt = np.arange(1, 34, dtype=np.int32)            # 33 tokens
    # 3 warm-up requests also run a queued request through staging
    eng = make_engine(cfg, params, warm=3, warm_prompt=prompt,
                      max_slots=2, max_len=128, decode_block=4,
                      overlap=overlap, prefill_chunk=8)
    means = []
    for trial in range(trials):
        eng.reset_metrics()
        base = 1000 * trial
        load = [Request(rid=base + 100 + i, prompt=prompt,
                        max_new_tokens=48 + 20 * i) for i in range(2)]
        for r in load:
            eng.submit(r)
        eng.step()              # admit the load before the queued arrivals
        queued = [Request(rid=base + i, prompt=prompt, max_new_tokens=13)
                  for i in range(n_queued)]
        for r in queued:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in load + queued)
        means.append(float(np.mean([r.ttft_s for r in queued])))
        streams = [list(r.output) for r in load + queued]
    return float(np.median(means)), streams


def run_ttft_under_load(quick: bool = False):
    arch = "qwen3-next-gdn"
    n_queued = 2
    trials = 3 if quick else 5
    cfg, params = arch_setup(arch)
    serialized, s_streams = _ttft_load(cfg, params, overlap=False,
                                       n_queued=n_queued, trials=trials)
    overlapped, o_streams = _ttft_load(cfg, params, overlap=True,
                                       n_queued=n_queued, trials=trials)
    assert o_streams == s_streams, \
        "overlap must move timing only — token streams diverged"
    for mode, ttft in (("serialized", serialized),
                       ("overlapped", overlapped)):
        emit(f"serving/{arch}/ttft_load_{mode}", ttft * 1e6,
             f"mean_ttft_ms={ttft * 1e3:.1f};queued={n_queued};"
             f"trials={trials};slots=2;decode_block=4;prefill_chunk=8;"
             f"reduced_cpu")
    speedup = serialized / max(overlapped, 1e-12)
    emit(f"serving/{arch}/ttft_load_speedup", speedup,
         f"serialized_over_overlapped;bitwise_identical_streams")
    assert overlapped < serialized, (
        f"overlapped admit must beat the serialized baseline under load: "
        f"{overlapped * 1e3:.1f} ms >= {serialized * 1e3:.1f} ms")


def _cold_ttft(cfg, params, *, plan_mode: str, prompt_len: int,
               trials: int):
    """First-prompt TTFT on a fresh engine: tracing + compile + prefill.

    A fresh ``DeviceExecutor`` per trial means every prefill program in
    the prompt's chunk plan is compiled from scratch (jit caches key on
    the per-engine closures), which is exactly the cold-start cost the
    masked planner shrinks.  Returns (median TTFT s, prefill program
    count, token stream of the last trial)."""
    ttfts = []
    for trial in range(trials):
        eng = DecodeEngine(cfg, params, max_slots=2, max_len=128,
                           decode_block=4, prefill_chunk=8,
                           plan_mode=plan_mode)
        req = Request(rid=trial, prompt=np.arange(1, prompt_len + 1,
                                                  dtype=np.int32),
                      max_new_tokens=5)
        eng.submit(req)
        eng.run_until_done()
        ttfts.append(req.ttft_s)
        stream = list(req.output)
    progs = eng.executor.compiled_programs()["prefill"]
    return float(np.median(ttfts)), progs, stream


def run_cold_ttft(quick: bool = False):
    """Cold-TTFT comparison of the masked planner vs the pow2 baseline.

    77 tokens with chunk 8 is an awkward length: pow2 needs scan(4) +
    scan(1) + chunk(4) + admit(1) = 4 prefill programs, masked needs
    scan(3) + masked admit = 2."""
    arch = "qwen3-next-gdn"
    cfg, params = arch_setup(arch)
    trials = 3 if quick else 5
    results = {}
    for mode in ("pow2", "masked"):
        ttft, progs, stream = _cold_ttft(cfg, params, plan_mode=mode,
                                         prompt_len=77, trials=trials)
        results[mode] = (ttft, progs, stream)
        emit(f"serving/{arch}/cold_ttft_{mode}", ttft * 1e3,
             f"first_prompt_ttft_ms_incl_compiles;prefill_programs="
             f"{progs};prompt_len=77;prefill_chunk=8;trials={trials};"
             f"reduced_cpu")
    assert results["masked"][2] == results["pow2"][2], \
        "plan mode must move compile counts only — token streams diverged"
    assert results["masked"][1] < results["pow2"][1], (
        f"masked planning must compile strictly fewer prefill programs: "
        f"{results['masked'][1]} vs {results['pow2'][1]}")
    emit(f"serving/{arch}/cold_ttft_speedup",
         results["pow2"][0] / max(results["masked"][0], 1e-12),
         f"pow2_over_masked;prefill_programs_"
         f"{results['pow2'][1]}_vs_{results['masked'][1]}")


def _tick_throughput(cfg, params, *, data: int, slots_per_shard: int,
                     max_new: int, trials: int) -> float:
    """Decode-only tokens/s of one saturated engine at data-axis size
    ``data`` (slot count = data * slots_per_shard, all slots busy)."""
    slots = data * slots_per_shard
    mesh = mesh_mod.make_serving_mesh(data, 1) if data > 1 else None
    eng = make_engine(cfg, params, warm=slots, max_slots=slots,
                      max_len=64, decode_block=8, mesh=mesh)
    best = 0.0
    for _ in range(trials):
        eng.reset_metrics()
        _serve(eng, slots, max_new)            # every slot decodes
        m = eng.metrics()
        best = max(best, m["decoded_tokens"] / max(m["decode_s"], 1e-12))
    return best


def run_mesh_scaling(quick: bool = False):
    """Per-tick decode throughput vs the data-axis size (slot-axis DP).

    Needs >= 4 visible devices; under
    ``--xla_force_host_platform_device_count`` the devices are host
    threads, so the measured speedup is emitted but only *asserted* on
    real multi-device backends (see module docstring)."""
    if jax.device_count() < 4:
        emit("serving/mesh_scaling/skipped", 0.0,
             f"device_count={jax.device_count()}<4;set XLA_FLAGS="
             f"--xla_force_host_platform_device_count=8 for the CPU "
             f"smoke measurement")
        return
    arch = "qwen3-next-gdn"
    cfg, params = arch_setup(arch)
    trials = 2 if quick else 3
    max_new = 17 if quick else 33
    tput = {d: _tick_throughput(cfg, params, data=d, slots_per_shard=2,
                                max_new=max_new, trials=trials)
            for d in (1, 4)}
    for d, t in tput.items():
        emit(f"serving/{arch}/mesh_data{d}", t,
             f"decode_tokens_per_s;slots={2 * d};slots_per_shard=2;"
             f"decode_block=8;reduced_cpu_virtual_devices")
    speedup = tput[4] / max(tput[1], 1e-12)
    cpu_virtual = jax.default_backend() == "cpu"
    emit(f"serving/{arch}/mesh_scaling_speedup", speedup,
         f"data4_over_data1;asserted={not cpu_virtual};"
         f"{'cpu_virtual_devices_share_host_flops' if cpu_virtual else 'real_devices'}")
    if not cpu_virtual:
        assert speedup >= 1.5, (
            f"slot-axis DP must scale decode throughput on real devices: "
            f"data=4 gave {speedup:.2f}x over data=1 (< 1.5x)")


def _burst_prefill(cfg, params, *, depth: int, batching: bool,
                   trials: int):
    """Burst arrival under saturation: ``depth`` prompts submitted at
    once while both slots decode long budgets, stepped manually so every
    tick's staged-prefill dispatch count is observable.

    Returns (max prefill dispatches in any tick, median aggregate
    prefill throughput in prompt tokens/s from burst submission to the
    last first-token, token streams of the last trial)."""
    import time
    prompt = np.arange(1, 58, dtype=np.int32)          # 57 = 7 chunks + 1
    eng = make_engine(cfg, params, warm=depth + 2, warm_prompt=prompt,
                      max_slots=2, max_len=128, decode_block=4,
                      overlap=True, prefill_chunk=8,
                      staging_depth=depth, prefill_batching=batching)
    disp_max, tputs = 0, []
    for trial in range(trials):
        base = 1000 * (trial + 1)
        load = [Request(rid=base + 100 + i, prompt=prompt,
                        max_new_tokens=70 + 10 * i) for i in range(2)]
        for r in load:
            eng.submit(r)
        eng.step()              # both slots busy before the burst lands
        burst = [Request(rid=base + i, prompt=prompt, max_new_tokens=4)
                 for i in range(depth)]
        t0 = time.perf_counter()
        for r in burst:
            eng.submit(r)
        ticks = 0
        while any(r.t_first is None for r in burst):
            d0 = eng.stage_dispatches
            eng.step()
            disp_max = max(disp_max, eng.stage_dispatches - d0)
            ticks += 1
            assert ticks < 500, "burst prefill stalled"
        tputs.append(depth * len(prompt) / (time.perf_counter() - t0))
        eng.run_until_done()
        assert all(r.done for r in load + burst)
        streams = [list(r.output) for r in load + burst]
    return disp_max, float(np.median(tputs)), streams


def run_burst_prefill(quick: bool = False):
    """Batched multi-prompt prefill vs the per-prompt baseline under
    burst arrivals.

    The per-prompt path dispatches one chunk program per staged request
    per tick, so its dispatch count per tick grows linearly with the
    staging depth; the batched packer fuses every staged prompt into one
    fixed-shape scan + one admit program per tick — O(1) in queue depth
    (asserted at every depth).  Fewer, wider dispatches are also faster
    end to end: at depth 8 the batched aggregate prefill throughput
    (burst submission -> last first-token) is asserted >= 1.5x the
    per-prompt baseline, with bitwise-identical token streams."""
    arch = "qwen3-next-gdn"
    cfg, params = arch_setup(arch)
    trials = 2 if quick else 3
    tput = {}
    for depth in (1, 4, 8):
        res = {}
        for mode, batching in (("batched", True), ("per_prompt", False)):
            disp, tps, streams = _burst_prefill(
                cfg, params, depth=depth, batching=batching,
                trials=trials)
            res[mode] = (disp, tps, streams)
            emit(f"serving/{arch}/burst_prefill_{mode}_d{depth}", tps,
                 f"prompt_tokens_per_s;max_dispatches_per_tick={disp};"
                 f"depth={depth};prompt_len=57;prefill_chunk=8;slots=2;"
                 f"trials={trials};reduced_cpu")
        assert res["batched"][2] == res["per_prompt"][2], (
            f"depth={depth}: batching must move dispatch shapes only — "
            f"token streams diverged")
        # O(1) dispatches per tick: <= 1 fixed-shape scan + 1 admit
        # regardless of depth (the per-prompt path pays one dispatch per
        # staged request per tick)
        assert res["batched"][0] <= 2, (
            f"depth={depth}: batched packer dispatched "
            f"{res['batched'][0]} prefill programs in one tick")
        if depth >= 4:
            assert res["per_prompt"][0] >= depth // 2, (
                f"depth={depth}: per-prompt baseline no longer scales "
                f"with depth ({res['per_prompt'][0]} dispatches/tick) — "
                f"the comparison lost its contrast")
        tput[depth] = (res["batched"][1], res["per_prompt"][1])
    speedup = tput[8][0] / max(tput[8][1], 1e-12)
    emit(f"serving/{arch}/burst_prefill_speedup_d8", speedup,
         f"batched_over_per_prompt;bitwise_identical_streams")
    assert speedup >= 1.5, (
        f"batched prefill must beat the per-prompt baseline at depth 8: "
        f"{speedup:.2f}x < 1.5x")


_MIXERS = {
    "gdn": "qwen3-next-gdn",
    "ssm": "mamba2-1.3b",
    "rglru": "recurrentgemma-2b",
    "attn": "yi-9b",
    "swa": "h2o-danube-1.8b",
}


def _oversubscribe_rotate(cfg, params, *, n: int, slots: int,
                          make_sessions, **kw):
    """One oversubscribed rotation: every tick the engine reconnects the
    oldest parked session (a "client came back") and pauses the
    most-recently-activated resident (its "client went idle"), so
    sessions take repeated swap round-trips for as long as the workload
    runs.  Returns (token streams, metrics)."""
    from collections import deque
    eng = make_engine(cfg, params, warm_paging=True, max_slots=slots,
                      max_len=64, decode_block=2, prefill_chunk=8, **kw)
    live = make_sessions()
    for r in live:
        eng.submit(r)
    parked = deque()
    ticks = 0
    while not all(r.done for r in live):
        ticks += 1
        assert ticks < 3000, "oversubscribed rotation stalled"
        if parked:
            eng.resume(parked.popleft())    # oldest client reconnects
        if len(eng.active) > 1:
            # the newest resident goes idle mid-stream
            slot = max(eng.active,
                       key=lambda s: eng.active[s]._t_active)
            parked.append(eng.active[slot].rid)
            eng.pause(parked[-1])
        eng.step()
    while parked:
        eng.resume(parked.popleft())
    eng.run_until_done()
    assert all(r.done for r in live)
    return [list(r.output) for r in live], eng.metrics()


def run_oversubscribe(quick: bool = False):
    """Slot oversubscription: N interleaved sessions with idle gaps
    rotate through S << N device slots via host-swapped state — once
    synchronous, once with ``async_paging=True``.

    Token streams are asserted bitwise identical across sync paging,
    async paging AND a dedicated-slot engine with one slot per session —
    paging (and its overlap) moves placement and timing, never a token —
    for each mixer kind (all five when full, a recurrent + a KV-window
    kind under ``--quick``; per-kind async parity is also pinned by
    tests/test_state_paging.py), with mixed greedy/stochastic sessions.
    Reported: swap traffic and µs/MiB against the spec-derived per-slot
    byte budget (``cache_spec`` state + rolling window + sampler row),
    plus the swap-stall breakdown — gather / put / scatter µs per swap,
    blocked-host stall vs non-blocking dispatch time, and the harvest
    overlap ratio.  Asserted: async overlap ratio > 0 (sync is 0 by
    construction: every gather is force-harvested at dispatch) and async
    blocked-host stall per swap strictly below the synchronous
    baseline's."""
    kinds = ("gdn", "attn") if quick else tuple(_MIXERS)
    n, slots = (8, 2) if quick else (16, 4)

    def make_sessions():
        return [Request(rid=i,
                        prompt=np.arange(1, 6 + (i % 5) * 3,
                                         dtype=np.int32),
                        max_new_tokens=10 + (i % 4),
                        temperature=0.8 if i % 3 == 0 else 0.0,
                        top_k=10 if i % 3 == 0 else 0,
                        top_p=0.9 if i % 3 == 0 else 1.0)
                for i in range(n)]

    for kind in kinds:
        arch = _MIXERS[kind]
        cfg, params = arch_setup(arch)

        # dedicated-slot reference: every session keeps its own slot
        ded = DecodeEngine(cfg, params, max_slots=n, max_len=64,
                           decode_block=2, prefill_chunk=8)
        ref = make_sessions()
        for r in ref:
            ded.submit(r)
        ded.run_until_done()
        ref_streams = [list(r.output) for r in ref]

        res = {}
        for mode, apg in (("sync", False), ("async", True)):
            streams, m = _oversubscribe_rotate(
                cfg, params, n=n, slots=slots,
                make_sessions=make_sessions, async_paging=apg)
            assert streams == ref_streams, (
                f"{kind}/{mode}: oversubscription must be bitwise: "
                f"paging moves state, never a token")
            assert m["swap_outs"] >= n // 2, (
                f"{kind}/{mode}: rotation produced too little swap "
                f"traffic: {m['swap_outs']}")
            assert m["swap_ins"] == m["swap_outs"], \
                f"{kind}/{mode}: a parked session never resumed"
            res[mode] = m

            swaps = m["swap_outs"] + m["swap_ins"]
            stall_us = m["swap_stall_s"] / swaps * 1e6
            kib_slot = m["swap_bytes_per_slot"] / 2 ** 10
            emit(f"serving/{arch}/oversubscribe_swap_us_per_mb_{mode}",
                 m["swap_us_per_mb"],
                 f"slots={slots};sessions={n};swap_outs={m['swap_outs']};"
                 f"swap_mib={m['swap_bytes'] / 2 ** 20:.2f};"
                 f"kib_per_swap={kib_slot:.1f};bitwise_vs_dedicated;"
                 f"reduced_cpu")
            emit(f"serving/{arch}/oversubscribe_swap_stall_us_{mode}",
                 stall_us,
                 f"blocked_host_us_per_swap;swaps={swaps};"
                 f"dispatch_s={m['swap_dispatch_s']:.4f};"
                 f"stall_s={m['swap_stall_s']:.4f};"
                 f"gather_us_per_swap="
                 f"{m['swap_gather_s'] / swaps * 1e6:.1f};"
                 f"put_us_per_swap={m['swap_put_s'] / swaps * 1e6:.1f};"
                 f"scatter_us_per_swap="
                 f"{m['swap_scatter_s'] / swaps * 1e6:.1f};"
                 f"overlap_ratio={m['swap_overlap_ratio']:.3f};"
                 f"harvests_overlapped={m['swap_harvests_overlapped']};"
                 f"harvests_forced={m['swap_harvests_forced']};"
                 f"prefetch_hits={m['swap_prefetch_hits']}")

        sync_m, async_m = res["sync"], res["async"]
        assert sync_m["swap_overlap_ratio"] == 0.0, \
            f"{kind}: sync paging cannot overlap a harvest"
        assert async_m["swap_overlap_ratio"] > 0.0, (
            f"{kind}: async paging overlapped no harvest with the tick "
            f"({async_m['swap_harvests_forced']} forced)")
        sync_stall = sync_m["swap_stall_s"] / (sync_m["swap_outs"]
                                               + sync_m["swap_ins"])
        async_stall = async_m["swap_stall_s"] / (async_m["swap_outs"]
                                                 + async_m["swap_ins"])
        assert async_stall < sync_stall, (
            f"{kind}: async paging must lower blocked-host stall per "
            f"swap: {async_stall * 1e6:.1f} us >= "
            f"{sync_stall * 1e6:.1f} us")
        emit(f"serving/{arch}/oversubscribe_async_stall_reduction",
             sync_stall / max(async_stall, 1e-12),
             f"sync_over_async_blocked_host_us_per_swap;"
             f"sync_us={sync_stall * 1e6:.1f};"
             f"async_us={async_stall * 1e6:.1f};"
             f"overlap_ratio={async_m['swap_overlap_ratio']:.3f};"
             f"bitwise_identical_streams")


def run_spec_decode(quick: bool = False):
    """Speculative decode (self-draft) vs the non-speculative baseline.

    Both engines serve the same mixed greedy/stochastic session set; the
    baseline fuses ``decode_block = k_draft`` steps per tick (its best
    host-sync amortisation), the speculative engine drafts ``k_draft``
    and verifies, emitting up to ``k_draft + 1`` tokens per sync.  Token
    streams are asserted bitwise identical (the whole point of the
    shared-key verify) and, because self-draft acceptance is near 1,
    host syncs per emitted token are asserted *strictly lower* than the
    baseline's.  Reported: µs/token, tokens/s, acceptance rate,
    syncs/token for both engines."""
    arch = "qwen3-next-gdn"
    cfg, params = arch_setup(arch)
    k = 4
    n, max_new = (6, 13) if quick else (12, 25)
    slots = 2 if quick else 4

    def sessions():
        return [Request(rid=i,
                        prompt=np.arange(1, 6 + (i % 5) * 3,
                                         dtype=np.int32),
                        max_new_tokens=max_new - (i % 4),
                        temperature=0.8 if i % 3 == 0 else 0.0,
                        top_k=10 if i % 3 == 0 else 0,
                        top_p=0.9 if i % 3 == 0 else 1.0)
                for i in range(n)]

    res = {}
    for mode, spec in (("baseline", False), ("speculative", True)):
        eng = make_engine(cfg, params, warm=2, warm_new=k + 2,
                          max_slots=slots, max_len=64,
                          decode_block=k, speculative=spec, k_draft=k)
        reqs = sessions()
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        m = eng.metrics()
        res[mode] = ([list(r.output) for r in reqs], m)
        tps = m["decoded_tokens"] / max(m["decode_s"], 1e-12)
        emit(f"serving/{arch}/spec_decode_{mode}",
             m["decode_us_per_token"],
             f"decode_tokens_per_s={tps:.1f};"
             f"syncs_per_token={m['syncs_per_token']:.4f};"
             f"acceptance_rate={m['acceptance_rate']:.3f};"
             f"drafted={m['drafted_tokens']};"
             f"accepted={m['accepted_tokens']};k_draft={k};"
             f"slots={slots};sessions={n};self_draft;reduced_cpu")
    base_m, spec_m = res["baseline"][1], res["speculative"][1]
    assert res["speculative"][0] == res["baseline"][0], (
        "speculative decode must be bitwise: the shared-key verify "
        "emits exactly the non-speculative stream")
    assert spec_m["acceptance_rate"] > 0, "self-draft accepted nothing"
    assert spec_m["syncs_per_token"] < base_m["syncs_per_token"], (
        f"at acceptance {spec_m['acceptance_rate']:.2f} > 0, host syncs "
        f"per emitted token must strictly decrease: "
        f"{spec_m['syncs_per_token']:.4f} >= "
        f"{base_m['syncs_per_token']:.4f}")
    emit(f"serving/{arch}/spec_decode_sync_reduction",
         base_m["syncs_per_token"] / max(spec_m["syncs_per_token"],
                                         1e-12),
         f"baseline_syncs_per_token_over_speculative;"
         f"acceptance={spec_m['acceptance_rate']:.3f};"
         f"bitwise_identical_streams")


def _disagg_longs(n, max_new, rid0=0):
    """Long decode sessions (short prompt, long budget), mixed
    greedy/stochastic.  Streams depend only on (rid, sampler params,
    engine seed) — identical across topologies and phases."""
    return [Request(rid=rid0 + i, prompt=np.arange(1, 9, dtype=np.int32),
                    max_new_tokens=max_new,
                    temperature=0.8 if i % 2 == 0 else 0.0,
                    top_k=10 if i % 2 == 0 else 0,
                    top_p=0.9 if i % 2 == 0 else 1.0)
            for i in range(n)]


def _disagg_storm(cfg, n, plen, rid0=1000):
    """Prefill-only storm: long prompts with a 1-token budget — each
    request completes at the admit boundary (its single token is the
    fused admit sample), so it is pure staged-prefill load that never
    takes a slot and never hands off."""
    prompt = (np.arange(1, plen + 1) % (cfg.vocab - 2) + 1).astype(
        np.int32)
    return [Request(rid=rid0 + i, prompt=prompt, max_new_tokens=1)
            for i in range(n)]


def run_disagg(quick: bool = False):
    """Disaggregated prefill/decode over worker processes vs colocated.

    Two ``EngineWorker`` subprocesses behind the router, each with its
    own interpreter and jax runtime.  Colocated: both workers serve
    both roles, so every long decode session shares its engine's tick
    loop with storm prefill chunks.  Disaggregated: the prefill worker
    pauses every request at the admit boundary and the router ships the
    swapped image to the decode worker — storm chunks and decode ticks
    run in different processes.

    Per topology: phase A serves the long sessions alone (baseline
    throughput T0, mean per-request tokens/s over active time), phase B
    serves the same sessions under a concurrent prefill storm (T1).
    Degradation = T0/T1.  Asserted: every long-session stream (both
    topologies, both phases) is bitwise the single-engine reference
    stream; the prefill worker decodes zero tokens; disaggregated
    degradation is strictly below colocated.  Reported: T0, T1,
    degradation per topology and the colocated/disagg degradation
    ratio.

    This process stays off JAX's backend until the workers are gone (on
    a TPU host each worker owns one chip); the single-engine reference
    runs last."""
    from repro.serving.engine import Router
    from repro.serving.rpc import EngineProxy, worker_chips

    arch = "qwen3-next-gdn"
    cfg = configs.get_arch(arch).reduced()      # workers build the weights
    n_long, max_new = (2, 24) if quick else (2, 48)
    n_storm, plen = (6, 96) if quick else (12, 96)
    kw = dict(max_slots=2, max_len=128, decode_block=2, prefill_chunk=8)
    chips = worker_chips(2)

    degradation = {}
    all_streams = {}
    for mode, roles in (("colocated", ("both", "both")),
                        ("disagg", ("prefill", "decode"))):
        engines = [EngineProxy(cfg, params_seed=0, chip=chip, role=role,
                               **kw)
                   for chip, role in zip(chips, roles)]
        router = Router(engines)
        # warm-up: compile every program the measured phases touch on
        # every worker (long-session chunk plan + decode on both, the
        # storm-length chunk plan on prefill-capable workers, and for
        # disagg the handoff gather/restore-scatter pair)
        warm = (_disagg_longs(2, 4, rid0=500)
                + _disagg_storm(cfg, 2, plen, rid0=700))
        for r in warm:
            router.submit(r)
        router.run_until_done()
        router.reset_metrics()

        streams = {}
        tps = {}
        for phase, stormy in (("unloaded", False), ("stormy", True)):
            longs = _disagg_longs(n_long, max_new)
            for r in longs:
                router.submit(r)
            storm = _disagg_storm(cfg, n_storm, plen) if stormy else []
            for r in storm:
                router.submit(r)
            router.run_until_done()
            assert all(r.done for r in longs + storm)
            streams[phase] = [list(r.output) for r in longs]
            tps[phase] = float(np.mean([r.tokens_per_s for r in longs]))

        m = router.metrics()
        if mode == "disagg":
            assert m["handoffs"] >= n_long * 2, (
                f"disagg served {n_long * 2} long sessions but shipped "
                f"only {m['handoffs']} handoffs")
            assert m["per_engine"][0]["decoded_tokens"] == 0, (
                "the prefill worker must never run a decode tick")
        degradation[mode] = tps["unloaded"] / max(tps["stormy"], 1e-12)
        emit(f"serving/{arch}/disagg_decode_degradation_{mode}",
             degradation[mode],
             f"unloaded_tokens_per_s={tps['unloaded']:.2f};"
             f"stormy_tokens_per_s={tps['stormy']:.2f};"
             f"workers=2;roles={','.join(roles)};"
             f"long_sessions={n_long};storm={n_storm}x{plen}tok;"
             f"handoffs={m['handoffs']};"
             f"bitwise_vs_single_engine;reduced_cpu")
        for e in engines:
            e.shutdown()
        all_streams[mode] = streams

    # single-engine colocated reference: the bitwise target
    _, params = arch_setup(arch)
    ref_eng = make_engine(cfg, params, **kw)
    ref = _disagg_longs(n_long, max_new)
    for r in ref:
        ref_eng.submit(r)
    ref_eng.run_until_done()
    ref_streams = [list(r.output) for r in ref]
    for mode, streams in all_streams.items():
        for phase, got in streams.items():
            assert got == ref_streams, (
                f"{mode}/{phase}: disaggregated serving must be "
                f"bitwise: the handoff restores the exact admit-"
                f"boundary image")

    assert degradation["disagg"] < degradation["colocated"], (
        f"disaggregation must shield decode from prefill load: "
        f"degradation {degradation['disagg']:.3f}x (disagg) >= "
        f"{degradation['colocated']:.3f}x (colocated)")
    emit(f"serving/{arch}/disagg_degradation_ratio",
         degradation["colocated"] / max(degradation["disagg"], 1e-12),
         f"colocated_over_disagg_decode_degradation;"
         f"colocated={degradation['colocated']:.3f};"
         f"disagg={degradation['disagg']:.3f};"
         f"bitwise_identical_streams")


SUBCOMMANDS = {
    "block_sweep": run_block_sweep,
    "ttft_under_load": run_ttft_under_load,
    "cold_ttft": run_cold_ttft,
    "burst_prefill": run_burst_prefill,
    "oversubscribe": run_oversubscribe,
    "mesh_scaling": run_mesh_scaling,
    "spec_decode": run_spec_decode,
    "disagg": run_disagg,
}


def run(quick: bool = False, only=None, json_path=None):
    """Run ``only`` (a subcommand name) or every subcommand; with
    ``json_path``, write the ``emit`` records grouped per subcommand as
    machine-readable JSON (the ``BENCH_*.json`` artifact trajectory)."""
    from benchmarks.common import drain_results
    names = [only] if only else list(SUBCOMMANDS)
    drain_results()
    grouped = {}
    for name in names:
        SUBCOMMANDS[name](quick=quick)
        grouped[name] = drain_results()
    if json_path:
        import json
        with open(json_path, "w") as f:
            json.dump({"benchmark": "bench_serving",
                       "quick": bool(quick),
                       "subcommands": grouped}, f, indent=2)
        print(f"wrote {sum(len(v) for v in grouped.values())} results "
              f"to {json_path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("subcommand", nargs="?", default=None,
                    choices=sorted(SUBCOMMANDS),
                    help="run one benchmark (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke config: one arch, k in {1, 4}, plus the "
                         "overlap-on/off TTFT-under-load comparison and "
                         "(4+ devices) the mesh-scaling measurement")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write per-subcommand machine-readable "
                         "results (name/value/derived records) to PATH")
    args = ap.parse_args()
    compile_cache.enable()
    run(quick=args.quick, only=args.subcommand, json_path=args.json)
