"""One run of one cell: set-up, the measured window, the drain, the
correctness check and the result line.

The system under test is the program's serving path as a user runs it:
``repro.launch.serve.parse_args`` -> ``build`` -> ``Router``.  The
harness passes only the architecture, ``--full``, the slot count,
``max_len`` and the seed; every other knob stays at the program's
default.  It drives ``Router.submit`` / ``Router.step`` itself, on the
schedule of the cell's traffic, and stamps every token on its own clock
after each step.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from harness import correct as correct_mod
from harness import spec
from harness import trace as trace_mod
from harness import traffic as traffic_mod
from harness.cost import Cost
from harness.peaks import PEAKS, peak_for
from harness.stats import percentile

DRAIN_S = 60.0            # how long past the close a due request may take
TRACE_S = 3.0             # length of the traced sub-window
PROBE_PROMPT = 71         # > 4 chunks of 16: one full scan and a tail


class NoDevice(SystemExit):
    """No accelerator, too few chips, or a chip with no published peaks:
    the run exits non-zero and prints no result."""

    def __init__(self, msg: str):
        print(f"error: {msg}", file=sys.stderr)
        super().__init__(3)


def check_device(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        if d.platform != "tpu":
            raise NoDevice(f"no TPU: JAX's default backend is "
                           f"{d.platform!r}")
        if len(devs) < chips:
            raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)}")
        try:
            peak_for(d.device_kind)
        except KeyError as e:
            raise NoDevice(str(e)) from None
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def program_seed(seed: int) -> int:
    """The 31-bit seed the program takes, derived from the run's seed
    (the program keeps seeds and request ids in int32)."""
    return int(np.random.SeedSequence([seed, 0]).generate_state(1)[0]
               & 0x7FFFFFFF)


@dataclass
class Record:
    item: traffic_mod.Item
    sched: float                  # scheduled send, s from window start
    submit: float                 # actual send
    req: object
    stamps: List[float] = field(default_factory=list)

    @property
    def in_window(self) -> bool:
        return not self.item.warm


@dataclass
class DecodeCall:
    start: float
    end: float
    live: List[int]               # live slots entering each step
    ctx: List[int]                # their summed context lengths


@dataclass
class Run:
    """What the metric readers see.  Times are seconds from the window's
    opening."""
    seconds: float
    slots: int
    setup_s: float
    records: List[Record]
    steps: List[tuple]            # (start, end, active after) per step
    decode_calls: List[DecodeCall]
    decode_s: float               # the program's own decode timer
    drain_end: float
    cost: Cost
    peak: Optional[dict]          # the chip's published peaks (None off it)
    trace: Optional[dict] = None
    trace_span: Optional[tuple] = None
    lateness: List[float] = field(default_factory=list)
    compiles: int = 0             # programs compiled or loaded in the window

    @property
    def window(self) -> List[Record]:
        return [r for r in self.records if r.in_window]

    def in_window(self, t: float) -> bool:
        return 0.0 <= t <= self.seconds


_COMPILES: List[float] = []
_LISTENING: list = []


def _listen_compiles():
    """Count the programs JAX compiles or loads from the cache."""
    if _LISTENING:
        return
    import jax

    def on_event(ev, duration, **kw):
        if ev == "/jax/core/compile/backend_compile_duration":
            _COMPILES.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _LISTENING.append(on_event)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(trace_mod.SPAN + name)


class Engine:
    """The cell's system under test, built and warmed: the set-up."""

    def __init__(self, bench: dict, workload: str, seed: int, *,
                 base=spec.HERE, require_tpu: bool = True,
                 use_cache: bool = True):
        import jax
        self.workload = spec.find_workload(bench, workload)
        self.conf = spec.load_config(self.workload["config"], base)
        self.hconf = self.conf["harness"]
        self.mix = spec.load_traffic(self.workload["traffic"], base)
        self.device = check_device(self.workload["chips"], require_tpu)
        if use_cache:
            from repro.launch import compile_cache
            compile_cache.enable()
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
        _listen_compiles()
        self.layout = self.hconf["layout"]
        self.cost = Cost(self.layout)
        self.seed = seed
        self.pseed = program_seed(seed)
        self.served, self.cfg = self._build()
        self.router = self.served.router
        self.eng = self.served.engines[0]
        self.warm_programs()
        self.decode_calls: List[DecodeCall] = []
        self._orig_decode = self.eng.executor.decode
        self.eng.executor.decode = self._decode

    def _build(self):
        import jax
        from repro import configs
        from repro.launch import serve
        from repro.models import lm
        h = self.hconf
        argv = ["--arch", h["arch"], "--slots", str(h["slots"]),
                "--max-len", str(h["max_len"]), "--seed", str(self.pseed)]
        if h.get("full", True):
            argv.append("--full")
        args = serve.parse_args(argv)
        cfg = configs.get_arch(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
        # the weights in one jitted program from the seed, in the dtype
        # they are served in (the key is an argument: one program for all
        # seeds)
        params = jax.jit(lambda key: lm.init_lm(key, cfg))(
            jax.random.PRNGKey(self.pseed))
        return serve.build(args, cfg=cfg, params=params), cfg

    def traffic(self, seed: int):
        """The cell's traffic, drawn from the run's seed."""
        return traffic_mod.make(self.mix, seed, self.cfg.vocab,
                                self.hconf["slots"])

    def _probe(self, rid: int, max_new: int, temperature: float):
        from repro.serving.engine import Request
        rng = np.random.default_rng(rid)
        self.router.submit(Request(
            rid=rid, prompt=rng.integers(1, self.cfg.vocab, PROBE_PROMPT,
                                         dtype=np.int32),
            max_new_tokens=max_new, temperature=temperature,
            top_p=0.9 if temperature > 0 else 1.0))

    def warm_programs(self):
        """Compile (or load from the cache) every program the window
        runs: the batched prefill scan and admit, the slot scatter, and
        the decode tick at every length the budget-aware ticks pick
        (powers of two up to ``decode_block``)."""
        rid = traffic_mod.RID_PROBE
        ks, k = [], 1
        while k <= self.eng.decode_block:
            ks.append(k)
            k <<= 1
        for k in reversed(ks):
            # a greedy and a sampled probe whose budget left after the
            # admit token is k: the next tick runs at length k
            self._probe(rid, k + 1, 0.0)
            self._probe(rid + 1, k + 1, 0.7)
            rid += 2
            self.router.run_until_done()

    def _decode(self, k):
        """The benchmark's span around the program's decode call: its
        host-clock time, and the live slots and their contexts per step."""
        ctx = {s: r.prompt_len + len(r.output)
               for s, r in self.eng.active.items()}
        t0 = time.perf_counter()
        toks, valid = self._orig_decode(k)
        t1 = time.perf_counter()
        steps = range(valid.shape[0])
        self.decode_calls.append(DecodeCall(
            t0, t1, [int(valid[j].sum()) for j in steps],
            [sum(c + j for s, c in ctx.items() if valid[j, s])
             for j in steps]))
        return toks, valid

    @property
    def memory_peak_bytes(self) -> int:
        """The allocator's peak of buffers in use plus its peak reserved
        for the programs' temporaries, which the first leaves out (an
        upper bound: the two peaks need not fall together)."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        return (int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))

    def close(self):
        """Free the program's state (before the reference runs)."""
        self.eng.executor.decode = self._orig_decode
        del self.served, self.router, self.eng, self._orig_decode
        gc.collect()

    # ---------------------------------------------------------- window
    def drive(self, gen, seconds: float, trace: bool = False,
              on_open=None) -> Run:
        """Warm traffic, the window of ``seconds``, then the drain: no new
        arrivals, until every request due in the window has its first
        token (at most ``DRAIN_S`` past the close)."""
        import jax
        from repro.serving.engine import Request
        router, eng = self.router, self.eng
        records: List[Record] = []
        live: Dict[int, Record] = {}
        steps, lateness = [], []
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace \
            else None
        tr = {"on": False, "done": not trace}
        # the traced span ends with the window, so that the profiler's
        # slow stop falls in the drain and delays no arrival
        t_trace = max(0.0, seconds - TRACE_S)
        zero = time.perf_counter() - gen.start

        def clock():
            return time.perf_counter() - zero

        def submit_due(now):
            for t_s, item in gen.due(now):
                req = Request(rid=item.rid, prompt=item.prompt,
                              max_new_tokens=item.max_new,
                              temperature=item.temperature,
                              top_k=item.top_k, top_p=item.top_p)
                sub = clock()
                router.submit(req)
                rec = Record(item, t_s, sub, req)
                records.append(rec)
                live[item.rid] = rec
                if not item.warm:
                    lateness.append(sub - t_s)

        def stamp(now):
            for rid in list(live):
                rec = live[rid]
                req = rec.req
                n = len(req.output)
                if n > len(rec.stamps):
                    rec.stamps.extend([now] * (n - len(rec.stamps)))
                if req.done:
                    del live[rid]
                    gen.finished(rec.item, now)

        def tracing(now):
            if tr["done"]:
                return
            if not tr["on"] and now >= t_trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tr["ann"] = _span("window")
                tr["ann"].__enter__()
                tr.update(on=True, t0=clock())


        opened = False
        while True:
            now = clock()
            if not opened and now >= 0.0:
                opened = True
                if on_open is not None:
                    on_open()
                router.reset_metrics()
                n_comp = len(_COMPILES)
                i_calls, i_steps = len(self.decode_calls), len(steps)
            if now >= seconds:
                submit_due(seconds)     # arrivals due at the close count
                break
            if opened:
                tracing(now)
            submit_due(now)
            if router.pending:
                with _span("router_step"):
                    a = clock()
                    router.step()
                    b = clock()
                with _span("client"):
                    stamp(b)
                steps.append((a, b, len(eng.active)))
            else:
                nxt = gen.next_time()
                wait = min(seconds if nxt is None else nxt, seconds) - now
                if wait > 0:
                    with _span("client_idle"):
                        time.sleep(min(wait, 0.002))
        if tr["on"]:
            tr["t1"] = clock()
            tr["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        decode_s = router.metrics()["decode_s"]
        compiles = len(_COMPILES) - n_comp

        gen.stop()
        deadline = seconds + DRAIN_S
        while clock() < deadline and any(
                not r.stamps for r in records if r.in_window):
            router.step()
            stamp(clock())
        drain_end = clock()

        trace_red = span = None
        if trace:
            path = trace_mod.find(trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            trace_red = trace_mod.read(path)
            span = (tr["t0"], tr["t1"])
            shutil.rmtree(trace_dir, ignore_errors=True)
        # steps and decode calls that began inside the window (the
        # program's decode_s counts the last one whole)
        calls = [DecodeCall(c.start - zero, c.end - zero, c.live, c.ctx)
                 for c in self.decode_calls[i_calls:]]
        return Run(seconds=seconds, slots=self.hconf["slots"], setup_s=0.0,
                   records=records,
                   steps=[s for s in steps[i_steps:] if s[0] < seconds],
                   decode_calls=[c for c in calls if c.start < seconds],
                   decode_s=decode_s, drain_end=drain_end, cost=self.cost,
                   peak=PEAKS.get(self.device["kind"]), trace=trace_red,
                   trace_span=span, lateness=lateness, compiles=compiles)


def check(eng: Engine, run: Run, control: bool = False):
    """The numbers that decide ``correct``, each beside its limit, read
    against the plain reference (after ``eng.close()``).  With
    ``control`` the float8 control's tokens take the served tokens' place
    in them.  Returns (checks, readings, lines)."""
    hconf, mix = eng.hconf, eng.mix
    finished = [(r.item, list(r.req.output)) for r in run.records
                if r.req.done]
    rng = np.random.default_rng(np.random.SeedSequence([eng.seed, 3]))
    chk = hconf.get("check", {})
    rows = int(chk.get("requests", 8))
    length = int(chk.get("length") or -(-(mix["prompt"]["max"]
                                          + mix["output"]["max"]) // 128)
                 * 128)
    sample = correct_mod.choose(finished, rows, rng)
    mismatch = sum(1 for it, out in finished if len(out) != it.max_new)
    lines, readings = [], {}
    if sample:
        t0 = time.perf_counter()
        readings = correct_mod.gaps(
            eng.layout, eng.pseed, sample, rows, length,
            far_gap=float(chk.get("far_gap", math.inf)), control=control)
        readings["reference_s"] = time.perf_counter() - t0
        for who in ("program", "control"):
            if who in readings:
                r = readings[who]
                lines.append(
                    f"reference, {who}: {len(sample)} requests, "
                    f"{r['tokens']} served tokens compared in "
                    f"{readings['reference_s']:.1f} s; widest gap per "
                    f"request {[round(g, 4) for g in r['per_request']]}, "
                    f"mean gap {r['mean_gap']:.5f}, {r['far_tokens']} "
                    f"tokens above {chk.get('far_gap')}, "
                    f"{r['disagree']:.4f} of tokens off the reference's "
                    f"argmax")
    else:
        lines.append("reference: no finished greedy request to compare")
    readings["served_len_mismatch"] = mismatch
    checks = judge(hconf["limits"], readings,
                   "control" if control else "program")
    return checks, readings, lines


def judge(limits: dict, readings: dict, who: str) -> dict:
    """The configuration's numbers, each beside its limit, as ``who``
    ("program" or "control") reads them."""
    r = readings.get(who, {})
    values = {"max_logit_gap": r.get("gap", math.inf),
              "mean_logit_gap": r.get("mean_gap", math.inf),
              "far_tokens": r.get("far_tokens", math.inf),
              "served_len_mismatch": readings["served_len_mismatch"]}
    return {k: {"value": values[k], "limit": lim}
            for k, lim in limits.items()}


def passes(checks: dict) -> bool:
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def report(eng: Engine, run: Run, metrics: list, readers: dict,
           checks: dict, memory_peak: int):
    """The result line and the lines for standard error."""
    out = {}
    for m in metrics:
        v = readers[m["name"]].read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(run.window)
    failed = sum(1 for r in run.window if not r.stamps)
    late = percentile(run.lateness, 95) * 1e3 if run.lateness else 0.0
    lines = [
        f"generator: {attempted} requests due in the window, "
        f"{len(run.records) - attempted} warm; lateness p95 {late:.3f} ms;"
        f" {sum(len(r.stamps) for r in run.records)} tokens stamped; "
        f"{len(run.steps)} steps and {len(run.decode_calls)} decode calls "
        f"in the window; {run.compiles} programs compiled or loaded in the "
        f"window; drain ended {run.drain_end - run.seconds:.2f} s after "
        f"the close"]
    ok = failed == 0 and passes(checks)
    dev = dict(eng.device, memory_peak_bytes=memory_peak)
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": out, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {k: {"value": _num(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    return result, lines


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, base=spec.HERE,
             require_tpu: bool = True, use_cache: bool = True,
             control: bool = False):
    """One run.  Returns (result dict for the last line, lines for
    standard error, {"readings": the correctness readings, "run": Run})."""
    metrics = spec.cell_metrics(bench, workload, trace)
    readers = {m["name"]: spec.load_metric(m["name"], base)
               for m in metrics}
    eng = Engine(bench, workload, seed, base=base, require_tpu=require_tpu,
                 use_cache=use_cache)
    gen = eng.traffic(seed)
    opened = {}
    run = eng.drive(gen, seconds, trace,
                    on_open=lambda: opened.setdefault(
                        "t", time.perf_counter()))
    run.setup_s = opened["t"] - t_start
    peak = eng.memory_peak_bytes
    eng.close()
    checks, readings, check_lines = check(eng, run, control)
    result, lines = report(eng, run, metrics, readers, checks, peak)
    lines += check_lines
    lines += [f"check {k}: {c['value']} (limit {c['limit']})"
              for k, c in checks.items()]
    return result, lines, {"readings": readings, "run": run}


def _num(x):
    return x if math.isfinite(x) else 1e308
