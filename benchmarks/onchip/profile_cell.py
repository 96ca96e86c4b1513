#!/usr/bin/env python3
"""Trace one cell's serving program and join the trace to its own record.

    python3 benchmarks/onchip/profile_cell.py --workload <name> --seed <n> \
        [--seconds 15] [--trace-seconds 3] [--out <dir>]

from the root of a checkout, on the chip.  Builds and warms the cell's
engine as ``run.py`` does, serves its traffic through the warm-up and a
window of ``--seconds``, traces the window's last 3 s under the
harness's ``bench:window`` span, and prints one JSON line:

- ``decode_device_roofline`` and ``prefill_device_roofline``: the decode
  and prefill programs' roofline shares over their own device time
  (``harness/program.py``), beside the host-clock ``decode_roofline.tps``
  of the same window;
- ``sched_self_ms`` and ``compiles`` over the window, and the self time
  inside the traced span against the rest (the spans' cost with the
  profiler on);
- the decode program's device time by model scope (from the compiled
  text's ``op_name``s), with the part outside every scope, and each
  scope's longest operations;
- the idle gaps labelled by the harness's and the program's spans, each
  with the spans over it, outermost first, beside the harness's own
  labels; for gaps of 10 ms or more, every span that overlaps them;
- the anatomy of the gap between two decode runs with nothing between
  them: the wait for the first run's result to reach the host, the
  host's turn, and the launch of the next run (medians), and the host
  phases' durations.

``--trace-seconds`` lengthens the traced part of the window (3 s as in
``run.py`` by default), to catch rarer stalls.

``--out`` keeps, per line of the trace, a sample of its events with their
stats, and every event that carries a ``run_id`` beside the host spans.  This is a look at the program, not a benchmark run: no result
line, no correctness check.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

from harness import cell, program, spec  # noqa: E402
from harness import trace as trace_mod  # noqa: E402
from harness.peaks import PEAKS  # noqa: E402


def serve(eng, seed: int, seconds: float, trace_s: float):
    """The cell's traffic through its warm-up and ``seconds`` of window,
    the last ``trace_s`` traced.  Returns (trace dir, window start,
    metrics at the trace's start, metrics at the window's close)."""
    import jax
    from repro.serving.engine import Request
    router, gen = eng.router, eng.traffic(seed)
    zero = time.perf_counter() - gen.start
    live, opened, traced, before = {}, None, None, None
    trace_dir = tempfile.mkdtemp(prefix="profile_cell_")
    while True:
        now = time.perf_counter() - zero
        if opened is None and now >= 0.0:
            router.reset_metrics()
            opened = time.perf_counter()
        if now >= seconds:
            break
        if opened is not None and traced is None \
                and now >= seconds - trace_s:
            before = router.metrics()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = cell._span("window")
            traced.__enter__()
        for _, item in gen.due(now):
            req = Request(rid=item.rid, prompt=item.prompt,
                          max_new_tokens=item.max_new,
                          temperature=item.temperature, top_k=item.top_k,
                          top_p=item.top_p)
            router.submit(req)
            live[item.rid] = (item, req)
        if router.pending:
            with cell._span("router_step"):
                router.step()
            with cell._span("client"):
                t = time.perf_counter() - zero
                for rid in [r for r, (_, q) in live.items() if q.done]:
                    gen.finished(live.pop(rid)[0], t)
        else:
            time.sleep(0.002)
    traced.__exit__(None, None, None)
    after = router.metrics()
    jax.profiler.stop_trace()
    return trace_dir, opened, before, after


def decode_scopes(eng, k: int):
    """Instruction name -> model scope of the decode program of length
    ``k``, from its compiled text."""
    ex = eng.eng.executor
    prog = ex._decode_p[k]
    text = prog.fn.lower(ex.params, ex.tokens, ex.caches,
                         ex.sampler).compile().as_text()
    return program.op_scopes(text)


def samples(data, n: int = 4, names: int = 40) -> dict:
    """Per line of the trace, its first ``n`` events and the first event
    of each of up to ``names`` other names, with their stats."""
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            evs, seen = [], set()
            for ev in line.events:
                if len(evs) < n or (ev.name not in seen
                                    and len(seen) < names):
                    evs.append(ev)
                seen.add(ev.name)
            out[f"{plane.name} | {line.name}"] = [
                {"name": ev.name[:200], "dur_ns": ev.duration_ns,
                 "stats": {k: str(v)[:200] for k, v in ev.stats}}
                for ev in evs]
    return out


def correlated(data) -> list:
    """Every event that carries a ``run_id`` stat, on any plane, as
    (plane | line, name, start_ns, end_ns, run_id), and every host span
    of the harness or the program (run_id None)."""
    out = []
    for plane in data.planes:
        for line in plane.lines:
            if line.name.endswith(trace_mod.OPS_LINE):
                continue            # operations carry no run_id
            for ev in line.events:
                st = dict(ev.stats)
                if "run_id" in st or ev.name.startswith(
                        (trace_mod.SPAN, program.SERVE)):
                    out.append([f"{plane.name} | {line.name}",
                                ev.name[:80], ev.start_ns, ev.end_ns,
                                st.get("run_id", st.get("tick"))])
    return out


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


def anatomy(runs, joined, spans) -> dict:
    """Medians (ms) over pairs of consecutive decode runs with no other
    program run between them: the first run's end to the end of its
    ``serve:decode.sync`` (the result reaching the host), from there to
    the next ``serve:decode.dispatch`` (the host's turn), and from that
    span's start to the next run's start (the launch)."""
    sync_end = {st["tick"]: e for s, e, n, st in spans
                if n == "serve:decode.sync"}
    sent = {st["tick"]: s for s, e, n, st in spans
            if n == "serve:decode.dispatch"}
    others = [s for f, s, e in runs if f != "decode"]
    dec = sorted((r for r in joined if r[1] == "decode"),
                 key=lambda r: r[2])
    rows = [((sync_end[t0] - e0) * 1e-6, (sent[t1] - sync_end[t0]) * 1e-6,
             (s1 - sent[t1]) * 1e-6, (s1 - e0) * 1e-6)
            for (t0, _, s0, e0), (t1, _, s1, e1) in zip(dec, dec[1:])
            if t0 in sync_end and t1 in sent
            and not any(e0 <= s < s1 for s in others)]
    keys = ("result_ms", "host_ms", "launch_ms", "idle_ms")
    return dict({k: _median([r[i] for r in rows])
                 for i, k in enumerate(keys)}, pairs=len(rows))


def phases(spans, lo, hi) -> dict:
    """Per host span name: count, median and total ms inside [lo, hi)."""
    by = {}
    for s, e, n, _ in spans:
        if n != trace_mod.WINDOW and lo <= s and e <= hi:
            by.setdefault(n, []).append((e - s) * 1e-6)
    return {n: {"n": len(d), "median_ms": _median(d), "total_ms": sum(d)}
            for n, d in sorted(by.items())}


def overlapping(spans, a, b) -> list:
    """Every span that overlaps [a, b), with its tick and the ms of the
    overlap."""
    return [[n, st.get("tick"), (min(e, b) - max(s, a)) * 1e-6]
            for s, e, n, st in spans
            if n != trace_mod.WINDOW and s < b and e > a]


def measure(eng, seed: int, seconds: float,
            trace_s: float = cell.TRACE_S) -> dict:
    """Serve, trace and read one window (see the module's docstring).
    Readers that need a device plane or the chip's peaks read None off
    the chip."""
    from jax.profiler import ProfileData
    trace_dir, opened, before, after = serve(eng, seed, seconds, trace_s)
    data = ProfileData.from_file(trace_mod.find(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    prof = program.read_profile(data)
    lo, hi = program.window(prof["spans"])
    plane = min(prof["ops"], default=None)
    runs = prof["modules"].get(plane, [])
    joined = program.join(runs, prof["spans"])
    peak, cost = PEAKS.get(eng.device["kind"]), eng.cost
    me = after["per_engine"][0]
    ks = Counter(t["k"] for t in me["tick_log"])
    k = ks.most_common(1)[0][0]
    tick_k = {t["tick"]: t["k"] for t in me["tick_log"]}
    split = program.scope_split(
        prof["ops"].get(plane, []),
        [("decode", s, e) for t, f, s, e in joined
         if f == "decode" and tick_k.get(t) == k],
        "decode", decode_scopes(eng, k), lo, hi, per_op=True)
    scopes, top = Counter(), {}
    for (scope, op), sec in sorted(split.items(), key=lambda kv: -kv[1]):
        scopes[scope] += sec
        if len(top.setdefault(scope, [])) < 4:
            top[scope].append([op, sec])

    host = SimpleNamespace(     # what decode_roofline.tps reads
        peak=peak, cost=cost, decode_s=after["decode_s"],
        decode_calls=[c for c in eng.decode_calls if c.start >= opened])
    red = trace_mod.reduce_profile(data)
    traced_steps = after["steps"] - before["steps"]
    roofline = peak is not None
    return {
        "device": eng.device, "traced_s": (hi - lo) * 1e-9,
        "busy_s": red["busy_s"],
        "decode_roofline.tps": spec.load_metric(
            "decode_roofline.tps").read(host),
        "decode_device_roofline": program.decode_device_roofline(
            joined, me["tick_log"], lo, hi, cost, peak)
        if roofline else None,
        "prefill_device_roofline": program.prefill_device_roofline(
            joined, me["prefill_log"], lo, hi, cost, peak)
        if roofline else None,
        "sched_self_ms": program.sched_self_ms(after),
        "sched_self_ms.untraced": program.sched_self_ms(before),
        "sched_self_ms.traced": (
            1e3 * (after["sched_self_s"] - before["sched_self_s"])
            / traced_steps if traced_steps else None),
        "compiles": after["compiles"],
        "ticks": after["ticks"], "steps": after["steps"],
        "tick_lengths": dict(ks),
        "joined": dict(Counter(f for _, f, _, _ in joined)),
        "module_runs": dict(Counter(f or "other" for f, _, _ in runs)),
        "decode_scope_s": dict(scopes),
        "decode_scope_top_ops": top,
        "idle_gaps": [
            g[:2] + [[f"{n}#{st.get('tick')}" for s, e, n, st
                      in prof["spans"]
                      if n != trace_mod.WINDOW and s <= g[2] < e]]
            + ([overlapping(prof["spans"], g[2] - g[1] * 5e8,
                            g[2] + g[1] * 5e8)] if g[1] >= 0.01 else [])
            for g in program.idle_gaps(prof["ops"], prof["spans"], lo,
                                       hi)],
        "gap_anatomy": anatomy(runs, joined, prof["spans"]),
        "host_phases": phases(prof["spans"], lo, hi),
        "idle_gaps.harness": red["idle_gaps"],
        "device_ops": red["device_ops"],
        "samples": samples(data),
        "events": correlated(data),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--trace-seconds", type=float, default=cell.TRACE_S)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    eng = cell.Engine(spec.load_benchmark(), args.workload, args.seed)
    result = measure(eng, args.seed, args.seconds, args.trace_seconds)
    result.update(workload=args.workload, seed=args.seed,
                  window_s=args.seconds)
    kept = {k: result.pop(k) for k in ("samples", "events")}
    print(json.dumps(result, default=str), flush=True)
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        for k, v in kept.items():
            (out / f"{args.workload}.{k}.json").write_text(
                json.dumps(v, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
