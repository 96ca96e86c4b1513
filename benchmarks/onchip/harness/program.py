"""The serving program's own record of its ticks, joined to the device trace.

The program names each of its XLA programs ``jit_serve_<family>`` (the
device plane's ``XLA Modules`` line has one event per run of one), marks
its host phases with ``serve:`` spans that carry the tick id, and keeps a
tick log and a prefill log (``Scheduler.metrics()``: ``tick_log``,
``prefill_log``).  This module joins each decode and prefill program run
in the traced span to the tick that dispatched it, and reads from the
join:

- the decode programs' share of their roofline over their own device
  time (``decode_device_roofline``);
- the prefill and admit programs' share of theirs
  (``prefill_device_roofline``);
- the device time of the decode program split by the model's named
  scopes (``scope_split``);
- the idle gaps of the traced span, labelled by the innermost harness
  (``bench:``) or program (``serve:``) span (``idle_gaps``).

Every reader returns ``None`` where the trace has no device or the
program left no such record (a program that predates its spans).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from harness import trace as trace_mod

MODULES_LINE = "XLA Modules"
SERVE = "serve:"
PREFILL = ("prefill_scan", "prefill_chunk", "admit")
SCOPES = re.compile(r"^(mixer_\w+|ffn|head_sample)$")
OUTSIDE = "outside any scope"

Span = Tuple[float, float, str, dict]        # start_ns, end_ns, name, stats
ProgramRun = Tuple[str, float, float]        # family, start_ns, end_ns


def family(module: str) -> Optional[str]:
    """``jit_serve_decode(123)`` -> ``decode``; None for other programs."""
    name = module.split("(", 1)[0]
    return name[len("jit_serve_"):] if name.startswith("jit_serve_") \
        else None


def read_profile(data) -> dict:
    """From a ``jax.profiler.ProfileData``: per device plane its program
    runs (``modules``: family, start, end) and operations (``ops``: name,
    start, end), and the harness's and the program's host spans."""
    modules, ops, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (family(ev.name), ev.start_ns, ev.end_ns)
                        for ev in line.events)
                elif line.name == trace_mod.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                            for ev in line.events
                            if ev.name.startswith((trace_mod.SPAN, SERVE)))
    return {"modules": modules, "ops": ops, "spans": sorted(host)}


def window(spans: List[Span]) -> Optional[Tuple[float, float]]:
    for s, e, name, _ in spans:
        if name == trace_mod.WINDOW:
            return s, e
    return None


def dispatches(spans: List[Span]) -> Dict[str, List[Tuple[float, int,
                                                            float]]]:
    """Per program family, (start, tick, end) of the host spans that
    dispatched its runs, in time order."""
    out: Dict[str, list] = defaultdict(list)
    for s, e, name, st in spans:
        if name == SERVE + "decode.dispatch":
            out["decode"].append((s, st["tick"], e))
        elif name == SERVE + "prefill.dispatch":
            out[st["program"]].append((s, st["tick"], e))
    return out


def join(runs: List[ProgramRun], spans: List[Span]) -> List[Tuple[int, str,
                                                              float, float]]:
    """(tick, family, start, end) of each decode or prefill program run
    that a traced dispatch span launched.

    A device runs one plane's programs in the order the host launched
    them, and the scheduler reads each decode tick's result before it
    dispatches the next.  So decode run k lies between the starts of
    dispatch spans k and k + 1: a decode run joins the latest decode
    dispatch span that starts before its middle (which leaves a few
    milliseconds between the host's and the device's clocks harmless).
    The prefill runs between two decode runs belong to the ticks after
    the first up to the second's (the second's alone where the first was
    launched before the trace), and pair in order with those ticks'
    dispatch spans of their family.  A run the trace cannot place, or a
    group whose count differs from its spans', is left out."""
    sent = dispatches(spans)
    dec = sent.get("decode", [])
    starts = [s for s, _, _ in dec]
    out, taken = [], set()
    anchors = []                    # (start, tick or None) of decode runs
    for f, s, e in sorted(r for r in runs if r[0] == "decode"):
        k = bisect.bisect_right(starts, (s + e) / 2) - 1
        tick = dec[k][1] if k >= 0 and k not in taken else None
        if tick is not None:
            taken.add(k)
            out.append((tick, "decode", s, e))
        anchors.append((s, tick))
    at = [s for s, _ in anchors]
    for fam in PREFILL:
        groups: Dict[int, list] = defaultdict(list)
        for f, s, e in sorted(r for r in runs if r[0] == fam):
            groups[bisect.bisect_right(at, s)].append((s, e))
        for j, group in groups.items():
            if j == 0 or j == len(anchors) or anchors[j][1] is None:
                continue
            lo, hi = anchors[j - 1][1], anchors[j][1]
            lo = hi - 1 if lo is None else lo
            ticks = [t for _, t, _ in sent.get(fam, []) if lo < t <= hi]
            if len(ticks) != len(group):
                continue
            out.extend((t, fam, s, e) for t, (s, e) in zip(ticks, group))
    return out


def _inside(run, lo, hi) -> bool:
    return lo <= run[2] and run[3] <= hi


def decode_device_roofline(joined, tick_log, lo, hi, cost,
                           peak) -> Optional[float]:
    """Least time of the decode ticks' live work over the device time of
    their ``jit_serve_decode`` runs, for the ticks whose run lies wholly
    in [lo, hi).  Per step the least time is the larger of its bytes over
    HBM bandwidth and its model FLOPs over peak, for the live slots and
    their contexts the tick log gives."""
    ticks = {t["tick"]: t for t in tick_log}
    least = busy = 0.0
    for run in joined:
        if run[1] != "decode" or run[0] not in ticks \
                or not _inside(run, lo, hi):
            continue
        t = ticks[run[0]]
        for live, ctx in zip(t["live"], t["ctx"]):
            if live:
                least += max(
                    cost.decode_step_bytes(live, ctx)
                    / peak["hbm_bytes_per_s"],
                    cost.decode_step_flops(live, ctx) / peak["bf16_flops"])
        busy += (run[3] - run[2]) * 1e-9
    return 100.0 * least / busy if busy > 0 else None


def prefill_bound(entries, cost, peak) -> float:
    """Least time of one tick's prefill dispatches: the larger of (every
    weight once, the state of each prompt admitted, the KV of each valid
    token written) over HBM bandwidth and the model FLOPs of the valid
    tokens over peak."""
    rows = [r for e in entries for r in e["rows"]]
    admitted = sum(len(e["rows"]) for e in entries
                   if e["program"] == "admit")
    nbytes = (cost.weight_bytes + admitted * cost.state_bytes
              + sum(v for _, _, v in rows) * cost.kv_bytes_per_position)
    flops = sum(cost.prompt_flops(a + v) - cost.prompt_flops(a)
                for _, a, v in rows)
    return max(nbytes / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])


def prefill_device_roofline(joined, prefill_log, lo, hi, cost,
                            peak) -> Optional[float]:
    """Least time of each tick's prefill over the device time of that
    tick's ``jit_serve_prefill_*`` and ``jit_serve_admit`` runs, for the
    ticks all of whose prefill runs lie in [lo, hi).  The slot scatter is
    not counted."""
    logged: Dict[int, list] = defaultdict(list)
    for e in prefill_log:
        logged[e["tick"]].append(e)
    ran: Dict[int, list] = defaultdict(list)
    for run in joined:
        if run[1] in PREFILL:
            ran[run[0]].append(run)
    least = busy = 0.0
    for tick, entries in logged.items():
        runs = ran.get(tick, [])
        if len(runs) != len(entries) or not all(_inside(r, lo, hi)
                                                for r in runs):
            continue
        least += prefill_bound(entries, cost, peak)
        busy += sum(r[3] - r[2] for r in runs) * 1e-9
    return 100.0 * least / busy if busy > 0 else None


def scope_of(op_name: str) -> str:
    """The model scope (``mixer_<kind>``, ``ffn``, ``head_sample``) in an
    HLO ``op_name`` path, or ``OUTSIDE``."""
    for part in op_name.split("/"):
        if SCOPES.match(part):
            return part
    return OUTSIDE


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> model scope, from a compiled module's text
    (each instruction's ``metadata={op_name=...}``)."""
    out = {}
    for m in re.finditer(r"%([\w.\-]+) = [^\n]*?op_name=\"([^\"]*)\"",
                         hlo_text):
        out[m.group(1)] = scope_of(m.group(2))
    return out


def scope_split(ops, runs: List[ProgramRun], fam: str, scopes: Dict[str, str],
                lo: float, hi: float, per_op: bool = False) -> dict:
    """Seconds of device time inside ``fam``'s runs in [lo, hi), per
    model scope (with ``per_op``, per scope and operation): leaf
    operations only (their times add up), named by ``scopes`` (an
    operation it does not name falls outside any scope)."""
    spans = trace_mod.union([(max(s, lo), min(e, hi))
                             for f, s, e in runs if f == fam
                             and e > lo and s < hi])
    out: dict = defaultdict(float)
    for name, s, e in trace_mod.leaves(ops):
        op = trace_mod.op_name(name)
        key = scopes.get(op, OUTSIDE)
        for a, b in trace_mod.clip(spans, s, e):
            out[(key, op) if per_op else key] += (b - a) * 1e-9
    return dict(out)


def label(spans: List[Span], t: float) -> str:
    """The innermost harness or program span over time ``t``: a harness
    span by its name after ``bench:``, a program span by its whole
    name."""
    best = None
    for s, e, name, _ in spans:
        if name != trace_mod.WINDOW and s <= t < e and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is None:
        return "outside harness spans"
    name = best[2]
    return name[len(trace_mod.SPAN):] if name.startswith(trace_mod.SPAN) \
        else name


def idle_gaps(ops: Dict[str, list], spans: List[Span], lo: float,
              hi: float, top: int = trace_mod.TOP) -> List[list]:
    """The longest idle gaps of [lo, hi) on the device planes as [label,
    seconds, middle in ns], each labelled by the span over its middle (as
    ``trace.reduce_events`` does, with the program's spans beside the
    harness's)."""
    out = []
    for plane_ops in ops.values():
        busy = trace_mod.union(trace_mod.clip(
            [(s, e) for _, s, e in plane_ops], lo, hi))
        for s, e in trace_mod.gaps(busy, lo, hi):
            sec, mid = (e - s) * 1e-9, (s + e) / 2
            out.append([label(spans, mid) if sec >= trace_mod.SHORT_S
                        else "short gaps", sec, mid])
    return sorted(out, key=lambda g: -g[1])[:top]


def sched_self_ms(metrics: dict) -> Optional[float]:
    """The scheduler's host time per step, less its waits for the device
    (the program's counter)."""
    steps = metrics.get("steps")
    return 1e3 * metrics["sched_self_s"] / steps if steps else None
