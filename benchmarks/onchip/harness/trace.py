"""From a profiler trace to device busy time, idle gaps and top ops.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the chip ran.  The harness's own host spans
(``TraceAnnotation``) sit on the ``/host:CPU`` plane, on the same clock.
The traced window is the host span named ``WINDOW``; idle gaps are
labelled by the innermost harness span (names starting ``SPAN``) that
covers them.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN = "bench:"
WINDOW = SPAN + "window"
OPS_LINE = "XLA Ops"
TOP = 10
SHORT_S = 20e-6          # idle gaps shorter than this go unlabelled

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The innermost host span that covers time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(SPAN):] if best else "outside harness spans"


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..), ..`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(ops: List[Tuple[str, float, float]]):
    """The operations that hold no other operation of the line (a while
    loop's event spans the ops of its body): their times add up."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    leaf = [True] * len(ops)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            leaf[stack[-1]] = False
        stack.append(i)
    return [o for o, keep in zip(ops, leaf) if keep]


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[float, float, str]]) -> dict:
    """device_ops: per device plane, (op name, start_ns, end_ns) events;
    host_spans: (start_ns, end_ns, name).  Returns seconds."""
    win = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not win:
        raise ValueError(f"no host span named {WINDOW!r} in the trace")
    lo, hi = win[0]
    window = (hi - lo) * 1e-9
    others = [sp for sp in host_spans if sp[2] != WINDOW]
    busy_total, per_op = 0.0, defaultdict(float)
    all_gaps = []
    for plane, ops in device_ops.items():
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in merged) * 1e-9
        for name, s, e in leaves(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[op_name(name)] += (e - s) * 1e-9
        for s, e in gaps(merged, lo, hi):
            sec = (e - s) * 1e-9
            all_gaps.append((label_at(others, (s + e) / 2)
                             if sec >= SHORT_S else "short gaps", sec))
    n = max(1, len(device_ops))
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    all_gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window,
        "busy_s": busy_total / n,
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in all_gaps[:TOP]],
        "devices": len(device_ops),
    }


def read(path: str) -> dict:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_profile(data) -> dict:
    device_ops, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.end_ns, ev.name)
                            for ev in line.events
                            if ev.name.startswith(SPAN))
    return reduce_events(device_ops, host)


def find(directory: str) -> Optional[str]:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None
