"""Readers of the traced sub-window: model FLOPs utilization and idle share.

The FLOPs are those of the tokens the client saw processed inside the
traced span: every output token stamped there (at its context length),
and the whole prompt of every request whose first token was stamped
there (its prefill ran in the ticks just before).  They come from the
configuration alone (``harness.cost``), whatever computes them, and are
divided by the device's busy time (the union of its operations) at the
chip's peak.
"""
from __future__ import annotations


def flops_in(run, lo: float, hi: float) -> float:
    total = 0.0
    for r in run.records:
        plen = len(r.item.prompt)
        for i, t in enumerate(r.stamps):
            if not lo <= t <= hi:
                continue
            if i == 0:
                total += run.cost.prompt_flops(plen)
            else:
                total += run.cost.token_flops(plen + i)
    return total


def read(run):
    if (run.trace is None or run.peak is None or not run.trace["devices"]
            or run.trace["busy_s"] <= 0):
        return None
    lo, hi = run.trace_span
    return 100.0 * flops_in(run, lo, hi) / (run.trace["busy_s"]
                                            * run.peak["bf16_flops"])


def idle(run):
    if (run.trace is None or not run.trace["devices"]
            or run.trace["window_s"] <= 0):
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
