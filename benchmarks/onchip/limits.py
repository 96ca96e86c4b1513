#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` on many seeds, for the program
and for its float8 control, in one process.

    python3 benchmarks/onchip/limits.py --workload mamba2-batch \
        --seconds 15 --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control 1,2,3

Each seed is a whole run of the cell at its own load (set-up, a short
window, the drain), then the reference over the run's sample of served
tokens; a seed in ``--control`` also puts the tokens that the reference
computed with float8 weight matmuls would put first in the program's
place, through the same comparison.  One JSON line per seed, with each
number beside the configuration's limit and whether it came out
correct.  The limit is set between the largest reading of the program
and the smallest of the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

from harness import cell, spec  # noqa: E402


def readings(bench, workload, seconds, seeds, control=(), **engine_kw):
    """One JSON-able row per seed."""
    for seed in seeds:
        t0 = time.perf_counter()
        eng = cell.Engine(bench, workload, seed, **engine_kw)
        run = eng.drive(eng.traffic(seed), seconds)
        peak = eng.memory_peak_bytes
        eng.close()
        row = {"seed": seed, "attempted": len(run.window),
               "failed": sum(1 for x in run.window if not x.stamps),
               "memory_peak_bytes": peak}
        _, r, _ = cell.check(eng, run, control=seed in control)
        for who in ("program", "control"):
            if who not in r:
                continue
            checks = cell.judge(eng.hconf["limits"], r, who)
            row[who] = {k: r[who][k] for k in (
                "gap", "mean_gap", "far_tokens", "disagree", "tokens",
                "per_request")}
            row[who].update(checks=checks, correct=cell.passes(checks)
                            and row["failed"] == 0)
        row["reference_s"] = r.get("reference_s")
        row["wall_s"] = time.perf_counter() - t0
        yield row
        del eng, run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control.split(",") if s}
    for row in readings(spec.load_benchmark(), args.workload, args.seconds,
                        seeds, control):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
