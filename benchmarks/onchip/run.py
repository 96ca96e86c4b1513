#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process holds.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` there names the cell's
configuration and traffic mix.  With ``--trace 0`` the last line of
standard output reports the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, the device's busy time and a
breakdown of the traced sub-window.  The numbers that decide ``correct``
are printed last on standard error and last in the result line, each
beside its limit.  ``--control 1`` puts the float8 control in the
program's place in those numbers: its run must come out not correct.

Exits non-zero, printing no result, when JAX's default backend is not a
TPU, when it has fewer chips than the cell asks for, or when the chip has
no entry in ``harness/peaks.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import repro  # noqa: E402,F401  (the system under test must be here)
from harness import cell, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    try:
        spec.find_workload(bench, args.workload)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    result, lines, _ = cell.run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, control=bool(args.control))
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
