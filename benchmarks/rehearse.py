#!/usr/bin/env python3
"""CPU rehearsal of every cell at a tiny size, before chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py [--fault <name>]

Calls the same functions as run.py for every cell of BENCHMARK.json,
with each config cut to a few thousand rows and a 2-second window.
Prints what it counted on stderr, no contract line and no device
metric: a time or a rate from a CPU means nothing here.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {"records": 400}
SECONDS = 2.0


def rehearse_cell(name: str, seed: int, trace: bool, fault: str = None,
                  seconds: float = SECONDS):
    """One cell of BENCHMARK.json at the rehearsal's size: run_cell's
    result and the line run.py would print of it."""
    from benchmarks.harness import run_cell
    from benchmarks.run import load_cell, result_line

    bench, cell, config, traffic = load_cell(name)
    traffic = dict(traffic, warmup_windows=8, trace_slice_s=[0.5, 1.0])
    res = run_cell(name, dict(config, **TINY), traffic, seed, seconds,
                   trace, time.perf_counter(), fault=fault)
    return res, result_line(bench, cell, res, trace,
                            {"platform": "cpu-rehearsal"})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seed", type=int, default=2_500_000_011)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    ok = True
    for name in names:
        res, line = rehearse_cell(name, args.seed, bool(args.trace),
                                  args.fault)
        print(f"[rehearse] {name}: correct={res['correct']} "
              f"attempted={res['attempted']} failed={res['failed']} "
              f"metrics reported={sorted(line['metrics'])} "
              f"checks={line['checks']} info={res['info']}",
              file=sys.stderr, flush=True)
        ok &= res["correct"] == (args.fault is None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
