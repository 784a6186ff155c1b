#!/usr/bin/env python3
"""The control of `correct`: one run of a cell with a stated guarantee
broken underneath (faults.py), on the chip at the cell's own size.

    python3 benchmarks/control.py --workload <cell> --seed <n> --seconds <s> --fault <name>

It has to come out not correct; the exit code is 0 when it did. The
benchmark's own runs never plant a fault: run.py has no such option.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks.faults import FAULTS

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args = ap.parse_args()

    from benchmarks.run import load_cell

    _bench, cell, config, traffic = load_cell(args.workload)
    from pegasus_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("benchmarks/control.py needs a TPU")
    from benchmarks.harness import run_cell

    res = run_cell(cell["name"], config, traffic, args.seed, args.seconds,
                   False, _T_START, fault=args.fault)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "fault": args.fault, "correct": res["correct"],
                      "attempted": res["attempted"],
                      "checks": {n: {"value": v, "limit": lim}
                                 for n, (v, lim) in res["checks"].items()}}),
          flush=True)
    return 0 if not res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
