#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip, builds the cell's SimCluster, loads it,
warms up, drives the cell's traffic through ClusterClient for
--seconds, verifies what the window received against the plain
reference, and prints one JSON line last on stdout. There is no CPU
mode: without a TPU (or with fewer chips than the cell asks for, or
without the native library) it exits non-zero and prints no result.
rehearse.py is the CPU rehearsal.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def load_cell(name: str):
    """The cell's entry, its config and its traffic mix, each from the
    file BENCHMARK.json names."""
    from benchmarks.generator import load_json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    return bench, cell, config, load_json("traffic", cell["traffic"])


def result_line(bench, cell, res, trace: bool, device: dict) -> dict:
    """The contract's last line from run_cell's result."""
    group = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in bench[group]
              if cell["name"] in m.get("workloads", [cell["name"]])}
    metrics = {n: {"value": v, "unit": u}
               for n, (v, u) in res[group].items() if n in listed}
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = res["trace"]["busy_s"]
        device["window_s"] = res["trace"]["window_s"]
        line["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                             "idle_gaps": res["trace"]["idle_gaps"]}
    line["info"] = res["info"]
    line["checks"] = {n: {"value": v, "limit": lim}
                      for n, (v, lim) in res["checks"].items()}
    return line


def print_checks(res) -> None:
    for n, (v, lim) in res["checks"].items():
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench, cell, config, traffic = load_cell(args.workload)
    from pegasus_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    from benchmarks.trace_reduce import peaks_for

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        raise SystemExit(
            f"benchmarks/run.py needs {cell['chips']} TPU chip(s); jax found "
            f"{len(devs)} x {devs[0].platform!r}. There is no CPU mode "
            f"(benchmarks/rehearse.py is the CPU rehearsal).")
    peaks_for(devs[0].device_kind)   # an unknown chip is an error
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from benchmarks.harness import run_cell

    res = run_cell(cell["name"], config, traffic, args.seed, args.seconds,
                   bool(args.trace), _T_START)
    print_checks(res)
    print(json.dumps(result_line(bench, cell, res, bool(args.trace), device)),
          flush=True)


if __name__ == "__main__":
    main()
