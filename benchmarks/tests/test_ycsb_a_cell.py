"""The cell `ycsb_a.p64r3` (PR 32): its per-layer metric files load,
every counter they name exists in METRICS after a tiny traced run, each
metric is reported, and the control comes out not correct. Drives
harness.run_cell on the CPU. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_ycsb_a_cell.py -q -p no:cacheprovider
"""

import json
import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import layer_metric_specs, run_cell  # noqa: E402
from benchmarks.run import load_cell, result_line  # noqa: E402
from pegasus_tpu.utils.metrics import METRICS  # noqa: E402

CELL = "ycsb_a.p64r3"
SHARES = ("update_replication_self_share", "point_overlay_self_share",
          "point_overlay_hit_share", "update_row_cache_hit_share")
METRICS_OF_CELL = SHARES + (
    "update_group_commit_size", "update_plog_flushes_per_op",
    "update_write_p95_ms", "row_cache_invalidated_per_kread",
    "update_flushes_in_window")


def _run(trace, fault=None):
    bench, cell, config, traffic = load_cell(CELL)
    traffic = dict(traffic, warmup_windows=8, trace_slice_s=[0.5, 1.0])
    res = run_cell(CELL, dict(config, records=400), traffic, 2_400_000_011,
                   2.0, trace, time.perf_counter(), fault=fault)
    return bench, cell, res


@pytest.fixture(scope="module")
def traced():
    return _run(True)


def test_specs_load_and_match_their_benchmark_entries(traced):
    bench, _cell, _res = traced
    specs = {s["name"]: s for s in layer_metric_specs(CELL)}
    assert sorted(specs) == sorted(METRICS_OF_CELL)
    entries = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert sorted(entries) == sorted(specs)
    for name, entry in entries.items():
        for key, value in entry.items():
            assert specs[name][key] == value, (name, key)


def test_counters_exist_and_every_metric_is_reported(traced):
    bench, cell, res = traced
    assert res["correct"], res["checks"]
    have = {(ent["type"], name) for ent in METRICS.snapshot()
            for name in ent["metrics"]}
    for spec in layer_metric_specs(CELL):
        if spec["reader"] == "latency_p95":
            continue
        pairs = list(spec["numerator"])
        for group in (spec.get("denominator", {}), spec.get("per", {})):
            pairs += group.get("counters", [])
        for etype, counter in pairs:
            assert (etype, counter) in have, (spec["name"], etype, counter)
    line = result_line(bench, cell, res, True, {"platform": "cpu-test"})
    assert sorted(line["metrics"]) == sorted(METRICS_OF_CELL)
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert all(0.0 <= values[n] <= 100.0 for n in SHARES), values
    assert values["update_flushes_in_window"] == 0
    assert values["point_overlay_hit_share"] > 0
    assert 1.0 <= values["update_plog_flushes_per_op"] <= 3.0
    json.dumps(line)        # the contract's line serialises


def test_the_control_lost_write_is_not_correct():
    _bench, _cell, res = _run(False, fault="lost_write")
    assert not res["correct"]
    checks = {n: v for n, (v, _lim) in res["checks"].items()}
    assert checks["wrong_answers"] > 0 and checks["missing_readbacks"] > 0
    assert checks["failed_ops"] == 0
