"""The cell `ycsb_f.p64r3`: at the rehearsal's size (benchmarks/rehearse.py)
it ends correct with every check 0, its per-layer metric files load and
match their BENCHMARK.json entries, every counter they name exists, a
traced run reports all four, and the control `lost_write` comes out not
correct. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_ycsb_f_cell.py -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import layer_metric_specs  # noqa: E402
from benchmarks.rehearse import rehearse_cell  # noqa: E402
from pegasus_tpu.utils.metrics import METRICS  # noqa: E402

CELL = "ycsb_f.p64r3"
RMW_METRICS = ("rmw_atomic_self_share", "rmw_check_failed_per_kop",
               "rmw_queued_per_kop", "rmw_busy_rejects_per_kop")


@pytest.fixture(scope="module")
def rmw_traced():
    return rehearse_cell(CELL, 4_000_000_029, True)


def test_rmw_cell_is_correct_with_every_check_0(rmw_traced):
    res, line = rmw_traced
    assert res["correct"] and line["correct"]
    assert {n: c["value"] for n, c in line["checks"].items()} == {
        "wrong_answers": 0, "failed_ops": 0, "missing_readbacks": 0,
        "replica_decree_spread": 0}
    assert res["info"]["compiled_in_window"] == 0
    assert res["info"]["ops_by_kind"]["rmw"] > 0
    json.dumps(line)        # the contract's line serialises


def test_rmw_specs_match_their_benchmark_entries_and_counters(rmw_traced):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    specs = {s["name"]: s for s in layer_metric_specs(CELL)}
    assert sorted(specs) == sorted(RMW_METRICS)
    entries = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert sorted(entries) == sorted(specs)
    for name, entry in entries.items():
        for key, value in entry.items():
            assert specs[name][key] == value, (name, key)
    have = {(ent["type"], name) for ent in METRICS.snapshot()
            for name in ent["metrics"]}
    for spec in specs.values():
        for etype, counter in (spec["numerator"]
                               + spec["denominator"].get("counters", [])):
            assert (etype, counter) in have, (spec["name"], etype, counter)


def test_rmw_traced_run_reports_the_four_metrics(rmw_traced):
    _res, line = rmw_traced
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert sorted(values) == sorted(RMW_METRICS)
    assert 0.0 < values["rmw_atomic_self_share"] < 100.0
    assert values["rmw_check_failed_per_kop"] > 0
    assert values["rmw_queued_per_kop"] > 0
    assert values["rmw_busy_rejects_per_kop"] == 0


def test_rmw_cell_control_lost_write_is_not_correct():
    res, line = rehearse_cell(CELL, 4_000_000_031, False, fault="lost_write")
    assert not res["correct"] and not line["correct"]
    checks = {n: c["value"] for n, c in line["checks"].items()}
    assert checks["wrong_answers"] > 0 and checks["missing_readbacks"] > 0
    assert checks["failed_ops"] == 0
