"""The per-layer metrics PR 25 added read what the program records: every
spec loads, its counters exist after a traced run, the self-time shares
lie in [0, 100] and a cell's shares sum to at most 100. Drives
harness.run_cell on the CPU at a tiny size, traced. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_layer_metrics.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import layer_metric_specs, run_cell  # noqa: E402
from benchmarks.run import load_cell  # noqa: E402
from pegasus_tpu.utils.metrics import METRICS  # noqa: E402
from pegasus_tpu.utils.tracing import LAYER_KEYS  # noqa: E402

SHARES = ("client_self_share", "gate_self_share", "coordinator_self_share",
          "scan_overlay_share", "block_decode_share",
          "replication_self_share")
COUNTS = ("overlay_rows_per_scan", "rows_examined_per_returned",
          "plog_flushes_per_write", "group_commit_size")
NEW = {"ycsb_e.p64r3": SHARES + COUNTS,
       "ycsb_c.p4r1": ("client_self_share", "gate_self_share",
                       "coordinator_self_share", "block_decode_share")}


def _counter_names() -> set:
    return {(ent["type"], name) for ent in METRICS.snapshot()
            for name in ent["metrics"]}


def _layer_totals() -> dict:
    out = {}
    for ent in METRICS.snapshot(entity_type="layer"):
        for name, m in ent["metrics"].items():
            out[name] = out.get(name, 0) + m["value"]
    return out


@pytest.fixture(scope="module", params=sorted(NEW))
def traced(request):
    _bench, cell, config, traffic = load_cell(request.param)
    traffic = dict(traffic, warmup_windows=8, trace_slice_s=[0.5, 1.0])
    before = _layer_totals()
    res = run_cell(cell["name"], dict(config, records=400), traffic,
                   2_400_000_011, 2.0, True, time.perf_counter())
    layer = {k: v - before.get(k, 0) for k, v in _layer_totals().items()}
    return request.param, res, layer


def test_new_specs_load_and_their_counters_exist(traced):
    cell, res, _layer = traced
    assert res["correct"], res["checks"]
    specs = {s["name"]: s for s in layer_metric_specs(cell)}
    assert set(NEW[cell]) <= set(specs)
    have = _counter_names()
    for name in NEW[cell]:
        spec = specs[name]
        assert spec["reader"] == "counter_ratio"
        pairs = spec["numerator"] + spec["denominator"].get("counters", [])
        for etype, counter in pairs:
            assert (etype, counter) in have, (name, etype, counter)
        assert name in res["per_layer"], name     # none left out


def test_shares_are_shares(traced):
    cell, res, _layer = traced
    shares = [res["per_layer"][n][0] for n in NEW[cell] if n in SHARES]
    assert all(0.0 <= v <= 100.0 for v in shares), shares
    assert sum(shares) <= 100.0 + 1e-6


def test_self_times_cover_the_traced_time(traced):
    _cell, _res, layer = traced
    traced_us = layer["traced_us"]
    self_us = sum(layer[f"{k}_self_us"] for k in LAYER_KEYS)
    assert traced_us > 0
    assert abs(self_us - traced_us) <= 0.01 * traced_us
    assert layer["other_self_us"] <= 0.02 * traced_us
