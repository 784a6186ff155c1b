"""`scan_merge_path_per_op` (PR 26) reads through `counter_ratio` like its
siblings: the spec loads for its cell and agrees with its `BENCHMARK.json`
entry, the reader turns the counter's delta into requests a scan, and a
traced tiny run of the cell reports it. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_scan_merge_path_metric.py -q -p no:cacheprovider
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import (  # noqa: E402
    layer_metric_specs,
    reader_module,
    run_cell,
)
from benchmarks.run import load_cell  # noqa: E402
from pegasus_tpu.utils.metrics import METRICS  # noqa: E402

NAME = "scan_merge_path_per_op"
CELL = "ycsb_e.p64r3"


def _spec(cell):
    return {s["name"]: s for s in layer_metric_specs(cell)}.get(NAME)


def test_spec_agrees_with_its_benchmark_entry():
    spec = _spec(CELL)
    assert spec is not None and _spec("ycsb_c.p4r1") is None
    assert spec["reader"] == "counter_ratio"
    assert spec["numerator"] == [["storage", "scan_merge_path_requests"]]
    assert spec["denominator"] == {"ops": ["scan"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == NAME]
    assert entry == {k: spec[k] for k in entry}


def test_reader_divides_the_counters_delta_by_scans():
    spec = _spec(CELL)
    reader = reader_module(spec["reader"])
    counter = METRICS.entity("storage", "node").counter(
        "scan_merge_path_requests")
    counter.increment(5)                    # before the window: not read
    before = reader.begin(spec)
    counter.increment(6)
    assert reader.read(spec, before, {"by_kind": {"scan": 4}}) == 1.5
    assert reader.read(spec, before, {"by_kind": {"insert": 4}}) is None


def test_a_traced_run_reports_it():
    _bench, cell, config, traffic = load_cell(CELL)
    traffic = dict(traffic, warmup_windows=8, trace_slice_s=[0.5, 1.0])
    res = run_cell(cell["name"], dict(config, records=400), traffic,
                   2_400_000_029, 2.0, True, time.perf_counter())
    assert res["correct"], res["checks"]
    value, unit = res["per_layer"][NAME]
    # every insert of the window sits in a memtable: some scans merge,
    # and a scan is one request unless its page is continued
    assert unit == "1/op" and 0.0 < value <= 2.0
    walked, _unit = res["per_layer"]["overlay_rows_per_scan"]
    assert walked > 0.0
