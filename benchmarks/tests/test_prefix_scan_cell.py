"""The cell `prefix_scan.p64r3` (PR 36): at a rehearsal's size it ends
correct and its control does not; its per-layer metric files load, name
readers and counters that exist, and each is reported by a traced run;
and one pass's static mask programs count the bytes the roofline
reader states. Drives harness.run_cell on the CPU, 8,000 records (two
blocks a partition, so every scanner pages):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_prefix_scan_cell.py -q -p no:cacheprovider
"""

import json
import os
import sys
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import (Cluster, layer_metric_specs,  # noqa: E402
                                reader_module, run_cell)
from benchmarks.ops import scan_prefix  # noqa: E402
from benchmarks.reference import epoch_now  # noqa: E402
from benchmarks.run import load_cell, result_line  # noqa: E402
from pegasus_tpu.utils.metrics import METRICS  # noqa: E402

CELL = "prefix_scan.p64r3"
RECORDS = 8000
SHARES = ("pscan_dispatch_self_share", "pscan_coordinator_self_share",
          "pscan_client_self_share", "pscan_block_decode_share",
          "pscan_mask_cache_hit_share", "pscan_device_idle_share")
COUNTS = ("pscan_mask_programs_per_op", "pscan_mask_MB_per_op",
          "pscan_pages_per_op", "pscan_rows_examined_per_returned")
DEVICE_ONLY = ("pscan_device_peak_hbm_MB", "pscan_mask_roofline")


def _run(trace, fault=None):
    bench, cell, config, traffic = load_cell(CELL)
    traffic = dict(traffic, trace_slice_s=[0.5, 1.5])
    res = run_cell(CELL, dict(config, records=RECORDS), traffic,
                   2_600_000_011, 3.0, trace, time.perf_counter(),
                   fault=fault)
    return bench, cell, res


@pytest.fixture(scope="module")
def pscan_traced():
    return _run(True)


def test_pscan_specs_load_and_match_their_benchmark_entries(pscan_traced):
    bench, _cell, _res = pscan_traced
    specs = {s["name"]: s for s in layer_metric_specs(CELL)}
    assert sorted(specs) == sorted(SHARES + COUNTS + DEVICE_ONLY)
    entries = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert sorted(entries) == sorted(specs)
    for name, entry in entries.items():
        assert entry["workloads"] == [CELL]
        for key, value in entry.items():
            assert specs[name][key] == value, (name, key)
    for spec in specs.values():
        reader = reader_module(spec["reader"])      # a reader that exists
        assert callable(reader.begin) and callable(reader.read)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert {s["moves"] for s in specs.values()} <= e2e


def test_pscan_counters_exist_and_every_metric_is_reported(pscan_traced):
    bench, cell, res = pscan_traced
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert res["info"]["compiled_in_window"] == 0
    # the mix drives the device path itself: no probe beside it
    assert list(res["info"]["ops_by_kind"]) == ["scan_prefix"]
    have = {(ent["type"], name) for ent in METRICS.snapshot()
            for name in ent["metrics"]}
    for spec in layer_metric_specs(CELL):
        if spec["reader"] != "counter_ratio":
            continue
        pairs = list(spec["numerator"])
        pairs += spec["denominator"].get("counters", [])
        for etype, counter in pairs:
            assert (etype, counter) in have, (spec["name"], etype, counter)
    line = result_line(bench, cell, res, True, {"platform": "cpu-test"})
    # a CPU has no memory_stats and no peaks.json entry: the two device
    # readers find nothing to read and the line leaves them out
    assert sorted(line["metrics"]) == sorted(SHARES + COUNTS)
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert all(0.0 <= values[n] <= 100.0 for n in SHARES), values
    assert 0.0 < values["pscan_mask_cache_hit_share"] < 100.0
    assert values["pscan_dispatch_self_share"] > 0
    # 64 partitions of two blocks: one further page a partition, and a
    # fresh pattern costs a stacked program a partition
    assert 32 <= values["pscan_pages_per_op"] <= 128
    assert 0 < values["pscan_mask_programs_per_op"] <= 128
    assert values["pscan_mask_MB_per_op"] > 0
    assert values["pscan_rows_examined_per_returned"] > 10
    json.dumps(line)        # the contract's line serialises


def test_pscan_control_lost_write_is_not_correct():
    _bench, _cell, res = _run(False, fault="lost_write")
    assert not res["correct"]
    checks = {n: v for n, (v, _lim) in res["checks"].items()}
    assert checks["wrong_answers"] > 0
    assert checks["failed_ops"] == 0 and checks["replica_decree_spread"] == 0
    assert "missing_readbacks" not in checks        # the mix writes nothing


def test_pscan_pass_counts_the_bytes_the_roofline_reader_states(tmp_path):
    """One pass under a fresh pattern: per partition the first page's
    look-ahead window of up to 8 blocks goes out as one program (a
    padded stack of 16 where it holds more than one block), and each
    block past the window as a program of its own, a page later."""
    from benchmarks.readers.filter_roofline import program_bytes

    def counted():
        m = next(e["metrics"] for e in METRICS.snapshot()
                 if e["type"] == "engine" and e["id"] == "filter_programs")
        return tuple(m[f"mask_{what}"]["value"]
                     for what in ("programs", "rows", "bytes"))

    _bench, _cell, config, traffic = load_cell(CELL)
    config = dict(config, records=RECORDS)
    cluster = Cluster(config, str(tmp_path))
    try:
        cluster.load(5, epoch_now(), None)
        want = [0, 0, 0]
        for r in cluster.primary_of:
            (run,) = r.server.engine.lsm.l1_runs
            n, width = len(run.blocks), run.blocks[0].key_width
            assert n >= 2 and all(b.count <= 1024 for b in run.blocks)
            programs = [(16384, True)] + [(1024, False)] * max(0, n - 8)
            for rows, stacked in programs:
                want[0] += 1
                want[1] += rows
                want[2] += program_bytes("mask", rows, width, hash_lo=True,
                                         stacked=stacked)
        (args,) = scan_prefix.draw(np.random.default_rng(7), None, 1,
                                   traffic["ops"][0],
                                   {"n_records": RECORDS})
        before = counted()
        (reply, _took), = scan_prefix.send(cluster.client, [args], {})
        assert sum(map(len, reply)) > 0
        assert [a - b for a, b in zip(counted(), before)] == want
        # the same pattern again dispatches nothing
        before = counted()
        scan_prefix.send(cluster.client, [args], {})
        assert counted() == before
    finally:
        cluster.close()
