"""The cell `ttl_compact.p64r3` (config `ycsb_ttl_p64r3`, BASELINE #3):
the `ttl` loader's table, and the cell rehearsed on a tiny table on the
CPU under either placement of the TTL filter, held to its guarantees
T1-T3 (T4 is tests/test_ttl_filter_program.py's). Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_ttl_cell.py -q -p no:cacheprovider
"""

import hashlib
import json
import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))))

from benchmarks.generator import load_json  # noqa: E402
from benchmarks.harness import table_rows  # noqa: E402
from benchmarks.reference import make_records  # noqa: E402

CONFIG = "ycsb_ttl_p64r3"
LOAD_NOW = 340_000_000
TTL_SEEDS = (2_147_483_659, 4_000_000_037)


def _ttl_config(**changes) -> dict:
    return dict(load_json("configs", CONFIG), **changes)


def _digest(rows) -> str:
    h = hashlib.sha256()
    for hk, sk, value, ets in rows:
        h.update(b"%s|%s|%s|%d\n" % (hk, sk, value, ets))
    return h.hexdigest()


@pytest.mark.parametrize("seed", TTL_SEEDS)
def test_ttl_loader_half_expired_none_expiring_soon(seed):
    cfg = _ttl_config(records=20_000)
    ttl = int(cfg["table_envs"]["default_ttl"])
    margin = cfg["expiry_margin_s"]
    age = cfg["load_compaction_age_s"]
    rows = list(table_rows(cfg, seed, LOAD_NOW))
    assert len(rows) == 20_000 * cfg["fields"]
    ets = [e for _hk, _sk, _v, e in rows]
    expired = sum(1 for e in ets if 0 < e <= LOAD_NOW)
    assert 0.45 <= expired / len(rows) <= 0.55
    # live rows: none within the margin, none past the retention
    assert all(e > LOAD_NOW + margin for e in ets if e > LOAD_NOW)
    assert max(ets) <= LOAD_NOW + ttl
    # all of them outlive the set-up's compaction, a week back
    assert min(ets) > LOAD_NOW - age
    # a record's rows share one write time; the values are
    # make_records' for the seed
    for off in range(0, len(rows), cfg["fields"]):
        assert len({e for *_r, e in rows[off:off + cfg["fields"]]}) == 1
    assert [(hk, sk, v) for hk, sk, v, _e in rows] == [
        (hk, sk, v) for hk, sk, v, _e in make_records(
            seed, 20_000, cfg["fields"], cfg["field_length"], 0.0,
            LOAD_NOW)]


def test_ttl_loader_is_deterministic_by_seed():
    cfg = _ttl_config(records=2_000)
    a, b = (_digest(table_rows(cfg, s, LOAD_NOW)) for s in TTL_SEEDS)
    assert a != b
    assert _digest(table_rows(cfg, TTL_SEEDS[0], LOAD_NOW)) == a


def test_ttl_cell_entries_name_its_files():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.realpath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "ttl_compact.p64r3"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ttl_compact", 1)
    (conf,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    cfg = load_json("configs", CONFIG)
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["loader"] == "ttl" and cfg["table_envs"] == {
        "default_ttl": "604800"}
    mix = load_json("traffic", "ttl_compact")
    (trigger,) = [o for o in mix["ops"] if o["kind"] == "ttl_compact"]
    assert "user_specified_compaction" not in trigger["app_envs"]
    assert "trace_probe" not in mix
    names = {m["name"] for m in bench["per_layer"]
             if "ttl_compact.p64r3" in m["workloads"]}
    assert names == {
        "ttl_compact_filter_roofline", "ttl_compact_in_MB_s",
        "ttl_compact_passes_in_window", "ttl_compact_dropped_rows",
        "ttl_filter_device_block_share", "ttl_rows_examined_per_returned",
        "ttl_scan_expired_per_returned", "ttl_device_idle_share"}
    for name in names:
        spec = load_json("layer_metrics", name)
        assert spec["workloads"] == ["ttl_compact.p64r3"]


def _dropped_ttl() -> int:
    from pegasus_tpu.utils.metrics import METRICS

    return sum(ent["metrics"]["compact_rows_dropped_ttl"]["value"]
               for ent in METRICS.snapshot()
               if "compact_rows_dropped_ttl" in ent["metrics"])


@pytest.mark.parametrize("verdict", ["host-XLA", "device"])
def test_ttl_cell_rehearsal_drops_each_expired_row_once_a_replica(
        monkeypatch, verdict):
    """The cell at the rehearsal's 400 records and 2-second window,
    with 60 warm-up windows: the mix's first trigger (window 45 of the
    layout stream) falls inside the warm-up, as in the cell's own 100,
    so the replicas that accept it compile there. The process's pool
    is the test's own; the cluster stagger's grant,
    which the three sim nodes take in turn at their beacons (3 s of
    real time apart, once a window at this size), is left to fail open
    as without a coordinator, so every replica compacts within the
    rehearsal. `device`: the TTL program forced on (the CPU backend
    stands in for the chip), warmed by the trigger's delivery."""
    from benchmarks.harness import run_cell
    from benchmarks.ops import ttl_compact
    from benchmarks.rehearse import SECONDS, TINY
    from benchmarks.run import load_cell
    from pegasus_tpu.ops import placement
    from pegasus_tpu.storage import compact_governor

    monkeypatch.setattr(compact_governor, "MANUAL_COMPACT_POOL",
                        compact_governor.ManualCompactPool("ttl-cell"))
    monkeypatch.setattr(compact_governor.GOVERNOR, "set_cluster_grant",
                        lambda granted: None)
    monkeypatch.setattr(compact_governor.GOVERNOR, "_grant", None)
    if verdict == "device":
        # the probe as a device on this host's own bus reads it
        import jax

        monkeypatch.setattr(placement, "_PROBE", placement.LinkProbe(
            1e-4, 10.0, 10.0, jax.devices()[0]))
    assert placement.placement_verdict("ttl") == verdict
    dropped_before = _dropped_ttl()
    _bench, _cell, config, traffic = load_cell("ttl_compact.p64r3")
    traffic = dict(traffic, warmup_windows=60, trace_slice_s=[0.5, 1.0])
    res = run_cell("ttl_compact.p64r3", dict(config, **TINY), traffic,
                   3_000_000_019, SECONDS, True, time.perf_counter())
    assert res["correct"], json.dumps(res["checks"])
    assert all(v == 0 for v, _lim in res["checks"].values())
    assert res["info"]["compiled_in_window"] == 0
    assert res["attempted"] > 1_000
    metrics = res["per_layer"]
    expired = ttl_compact.EXPIRED_ON_DISK
    assert 0.45 * 4_000 <= expired <= 0.55 * 4_000
    # the metric counts since the process started: every run of it
    assert (metrics["ttl_compact_dropped_rows"][0] - dropped_before
            == 3 * expired)
    share = metrics["ttl_filter_device_block_share"][0]
    assert share == (100.0 if verdict == "device" else 0.0)
    assert metrics["ttl_scan_expired_per_returned"][0] > 0.3
    assert metrics["ttl_rows_examined_per_returned"][0] > 1.0
    # a device kind the rehearsal does not know: no roofline
    assert "ttl_compact_filter_roofline" not in metrics
