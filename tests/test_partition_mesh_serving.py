"""Resident mesh SPMD serving acceptance: one whole-table device
dispatch must answer every partition's scan waves and pushdown
aggregates BYTE-IDENTICALLY to the host kernels over every store shape
(stores written under mixed none/dcz/dcz2 codecs, empty-hashkey
overflow rows, unflushed overlay), refresh incrementally at
flush/compaction publish (never serving a stale image), and degrade
through the dispatch watchdog to host serving with zero hung scans when
dispatches overrun their deadline."""

import os
import time

# idempotent with conftest: the virtual 8-device CPU mesh must exist
# before jax initializes (standalone runs of this module included)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import pytest

from pegasus_tpu.client.client import PegasusClient
from pegasus_tpu.client.table import Table
from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_PREFIX,
)
from pegasus_tpu.ops.pushdown import PushdownSpec
from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
from pegasus_tpu.server.types import (
    GetScannerRequest,
    SCAN_CONTEXT_ID_COMPLETED,
)
from pegasus_tpu.utils.errors import StorageStatus
from pegasus_tpu.utils.flags import FLAGS

OK = int(StorageStatus.OK)
N_PARTS = 8


@pytest.fixture
def mesh_guard():
    """Flag + singleton isolation: every test leaves the process-global
    MESH_SERVING detached and the touched flags restored."""
    saved = [(sec, name, FLAGS.get(sec, name)) for sec, name in (
        ("pegasus.storage", "block_codec"),
        ("pegasus.mesh", "serving_enabled"),
        ("pegasus.mesh", "dispatch_deadline_s"),
        ("pegasus.server", "rocksdb_max_iteration_count"),
    )]
    MESH_SERVING.reset()
    yield
    MESH_SERVING.reset()
    for sec, name, val in saved:
        FLAGS.set(sec, name, val)


def drain(s, req):
    rows, shipped = [], 0
    resp = s.on_get_scanner(req)
    while True:
        assert resp.error == OK
        shipped += resp.wire_bytes()
        rows.extend((kv.key, kv.value) for kv in resp.kvs)
        if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
            return rows, shipped, resp.agg
        resp = s.on_scan(resp.context_id)


def vf_req(pat, ft=FT_MATCH_ANYWHERE, agg="", k=0, seed=0, **kw):
    pd = PushdownSpec(value_filter_type=ft, value_filter_pattern=pat,
                      aggregate=agg, k=k, seed=seed)
    return GetScannerRequest(pushdown=pd, **kw)


def build_mixed_table(tmp_path, rows=240, compact_codec=None):
    """8 partitions whose history crosses every storage shape: rows
    written under three SST codec generations (none/dcz/dcz2) plus
    empty-hashkey rows (dcz2's group-overflow slots). The wave/aggregate
    serving paths only exist over pure sorted runs, so when
    `compact_codec` is set every partition is compacted under it."""
    table = Table(str(tmp_path), partition_count=N_PARTS)
    c = PegasusClient(table)
    i = 0
    for codec in ("none", "dcz", "dcz2"):
        FLAGS.set("pegasus.storage", "block_codec", codec)
        for _ in range(rows // 3):
            v = b"blue-%04d" % i if i % 5 == 0 else b"red-%04d" % i
            assert c.set(b"hk%02d" % (i % 13), b"s%05d" % i, v) == 0
            i += 1
        assert c.set(b"", b"osk%02d" % (i % 7), b"blue-ovf-%d" % i) == 0
        i += 1
        table.flush_all()
    if compact_codec is not None:
        FLAGS.set("pegasus.storage", "block_codec", compact_codec)
        for s in table.partitions.values():
            s.engine.flush()
            s.engine.manual_compact()
    return table, c


def all_rows(table, req_factory):
    """Per-partition full drains (fresh request per drain)."""
    return {p: drain(s, req_factory())[0]
            for p, s in sorted(table.partitions.items())}


def clear_mask_caches(table):
    """Static keep masks cache per (ckey, filters): clear so each arm
    evaluates REAL waves instead of replaying the other arm's masks."""
    for s in table.partitions.values():
        with s._mask_lock:
            s._mask_cache.clear()


def force_mesh_pays(monkeypatch):
    """Tiny test fixtures never amortize a dispatch; the identity tests
    pin the routing gate open so every wave exercises the mesh path (the
    real gate has its own test + the bench's 8-partition phase)."""
    from pegasus_tpu.ops import placement
    monkeypatch.setattr(placement, "mesh_wave_pays", lambda *_a: True)


def attach_all(table):
    for s in table.partitions.values():
        MESH_SERVING.attach(s)


REQS = (
    ("plain", lambda: GetScannerRequest(batch_size=171)),
    ("value-filter", lambda: vf_req(b"blue", batch_size=64)),
    ("hash-prefix", lambda: GetScannerRequest(
        hash_key_filter_type=FT_MATCH_PREFIX,
        hash_key_filter_pattern=b"hk0", batch_size=97)),
)


@pytest.mark.parametrize("codec", ["none", "dcz", "dcz2"])
def test_wave_identity_mixed_codecs(tmp_path, mesh_guard, monkeypatch,
                                    codec):
    table, _c = build_mixed_table(tmp_path, compact_codec=codec)
    try:
        host = {name: all_rows(table, f) for name, f in REQS}
        assert any(host["value-filter"].values()), "degenerate fixture"
        assert any(host["hash-prefix"].values()), "degenerate fixture"
        clear_mask_caches(table)
        force_mesh_pays(monkeypatch)
        attach_all(table)
        st0 = MESH_SERVING.status()
        for name, f in REQS:
            assert all_rows(table, f) == host[name], (codec, name)
        st1 = MESH_SERVING.status()
        # the mesh actually served (not silently declined to host)
        assert MESH_SERVING.wave_dispatches > 0
        assert st1["mesh_dispatch_count"] > st0["mesh_dispatch_count"]
        assert st1["mesh_verdict_share"] > 0.0
    finally:
        table.close()


def test_wave_identity_with_overlay(tmp_path, mesh_guard, monkeypatch):
    """An unflushed overlay generation must not poison identity: the
    overlay merge shadows on top of whatever arm serves the base."""
    table, c = build_mixed_table(tmp_path, compact_codec="dcz2")
    try:
        force_mesh_pays(monkeypatch)
        attach_all(table)
        base = all_rows(table, REQS[1][1])
        assert MESH_SERVING.wave_dispatches > 0
        assert c.set(b"hk00", b"s00000", b"red-shadowed") == 0
        assert c.set(b"hknew", b"s0", b"blue-overlay-only") == 0
        clear_mask_caches(table)
        with_overlay = all_rows(table, REQS[1][1])
        assert with_overlay != base  # the overlay is visible
        MESH_SERVING.reset()
        clear_mask_caches(table)
        assert all_rows(table, REQS[1][1]) == with_overlay
    finally:
        table.close()


def test_aggregates_mesh_vs_host_single_dispatch(tmp_path, mesh_guard):
    table, _c = build_mixed_table(tmp_path, compact_codec="dcz2")
    try:
        def agg_wires(kind, k=0, seed=0):
            return {p: drain(s, vf_req(b"blue", agg=kind, k=k,
                                       seed=seed))[2]
                    for p, s in sorted(table.partitions.items())}

        host = {kind: agg_wires(kind, k=3, seed=9)
                for kind in ("count", "sum", "top_k", "sample")}
        assert sum(w["count"] for w in host["count"].values()) > 0
        attach_all(table)
        # all four aggregates: psum counts/sums and host-edge top_k /
        # sample folds must match the host arm byte for byte — and ALL
        # 32 (kind, partition) folds share TWO dispatches (one per
        # with_sum flavor; count/top_k/sample reuse the same cached
        # static+counts image), tolerance for wall-clock-second ticks
        # splitting a run into extra cache generations
        for kind in ("count", "sum", "top_k", "sample"):
            assert agg_wires(kind, k=3, seed=9) == host[kind], kind
        assert 2 <= MESH_SERVING.agg_dispatches <= 6
        assert MESH_SERVING.status()["mesh_dispatch_count"] > 0
    finally:
        table.close()


def test_incremental_refresh_no_stale_image(tmp_path, mesh_guard,
                                            monkeypatch):
    table, c = build_mixed_table(tmp_path, rows=120, compact_codec="dcz")
    try:
        force_mesh_pays(monkeypatch)
        attach_all(table)
        before = all_rows(table, REQS[0][1])
        assert MESH_SERVING.wave_dispatches > 0
        sb0, stk0 = MESH_SERVING.slab_builds, MESH_SERVING.stack_builds
        assert sb0 >= N_PARTS  # first image staged every partition
        # dirty exactly ONE partition: new rows, flush + compact publish
        target = table.resolve(b"hot-hk")
        for j in range(40):
            assert c.set(b"hot-hk", b"z%03d" % j, b"blue-hot-%d" % j) == 0
        target.engine.flush()
        target.engine.manual_compact()
        clear_mask_caches(table)
        w0 = MESH_SERVING.wave_dispatches
        after = all_rows(table, REQS[0][1])
        # the REFRESHED image served these waves — not a host fallback
        assert MESH_SERVING.wave_dispatches > w0
        grew = {p for p in after if len(after[p]) != len(before[p])}
        assert grew == {target.pidx}, "stale (or over-fresh) mesh image"
        got = {v for _k, v in after[target.pidx]}
        assert all(b"blue-hot-%d" % j in got for j in range(40))
        # incremental: only the published partition restaged
        assert MESH_SERVING.slab_builds == sb0 + 1
        assert MESH_SERVING.stack_builds == stk0 + 1
        # a second compaction publish must invalidate again (same rows)
        target.engine.manual_compact()
        clear_mask_caches(table)
        assert all_rows(table, REQS[0][1]) == after
        assert MESH_SERVING.slab_builds <= sb0 + 2
    finally:
        table.close()


def test_watchdog_trip_degrades_to_host_mid_scan(tmp_path, mesh_guard,
                                                 monkeypatch):
    table, _c = build_mixed_table(tmp_path, rows=120, compact_codec="none")
    try:
        host = all_rows(table, REQS[1][1])
        clear_mask_caches(table)
        force_mesh_pays(monkeypatch)
        attach_all(table)
        # every dispatch now overruns: the second consecutive failure
        # must trip the watchdog; a trip disables mesh
        # serving outright and the host kernels carry the rest
        MESH_SERVING.watchdog.deadline_s = 1e-9
        t0 = time.monotonic()
        degraded = all_rows(table, REQS[1][1])
        wall = time.monotonic() - t0
        assert degraded == host, "fallback rows differ from host arm"
        assert wall < 60.0, "a wedged dispatch hung the scan"
        st = MESH_SERVING.status()
        assert st["mesh_fallback_count"] >= 2
        assert st["watchdog"]["trips"] >= 1
        assert st["dispatch_wedged"] is True
        assert MESH_SERVING.disabled and not MESH_SERVING.enabled
        # wedged is a verdict, not a wedge: later scans still correct
        clear_mask_caches(table)
        assert all_rows(table, REQS[1][1]) == host
    finally:
        table.close()


def test_make_mesh_single_device_degrades():
    from pegasus_tpu.parallel.partition_mesh import make_mesh

    with pytest.warns(RuntimeWarning, match="single-device host"):
        pm = make_mesh(n_devices=1, dp=8)
    assert pm.dp == 1 and pm.sp == 1
    # multi-device invalid factorizations still fail loudly
    with pytest.raises(ValueError):
        make_mesh(dp=3)


def test_mesh_cost_gate_and_verdict():
    from pegasus_tpu.ops import placement

    # single-chunk waves share the host dispatch floor: nothing to
    # amortize, the mesh must decline
    assert not placement.mesh_wave_pays(1, 4096)
    # multi-chunk / multi-partition waves collapse to one round and win
    assert placement.mesh_wave_pays(8, 1 << 20)
    assert placement.placement_verdict("mesh") == "mesh"
    assert placement.predict_kernel_seconds("mesh", 1 << 20) > 0.0


def test_explain_reports_mesh_ride(tmp_path, mesh_guard, monkeypatch):
    from pegasus_tpu.server import explain as explain_mod

    # codec "none": compressed blocks resolve their static masks via
    # the encoded-domain host probe and never reach the wave path
    table, _c = build_mixed_table(tmp_path, rows=120, compact_codec="none")
    try:
        force_mesh_pays(monkeypatch)
        attach_all(table)
        clear_mask_caches(table)  # prefreshed masks would skip the wave
        s = table.partitions[0]
        # a FULL-range scan: the shape that rides the stacked wave path
        # (hashkey-scoped scans take the block-probe path, no waves)
        spec = explain_mod.spec_from_words(
            ["scan", "filter=blue", "batch_size=1000"])
        op, args, ph = explain_mod.op_from_spec(spec)
        report = explain_mod.explain_op(s, op, args, partition_hash=ph)
        assert report["perf"]["placement"] == "mesh"
        assert report["perf"]["mesh_partitions"] >= 1
        assert report["perf"]["mesh_wave_ms"] > 0.0
        rendered = explain_mod.render_report(report)
        assert "mesh: partitions=" in rendered
        # the aggregate explain rides the mesh aggregate arm
        spec = explain_mod.spec_from_words(["scan", "filter=blue",
                                            "agg=count"])
        op, args, ph = explain_mod.op_from_spec(spec)
        report = explain_mod.explain_op(s, op, args, partition_hash=ph)
        assert report["perf"]["placement"] == "mesh"
        assert report["perf"]["rows_aggregated"] == \
            report["result"]["agg"]["count"]
    finally:
        table.close()


def test_aggregate_declines_paged_and_overlay(tmp_path, mesh_guard):
    """The mesh aggregate only answers folds the host arm would serve in
    ONE page over pure sorted runs; paging budgets smaller than the
    resident range and overlay generations keep riding the host arm
    (and stay correct)."""
    table, c = build_mixed_table(tmp_path, compact_codec="dcz2")
    try:
        host = {p: drain(s, vf_req(b"blue", agg="count"))[2]
                for p, s in sorted(table.partitions.items())}
        attach_all(table)
        # paged: a budget below the resident row count forces the host
        # paging protocol (partial rides the context, ships last)
        FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", 10)
        a0 = MESH_SERVING.agg_dispatches
        got = {p: drain(s, vf_req(b"blue", agg="count"))[2]
               for p, s in sorted(table.partitions.items())}
        assert got == host and MESH_SERVING.agg_dispatches == a0
        FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", 0)
        # overlay: an unflushed write reopens the merge path
        assert c.set(b"hk01", b"blue-snew", b"blue-overlay") == 0
        target = table.resolve(b"hk01")
        a0 = MESH_SERVING.agg_dispatches
        agg = drain(target, vf_req(b"blue", agg="count"))[2]
        assert agg["count"] == host[target.pidx]["count"] + 1
        assert MESH_SERVING.agg_dispatches == a0
    finally:
        table.close()


def test_mesh_metrics_lint_and_health_rule():
    from pegasus_tpu.tools.metrics_lint import lint
    from pegasus_tpu.utils.health import default_rules

    assert not [c for c in lint() if "mesh" in c or "wedged" in c]
    rules = [r for r in default_rules()
             if r.name == "mesh_dispatch_wedged"]
    assert len(rules) == 1 and rules[0].hold == 2
