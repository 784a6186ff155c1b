"""Info-collector + availability-detector tests (parity:
src/server/info_collector.h:48, available_detector.h:49)."""

import pytest

from pegasus_tpu.tools.cluster import SimCluster
from pegasus_tpu.tools.collector import DETECT_TABLE, STAT_TABLE, InfoCollector


@pytest.fixture
def cluster(tmp_path):
    c = SimCluster(str(tmp_path / "c"), n_nodes=3)
    yield c
    c.close()


def make_collector(cluster):
    cluster.create_table(STAT_TABLE, partition_count=2)
    cluster.create_table(DETECT_TABLE, partition_count=2)
    return InfoCollector(cluster.net, "collector",
                         list(cluster.stubs), cluster.client, cluster.pump)


def test_collect_round_aggregates_and_persists(cluster):
    cluster.create_table("traffic", partition_count=4)
    c = cluster.client("traffic")
    for i in range(30):
        assert c.set(b"t%02d" % i, b"s", b"v" * 100) == 0
    for i in range(30):
        assert c.get(b"t%02d" % i, b"s")[0] == 0
    col = make_collector(cluster)
    per_table = col.collect_round()
    app_id = str(c.app_id)
    assert app_id in per_table
    assert per_table[app_id]["write_cu"] > 0
    assert per_table[app_id]["read_cu"] > 0
    assert per_table[app_id]["partitions"] >= 4
    # the row landed in the stat table (result_writer parity)
    history = col.table_history(app_id)
    assert history and history[-1]["write_cu"] == \
        per_table[app_id]["write_cu"]


def test_availability_probe_tracks_failures(cluster):
    col = make_collector(cluster)
    assert col.probe_round(probes=5) == 1.0
    # cut every node off: probes fail, availability drops below 1
    for name in list(cluster.stubs):
        cluster.kill(name)
    col._detect_client._max_retries = 1
    col._detect_client._pump_rounds = 3
    av = col.probe_round(probes=3)
    assert av < 1.0
    assert col.probe_total == 8 and col.probe_failed >= 3


def test_collect_dups_aggregates_per_table_lag_rows(cluster):
    """The collector's geo-replication surface: every node's dup.stats
    verb rolls up into one per-table row (worst lag, shipped/error
    totals) persisted as the `_dups` stat row."""
    import json

    from pegasus_tpu.utils.metrics import METRICS

    cluster.create_table("gm", partition_count=2)
    cluster.create_table("gf", partition_count=2)
    c = cluster.client("gm")
    for i in range(15):
        assert c.set(b"g%02d" % i, b"s", b"v%d" % i) == 0
    # duplication entity ids are node.app.pidx.dupid — other sim tests
    # in this process may have used colliding ids, so counter
    # assertions are DELTAS against this snapshot, never absolutes
    pre_skips = sum(ent["metrics"].get("dup_skip_count",
                                       {}).get("value", 0)
                    for ent in METRICS.snapshot("duplication"))
    cluster.meta.duplication.add_duplication("gm", "meta", "gf")
    cluster.step(rounds=6)
    col = make_collector(cluster)
    rows = col.collect_dups()
    app_id = str(c.app_id)
    assert app_id in rows, rows
    assert rows[app_id]["sessions"] >= 2  # one per partition
    assert rows[app_id]["shipped_bytes"] > 0
    assert rows[app_id]["max_lag_decrees"] == 0  # fully drained
    post_skips = sum(ent["metrics"].get("dup_skip_count",
                                        {}).get("value", 0)
                     for ent in METRICS.snapshot("duplication"))
    assert post_skips == pre_skips  # this dup abandoned nothing
    # the row rides collect_round into the stat table
    col.collect_round()
    err, kvs = col._stat_client.multi_get(b"_dups")
    assert err == 0 and kvs
    persisted = json.loads(sorted(kvs.items())[-1][1])
    assert persisted[app_id]["shipped_bytes"] > 0


def test_probe_round_healthy_then_partitioned_node_degrades(cluster):
    """The availability detector under SimCluster: a healthy cluster
    probes at 1.0; partitioning ONE node (not killing it — its
    partitions stay assigned until the FD cures them) degrades the
    fraction below 1.0; healing and re-probing raises it again."""
    col = make_collector(cluster)
    assert col.probe_round(probes=6) == 1.0
    assert col.probe_total == 6 and col.probe_failed == 0
    victim = next(iter(cluster.stubs))
    cluster.net.partition(victim)
    col._detect_client._max_retries = 1
    col._detect_client._pump_rounds = 3
    av = col.probe_round(probes=6)
    assert av < 1.0
    assert col.probe_failed >= 1
    cluster.net.heal(victim)
    cluster.step(rounds=2)
    col._detect_client._max_retries = 3
    col._detect_client._pump_rounds = 100
    assert col.probe_round(probes=6) > av


def test_collect_workload_row_aggregates_shape_stats(cluster):
    """The workload-profiler surface (PR 15): per-table op mix /
    batch-size / selectivity / hot-share roll up from the nodes'
    `workload` metric entities into one `_workload` stat row, with the
    node cost-model drift ratio alongside."""
    import json as _json

    cluster.create_table("wl", partition_count=4)
    c = cluster.client("wl")
    col = make_collector(cluster)
    # workload entity ids are app.pidx and the registry is process-
    # global, so (like the dup test above) counter assertions are
    # DELTAS against this snapshot, never absolutes (c.app_id resolves
    # lazily — read it after the first op)
    pre_rows = col.collect_workload().get("tables", {})
    for i in range(25):
        assert c.set(b"w%02d" % i, b"s", b"v" * 80) == 0
    for i in range(25):
        assert c.get(b"w%02d" % i, b"s")[0] == 0
    err, kvs = c.multi_get(b"w03")  # ranged leg feeds selectivity
    assert err == 0 and kvs
    app_id = str(c.app_id)
    pre = pre_rows.get(app_id, {})
    out = col.collect_workload()
    rows = out["tables"]
    assert app_id in rows, rows
    agg = rows[app_id]
    # entities dedupe by id across the scraped nodes: PARTITIONS, not
    # replicas (a per-node sum reported 12 partitions and ~3x ops for
    # this exact scenario — the read delta below would be 75), and the
    # 25 primary-served reads count exactly once. Another test file of
    # this worker may have registered workload entities under the same
    # app id (the registry is the process's), so the count is held to
    # the registry's own distinct ids for this table, never to 4.
    from pegasus_tpu.utils.metrics import METRICS
    ids = {e["id"] for e in METRICS.snapshot(entity_type="workload")
           if e.get("attributes", {}).get("table") == app_id}
    assert 4 <= len(ids) == agg["partitions"]
    assert agg["read_ops"] - pre.get("read_ops", 0) == 25
    # writes apply on secondaries too and the in-process sim shares
    # one registry (the known storage/rpc-singleton artifact), so the
    # floor — never an exact count — is what's assertable here
    assert agg["write_ops"] - pre.get("write_ops", 0) >= 25
    assert agg["scan_ops"] - pre.get("scan_ops", 0) >= 1
    assert agg["scan_selectivity_p50"] > 0.0
    assert agg["value_bytes_p99"] >= 80
    assert "drift_ratio" in out  # beside the tables, never among them
    # every tables value is a row dict (the sentinel-key regression)
    assert all(isinstance(v, dict) for v in rows.values())
    # the row rides collect_round into the stat table
    col.collect_round()
    err, kvs = col._stat_client.multi_get(b"_workload")
    assert err == 0 and kvs
    persisted = _json.loads(sorted(kvs.items())[-1][1])
    assert persisted["tables"][app_id]["read_ops"] > 0


def test_collect_round_persists_health_and_alert_rows(cluster):
    """The flight-recorder rows: `_health` lands per-node watchdog
    status in table history each round; `_alerts` appears once a node
    journals a typed event."""
    import json as _json

    from pegasus_tpu.utils.fail_point import FAIL_POINTS
    from pegasus_tpu.utils.flags import FLAGS

    cluster.create_table("traffic2", partition_count=2)
    c = cluster.client("traffic2")
    for i in range(10):
        assert c.set(b"h%02d" % i, b"s", b"v") == 0
    cluster.step(rounds=3)
    col = make_collector(cluster)
    col.collect_round()
    err, kvs = col._stat_client.multi_get(b"_health")
    assert err == 0 and kvs
    rows = _json.loads(sorted(kvs.items())[-1][1])
    assert set(rows) == set(cluster.stubs)
    for node, row in rows.items():
        assert row["status"] == "ok" and row["firing"] == []
        assert row["ring_bytes"] > 0
    # fire an incident on one node -> its `_alerts` row appears
    victim = "node0"
    FLAGS.set("pegasus.health", "recorder_interval_s", 1.0)
    FAIL_POINTS.setup()
    FAIL_POINTS.cfg(f"stub_read_shed:{victim}", "return(busy)")
    try:
        for _ in range(4):
            for i in range(10):
                try:
                    c.get(b"h%02d" % i, b"s")
                except Exception:  # noqa: BLE001 - shed IS the scenario
                    pass
            cluster.step()
        col.collect_round()
    finally:
        FAIL_POINTS.teardown()
        from pegasus_tpu.utils import health as health_mod

        health_mod.reset_capture()
        FLAGS.set("pegasus.health", "recorder_interval_s", 10.0)
        FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    err, kvs = col._stat_client.multi_get(b"_health")
    assert err == 0
    rows = _json.loads(sorted(kvs.items())[-1][1])
    assert rows[victim]["status"] == "degraded"
    assert "read_shed_growth" in rows[victim]["firing"]
    err, kvs = col._stat_client.multi_get(b"_alerts")
    assert err == 0 and kvs
    alerts = _json.loads(sorted(kvs.items())[-1][1])
    assert any(ev["rule"] == "read_shed_growth" and ev["firing"]
               for ev in alerts.get(victim, []))
