"""Distributed-tracing tests: context propagation across SimCluster
hops (incl. batch fan-out/fan-in parenting), tail keep, ring bounds,
cross-node stitching with clock alignment, the zero-overhead off state,
plus the transport error counters and the Prometheus exposition."""

import json
import time
import urllib.request

import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.tools.cluster import SimCluster
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS, MetricEntity, to_prometheus


@pytest.fixture(autouse=True)
def _trace_isolation():
    """Every test starts with empty rings, deterministic ids, and
    sampling OFF; nothing leaks into later tests."""
    tracing.reset()
    tracing.seed(7)
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    yield
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    FLAGS.set("pegasus.tracing", "ring_capacity", 2048)
    FLAGS.set("pegasus.tracing", "slow_trace_ms", 20.0)
    tracing.reset()


@pytest.fixture
def cluster(tmp_path):
    c = SimCluster(str(tmp_path / "c"), n_nodes=3, seed=5)
    yield c
    c.close()


def _partition_of(cluster, hk, sk, partition_count):
    pidx = key_hash_parts(hk, sk) % partition_count
    return pidx, cluster.meta.state.get_partition(1, pidx)


def _cluster_spans(cluster, client, tid):
    """The `shell trace <id>` machinery: local (client) ring + the
    trace-dump remote verb fanned to every node."""
    spans = list(tracing.ring_for(client.name).dump(tid))
    for stub in cluster.stubs.values():
        spans += stub.commands.call("trace-dump", [tid])
    return spans


# ---- sampling off: nothing happens ---------------------------------------


def test_sampled_zero_adds_no_spans(cluster):
    cluster.create_table("t", partition_count=2)
    c = cluster.client("t")
    assert c.set(b"hk", b"s", b"v") == 0
    assert c.get(b"hk", b"s")[0] == 0
    assert tracing.dump_all() == []
    # and no payload grew a context: the rings never even saw a trace
    assert tracing.ring_for(c.name).dump() == []


# ---- propagation + stitching ---------------------------------------------


def test_write_trace_crosses_every_hop(cluster):
    cluster.create_table("t", partition_count=2, replica_count=3)
    c = cluster.client("t")
    pidx, pc = _partition_of(cluster, b"hk", b"s", 2)
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    assert c.set(b"hk", b"s", b"v") == 0
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    client_spans = tracing.ring_for(c.name).dump()
    roots = [s for s in client_spans if s["parent"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "client.write"
    tid = roots[0]["trace"]
    spans = _cluster_spans(cluster, c, tid)
    nodes = {s["node"] for s in spans}
    # client, primary, and both secondaries all contributed spans
    assert c.name in nodes and pc.primary in nodes
    for sec in pc.secondaries:
        assert sec in nodes
    by_id = {s["span"]: s for s in spans}
    # every non-root span's parent resolves inside the same trace
    for s in spans:
        assert s["trace"] == tid
        if s["parent"] is not None:
            assert s["parent"] in by_id
    # the 2PC span carries the LatencyTracer stage chain as annotations
    tpc = [s for s in spans if s["name"].startswith("2pc.")]
    assert len(tpc) == 1
    stages = [a[0] for a in tpc[0]["ann"]]
    for want in ("prepare_local", "append_plog", "plog_durable",
                 "prepares_sent", "committed_applied", "replied"):
        assert want in stages


def test_stitch_one_rooted_tree_monotonic(cluster):
    cluster.create_table("t", partition_count=2, replica_count=3)
    c = cluster.client("t")
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    assert c.set(b"hk", b"s", b"v") == 0
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    tid = tracing.ring_for(c.name).dump()[-1]["trace"]
    tree = tracing.stitch(_cluster_spans(cluster, c, tid))
    assert tree is not None and tree["name"] == "client.write"

    seen = []

    def check(n):
        seen.append(n)
        for ch in n["children"]:
            # per-hop alignment is monotonic: a child never starts
            # before its parent on the stitched timeline
            assert ch["rel_ms"] >= n["rel_ms"] - 1e-6
            check(ch)

    check(tree)
    assert len(seen) >= 4  # client -> dispatch -> 2pc -> prepare hops
    # rendering never throws and names every hop
    text = tracing.render(tree)
    assert "client.write" in text and "2pc." in text


# ---- the acceptance scenario: injected slow secondary --------------------


def test_slow_secondary_trace_and_tail_keep(cluster):
    """FaultPlan-style delay on the prepare link: `trace <id>` stitches
    one cross-node tree whose longest (self-time) span is the delayed
    prepare hop, and tail keep pins the trace at every hop the keep
    decision reaches."""
    cluster.create_table("t", partition_count=2, replica_count=3)
    c = cluster.client("t")
    pidx, pc = _partition_of(cluster, b"hk", b"s", 2)
    slow_peer = pc.secondaries[0]
    cluster.net.set_delay(0.5, src=pc.primary, dst=slow_peer)
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    assert c.set(b"hk", b"s", b"v") == 0
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    # tail keep: the client's op crossed the slow threshold -> pinned
    kept = tracing.ring_for(c.name).slow_roots()
    assert kept and kept[-1]["name"] == "client.write"
    assert kept[-1]["total_ms"] >= 500.0
    tid = kept[-1]["trace"]
    # ... and the primary pinned too (local slow prepare hop + the keep
    # bit riding the reply pinned the client; spans exist on all hops)
    assert tracing.ring_for(pc.primary).is_kept(tid)
    spans = _cluster_spans(cluster, c, tid)
    assert {s["node"] for s in spans} >= {c.name, pc.primary, slow_peer}
    tree = tracing.stitch(spans)
    nodes = [n for n in tracing.walk_dict(tree) if n is not tree]
    slowest = max(nodes, key=lambda n: n["self_ms"])
    assert slowest["name"] == f"prepare.{slow_peer}"
    assert slowest["node"] == pc.primary
    assert slowest["self_ms"] >= 450.0
    # the meta heard about it on config-sync (one-call `traces --slow`)
    cluster.step()
    rep = cluster.meta._trace_reports.get(pc.primary)
    assert rep and rep["kept"] >= 1
    assert any(r["trace"] == tid for r in rep["roots"])


# ---- batch fan-out / fan-in ----------------------------------------------


def test_read_batch_carrier_fans_out_per_op(cluster):
    cluster.create_table("t", partition_count=2, replica_count=3)
    c = cluster.client("t")
    for i in range(4):
        assert c.set(b"hk%d" % i, b"s", b"v%d" % i) == 0
    # group N=4 gets by their partitions (ops carry partition_hash)
    groups = {}
    for i in range(4):
        ph = key_hash_parts(b"hk%d" % i, b"s")
        pidx = ph % 2
        groups.setdefault(pidx, []).append(
            ("get", generate_key(b"hk%d" % i, b"s"), ph))
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    res = c.point_read_multi(groups)
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    assert all(r[0] == 0 for rs in res.values() for r in rs)
    tid = tracing.ring_for(c.name).dump()[-1]["trace"]
    spans = _cluster_spans(cluster, c, tid)
    carriers = [s for s in spans if s["name"] == "client_read_batch"]
    op_spans = [s for s in spans if s["name"].startswith("op.get.")]
    # N ops in the carriers fan out to N child spans — never N carriers
    # (one carrier per NODE, not per op; 3-replica spread over 3 nodes
    # means at most 2 distinct primaries for 2 partitions)
    assert 1 <= len(carriers) <= 2
    assert len(op_spans) == 4
    carrier_ids = {s["span"] for s in carriers}
    assert all(s["parent"] in carrier_ids for s in op_spans)


def test_write_batch_carrier_fans_out_per_op(cluster):
    cluster.create_table("t", partition_count=2, replica_count=3)
    c = cluster.client("t")
    from pegasus_tpu.base.value_schema import expire_ts_from_ttl
    from pegasus_tpu.rpc.codec import OP_PUT

    groups = {}
    for i in range(4):
        hk = b"wk%d" % i
        ph = key_hash_parts(hk, b"s")
        groups.setdefault(ph % 2, []).append(
            (OP_PUT, (generate_key(hk, b"s"), b"v",
                      expire_ts_from_ttl(0)), ph))
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    res = c.write_multi(groups)
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    assert all(r == 0 for rs in res.values() for r in rs)
    tid = tracing.ring_for(c.name).dump()[-1]["trace"]
    spans = _cluster_spans(cluster, c, tid)
    carriers = [s for s in spans if s["name"] == "client_write_batch"]
    op_spans = [s for s in spans if s["name"].startswith("op.write.")]
    assert 1 <= len(carriers) <= 2
    assert len(op_spans) == 4
    carrier_ids = {s["span"] for s in carriers}
    assert all(s["parent"] in carrier_ids for s in op_spans)
    # fan-in: the coalesced 2PC rounds also hang off the carriers, one
    # per combined run per partition — not one per op
    tpc = [s for s in spans if s["name"].startswith("2pc.")]
    assert 1 <= len(tpc) <= 2
    assert all(s["parent"] in carrier_ids for s in tpc)


# ---- ring bounds ----------------------------------------------------------


def test_ring_bounds_under_churn():
    FLAGS.set("pegasus.tracing", "ring_capacity", 64)
    clock = [0.0]
    ring = tracing.ring_for("churn", clock=lambda: clock[0])
    for i in range(500):
        sp = ring.start(f"op{i}")
        sp.finish()  # zero elapsed: never slow, never pinned
    assert len(ring.dump()) == 64
    assert ring.drop_count.value() == 436
    # a pinned trace SURVIVES churn
    slow = ring.start("slow-op")
    clock[0] += 1.0  # one virtual second: way past slow_trace_ms
    slow.finish()
    tid = slow.trace_id
    assert ring.is_kept(tid)
    for i in range(200):
        ring.start(f"more{i}").finish()
    assert [s["name"] for s in ring.dump(tid)] == ["slow-op"]
    # kept-trace store is bounded too
    FLAGS.set("pegasus.tracing", "kept_traces", 4)
    for i in range(8):
        sp = ring.start(f"slow{i}")
        clock[0] += 1.0
        sp.finish()
    assert len(ring.slow_roots(limit=100)) == 4


# ---- clock alignment ------------------------------------------------------


def test_stitch_aligns_skewed_clocks():
    """Two processes with clocks 5 s apart: the per-hop alignment lands
    the child inside its parent with a bounded skew estimate."""
    t = [1000.0]
    a = tracing.ring_for("A", clock=lambda: t[0])
    skew = 5.0
    b = tracing.ring_for("B", clock=lambda: t[0] + skew)
    parent = a.start("client.op")
    t[0] += 0.010  # request travels 10ms
    child = b.start("serve", parent_ctx=parent.ctx())
    t[0] += 0.050  # server works 50ms
    child.finish()
    t[0] += 0.010  # reply travels 10ms
    parent.finish()
    tree = tracing.stitch(a.dump() + b.dump())
    assert tree["name"] == "client.op"
    (ch,) = tree["children"]
    # aligned: child starts after parent, ends before it, despite the
    # raw clocks being 5s apart; skew bound covers the 10ms asymmetry
    assert 0.0 <= ch["rel_ms"] <= 20.0
    assert ch["skew_ms"] <= 11.0
    assert ch["rel_ms"] + ch["dur_ms"] <= tree["dur_ms"] + 1e-6


# ---- transport error counters --------------------------------------------


def test_transport_error_counters():
    from pegasus_tpu.rpc.transport import TcpTransport

    ent = METRICS.entity("rpc", "dispatch", {})
    d0 = ent.counter("dispatch_error_count").value()
    s0 = ent.counter("sender_error_count").value()
    server = TcpTransport(("127.0.0.1", 0), {})
    host, port = server.listen_addr

    def bad_handler(src, msg_type, payload):
        raise RuntimeError("boom")

    server.register("srv", bad_handler)
    client = TcpTransport(None, {"srv": (host, port),
                                 "ghost": ("127.0.0.1", 1)})
    try:
        client.send("cli", "srv", "poke", {"x": 1})
        deadline = time.monotonic() + 5.0
        while (ent.counter("dispatch_error_count").value() == d0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        # the dispatcher survived AND counted the handler failure
        assert ent.counter("dispatch_error_count").value() > d0
        # a dead peer counts sender errors instead of spamming stdout
        client.send("cli", "ghost", "poke", {"x": 2})
        deadline = time.monotonic() + 5.0
        while (ent.counter("sender_error_count").value() == s0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert ent.counter("sender_error_count").value() > s0
    finally:
        client.close()
        server.close()


# ---- prometheus exposition ------------------------------------------------


def test_prometheus_text_format():
    ent = MetricEntity("replica", "1.0", {"table": "1", "partition": "0"})
    ent.counter("read_cu").increment(42)
    ent.gauge("depth").set(3.5)
    p = ent.percentile("lat_ms")
    for v in range(100):
        p.set(float(v))
    text = to_prometheus([ent.snapshot()])
    lines = text.splitlines()
    assert "# TYPE pegasus_read_cu counter" in lines
    assert ('pegasus_read_cu{entity="replica",id="1.0",table="1",'
            'partition="0"} 42') in lines
    assert "# TYPE pegasus_depth gauge" in lines
    assert any(line.startswith("pegasus_lat_ms{") and
               'quantile="0.99"' in line for line in lines)
    # label escaping: quotes/newlines/backslashes never break the format
    weird = MetricEntity("x", 'a"b\nc\\d', {})
    weird.counter("c").increment()
    text2 = to_prometheus([weird.snapshot()])
    assert 'id="a\\"b\\nc\\\\d"' in text2


def test_prometheus_over_http():
    from pegasus_tpu.http.http_server import MetricsHttpServer

    METRICS.entity("tracing", "prom-node").counter(
        "kept_trace_count").increment(2)
    srv = MetricsHttpServer().start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics?format=prom"
                "&entity_type=tracing") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert "# TYPE pegasus_kept_trace_count counter" in body
        assert 'id="prom-node"' in body
        # JSON stays the default
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics"
                "?entity_type=tracing") as r:
            assert r.headers["Content-Type"] == "application/json"
            json.loads(r.read().decode())
    finally:
        srv.stop()


# ---- read-path slow-query stage chain ------------------------------------


def test_point_read_slow_log_stage_chain(tmp_path):
    from pegasus_tpu.server.partition_server import PartitionServer

    s = PartitionServer(str(tmp_path / "p0"))
    try:
        for i in range(20):
            s.on_put(generate_key(b"hk%02d" % i, b"s"), b"v%02d" % i)
        s.flush()
        s.slow_log.threshold_ms = 0.0  # everything is "slow"
        ops = [("get", generate_key(b"hk%02d" % i, b"s"), None)
               for i in range(8)]
        res = s.on_point_read_batch(ops)
        assert all(r[0] == 0 for r in res)
        dump = s.slow_log.dump()
        rep = dump[-1]
        assert rep["name"].startswith("point_get_batch.")
        stages = [st["stage"] for st in rep["stages"]]
        # the real chain: WHERE the read stalled, not just that it did
        for want in ("plan", "bloom", "block_probe", "decode", "finish"):
            assert want in stages, (want, stages)
        assert rep["ops"] == 8
    finally:
        s.close()


def test_scan_page_slow_log_stage_chain(tmp_path):
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.server.types import GetScannerRequest

    s = PartitionServer(str(tmp_path / "p0"))
    try:
        for i in range(50):
            s.on_put(generate_key(b"hk", b"s%03d" % i), b"v")
        s.flush()
        s.slow_log.threshold_ms = 0.0
        resp = s.on_get_scanner(GetScannerRequest(
            start_key=generate_key(b"hk", b""), stop_key=b"",
            batch_size=10))
        assert resp.error == 0 and resp.kvs
        rep = s.slow_log.dump()[-1]
        assert rep["name"].startswith("scan")
        stages = [st["stage"] for st in rep["stages"]]
        assert "plan" in stages and "finish" in stages or \
            "block_scan" in stages
    finally:
        s.close()


# ---- collector integration ------------------------------------------------


def test_collector_scrapes_latency_and_kept_traces(cluster):
    from pegasus_tpu.tools.collector import (
        DETECT_TABLE,
        STAT_TABLE,
        InfoCollector,
    )

    cluster.create_table(STAT_TABLE, partition_count=2)
    cluster.create_table(DETECT_TABLE, partition_count=2)
    cluster.create_table("traffic", partition_count=2)
    c = cluster.client("traffic")
    for i in range(10):
        assert c.set(b"k%d" % i, b"s", b"v" * 50) == 0
    groups = {}
    for i in range(10):
        ph = key_hash_parts(b"k%d" % i, b"s")
        groups.setdefault(ph % 2, []).append(
            ("get", generate_key(b"k%d" % i, b"s"), ph))
    res = c.point_read_multi(groups)
    assert all(r[0] == 0 for rs in res.values() for r in rs)
    # pin one slow trace on a node ring
    stub_name = next(iter(cluster.stubs))
    ring = tracing.ring_for(stub_name)
    sp = ring.start("slowread")
    sp.end = sp.start + 10.0
    ring.record(sp)
    assert ring.is_kept(sp.trace_id)
    col = InfoCollector(cluster.net, "collector", list(cluster.stubs),
                        cluster.client, cluster.pump)
    per_table = col.collect_round()
    app = per_table[str(c.app_id)]
    assert app["write_p99_ms"] > 0.0
    assert app["read_p99_ms"] > 0.0
    traces = col.collect_traces()
    assert traces.get(stub_name, 0) >= 1
