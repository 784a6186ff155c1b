"""Layer spans on the host's clock (utils/tracing.py): host durations
beside the ring clock's, self time per layer key on the metrics spine,
the profiler session as the switch, and the four always-on counts.

SimCluster, 4 partitions x 3 replicas, a few hundred rows flushed and
compacted to L1 (one block a partition), one record left in the
memtable of one partition.
"""

import time

import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.replica.replica import PartitionStatus
from pegasus_tpu.rpc.codec import OP_MULTI_PUT
from pegasus_tpu.server.types import (
    GetScannerRequest,
    KeyValue,
    MultiPutRequest,
)
from pegasus_tpu.tools.cluster import SimCluster
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS

PARTS = 4
RECORDS = 120
FIELDS = 10


def _hk(r: int) -> bytes:
    return b"user%08d" % r


def _pidx(r: int) -> int:
    return key_hash_parts(_hk(r)) % PARTS


def _put_groups(records):
    groups = {}
    for r in records:
        hk = _hk(r)
        ph = key_hash_parts(hk)
        groups.setdefault(ph % PARTS, []).append((
            OP_MULTI_PUT,
            MultiPutRequest(hk, [KeyValue(b"field%d" % j, b"v" * 100)
                                 for j in range(FIELDS)], 0), ph))
    return groups


def _scan_groups(records, rows=50):
    groups = {}
    for r in records:
        groups.setdefault(_pidx(r), []).append(GetScannerRequest(
            start_key=generate_key(_hk(r), b""), start_inclusive=True,
            batch_size=rows, validate_partition_hash=True, one_page=True))
    return groups


def _get_groups(records):
    groups = {}
    for r in records:
        ph = key_hash_parts(_hk(r), b"field3")
        groups.setdefault(ph % PARTS, []).append(
            ("get", generate_key(_hk(r), b"field3"), ph))
    return groups


def _counters(etype: str) -> dict:
    out = {}
    for ent in METRICS.snapshot(entity_type=etype):
        for name, m in ent["metrics"].items():
            if m["type"] == "counter":
                out[name] = out.get(name, 0) + m["value"]
    return out


def _delta(etype: str, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _counters(etype).items()
            if v != before.get(k, 0)}


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    tracing.reset()
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    c = SimCluster(str(tmp_path_factory.mktemp("layers")), n_nodes=3,
                   seed=5)
    c.create_table("t", partition_count=PARTS, replica_count=3)
    cl = c.client("t")
    for lo in range(0, RECORDS, 32):
        res = cl.write_multi(_put_groups(range(lo, min(RECORDS, lo + 32))))
        assert all(s == 0 for rs in res.values() for s in rs)
    for stub in c.stubs.values():
        for r in stub.replicas.values():
            r.server.flush()
            r.server.manual_compact()
    # warm every path once, untraced
    cl.scan_multi(_scan_groups(range(0, 32)))
    cl.point_read_multi(_get_groups(range(0, 32)))
    yield c, cl
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    c.close()
    tracing.reset()


@pytest.fixture
def sampled():
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    yield
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)


def _last_tree(cl):
    tid = tracing.ring_for(cl.name).dump()[-1]["trace"]
    spans = tracing.dump_all(tid)
    return spans, tracing.stitch(spans)


def test_root_host_duration_is_wall_time_ring_duration_is_sim_time(
        loaded, sampled):
    c, cl = loaded
    groups = _scan_groups(range(0, 32))
    sim0 = c.loop.now
    t0 = time.perf_counter()
    cl.scan_multi(groups)
    wall_ms = (time.perf_counter() - t0) * 1000.0
    sim_ms = (c.loop.now - sim0) * 1000.0
    spans, tree = _last_tree(cl)
    assert tree["name"] == "client.scan_multi"
    assert abs(tree["host_ms"] - wall_ms) <= 0.10 * wall_ms
    # the ring's clock is still the sim's: the virtual link delays, not
    # what the host burned
    assert abs(tree["dur_ms"] - sim_ms) < 1e-3
    # the Motivation's experiment: the server spans are no longer
    # 0.000 ms, and with the client's own self time they add up to
    # the call
    servers = [s for s in spans if s["name"] == "client_scan_multi"]
    assert servers and all(s["host_ms"] > 0 for s in servers)
    assert all(s["end"] == s["start"] for s in servers)  # sim time
    total_self = sum(s["host_self_ms"] for s in spans)
    assert abs(total_self - wall_ms) <= 0.10 * wall_ms
    text = tracing.render(tree)
    assert "host " in text and "stages:" in text


@pytest.mark.parametrize("call", ["scan_multi", "point_read_multi",
                                  "write_multi"])
def test_layer_self_times_sum_to_traced_and_none_is_other(
        loaded, sampled, call):
    c, cl = loaded
    before = _counters("layer")
    n0 = _counters("tracing").get("span_count", 0)
    if call == "scan_multi":
        cl.scan_multi(_scan_groups(range(0, 32)))
    elif call == "point_read_multi":
        cl.point_read_multi(_get_groups(range(0, 32)))
    else:
        res = cl.write_multi(_put_groups(range(1000, 1004)))
        assert all(s == 0 for rs in res.values() for s in rs)
    d = _delta("layer", before)
    n_spans = _counters("tracing")["span_count"] - n0
    traced = d.pop("traced_us")
    assert traced > 0 and n_spans > 0
    # one thread, so every nanosecond of the root is some frame's or
    # some stage's self time: whole microseconds differ by the
    # remainders the counters hold back
    assert abs(sum(d.values()) - traced) <= n_spans
    assert d.get("other_self_us", 0) == 0
    want = {"scan_multi": {"client", "rpc", "gate", "coord"},
            "point_read_multi": {"client", "rpc", "gate", "coord",
                                 "index", "decode"},
            "write_multi": {"client", "rpc", "gate", "repl"}}[call]
    assert want <= {k[:-len("_self_us")] for k in d}, d


def test_untraced_calls_record_nothing(loaded):
    c, cl = loaded
    assert not tracing.profiling()
    layer0 = _counters("layer")
    spans0 = _counters("tracing").get("span_count", 0)
    scans, gets = _scan_groups(range(0, 8)), _get_groups(range(0, 8))
    for i in range(50):
        cl.scan_multi(scans)
        cl.point_read_multi(gets)
    assert _counters("tracing").get("span_count", 0) == spans0
    assert _counters("layer") == layer0
    # and the scopes themselves are the one shared null object
    assert tracing.layer("gate.read") is tracing.layer("coord.route")


def test_profiler_session_is_the_switch(loaded, tmp_path):
    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    from benchmarks.trace_reduce import load_xplane

    c, cl = loaded
    assert FLAGS.get("pegasus.tracing", "sample_ratio") == 0.0
    spans0 = _counters("tracing").get("span_count", 0)
    layer0 = _counters("layer")
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        assert tracing.profiling()
        with TraceAnnotation("test.enclosing"):
            cl.scan_multi(_scan_groups(range(0, 8)))
            res = cl.write_multi(_put_groups(range(2000, 2002)))
    finally:
        jax.profiler.stop_trace()
    assert all(s == 0 for rs in res.values() for s in rs)
    assert not tracing.profiling()
    assert _counters("tracing")["span_count"] > spans0
    assert _delta("layer", layer0)["traced_us"] > 0
    # outside the session again: nothing
    spans1 = _counters("tracing")["span_count"]
    cl.scan_multi(_scan_groups(range(0, 8)))
    assert _counters("tracing")["span_count"] == spans1
    # the program's spans sit in the profile, inside the test's own
    events = [(n, s, s + d) for _pn, lines in load_xplane(trace_dir)
              for _ln, evs in lines for n, s, d in evs]
    (_n, lo, hi), = [e for e in events if e[0] == "test.enclosing"]
    ours = [e for e in events if e[0].startswith("pegasus.")]
    names = {e[0] for e in ours}
    assert {"pegasus.client.scan_multi", "pegasus.client.write_multi",
            "pegasus.rpc.deliver", "pegasus.client_scan_multi",
            "pegasus.coord.route", "pegasus.gate.read",
            "pegasus.gate.write", "pegasus.repl.window_flush",
            "pegasus.stage"} <= names, names
    assert all(lo <= s and e <= hi for _n, s, e in ours)


def test_counts_move_by_hand_computed_amounts(loaded):
    c, cl = loaded
    # earlier tests may have left rows in memtables: settle them into L1
    for stub in c.stubs.values():
        for rep in stub.replicas.values():
            rep.server.flush()
            rep.server.manual_compact()
    rows_l1 = {}                    # on the primaries, which serve
    for stub in c.stubs.values():
        for (_app, pidx), rep in stub.replicas.items():
            if rep.status != PartitionStatus.PRIMARY:
                continue
            runs = rep.server.engine.lsm.l1_runs
            assert len(runs) == 1 and len(runs[0].blocks) == 1
            rows_l1[pidx] = runs[0].total_count
    p = _pidx(0)
    q = next(x for x in range(PARTS) if x != p)
    rec_q = next(r for r in range(RECORDS) if _pidx(r) == q)
    new = next(r for r in range(5000, 6000) if _pidx(r) == p)

    # one insert of one 10-row record into partition p: three replicas
    # each stage one mutation in one group-commit window and hand their
    # private log to the OS once
    w0 = _counters("write")
    res = cl.write_multi(_put_groups([new]))
    assert res == {p: [0]}
    dw = _delta("write", w0)
    assert dw["plog_flush_count"] == 3
    assert dw["group_commit_windows"] == 3
    assert dw["group_commit_mutations"] == 3

    # a second new record after it in p: the primary's memtable holds
    # 20 rows, both records past every row of p's one block. In one
    # flush and one flavor: two scans of 5 rows from the later record
    # (no block planned, so no frontier: their range is the record's 10
    # rows, evaluated once for the two of them, and both merge), one of
    # 5 rows from record 0 (its plan holds the whole block, more than
    # 2 * 5 + 64 rows, so its range ends at the block's last key, before
    # either new record: nothing evaluated, no merge) and one of 7 rows
    # in q (no overlay). Every row of each partition's one block is
    # examined once per partition; 5 + 5 + 5 + 7 rows returned
    assert rows_l1[p] >= 2 * 5 + 64
    later = next(r for r in range(new + 1, 6000) if _pidx(r) == p)
    assert cl.write_multi(_put_groups([later])) == {p: [0]}
    s0 = _counters("storage")
    out = cl.scan_multi({p: _scan_groups([later, later, 0], rows=5)[p],
                         q: _scan_groups([rec_q], rows=7)[q]})
    assert [len(r.kvs) for r in out[p]] == [5, 5, 5]
    assert [len(r.kvs) for r in out[q]] == [7]
    ds = _delta("storage", s0)
    assert ds["overlay_rows_walked"] == FIELDS
    assert ds["scan_merge_path_requests"] == 2
    assert ds["scan_rows_evaluated"] == rows_l1[p] + rows_l1[q]
    assert ds["scan_rows_returned"] == 22
