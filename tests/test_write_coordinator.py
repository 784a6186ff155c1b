"""Batched write hot path tests: cross-partition write coalescing
(client_write_batch / write_multi), node-level plog group commit,
prepare fan-out aggregation, the vectorized apply translate, and the
shared framed-log codec.

The load-bearing regressions: batched writes must leave state (and
per-op results) identical to the solo handlers, and the group-commit
window must never release an ack before its mutations are durable —
a crash mid-window loses only writes nobody was acked for.
"""

import os

import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.replica import (
    Mutation,
    MutationLog,
    Replica,
    ReplicaBusyError,
    ReplicaConfig,
    WriteFlushWindow,
    WriteOp,
)
from pegasus_tpu.rpc.codec import (
    OP_INCR,
    OP_MULTI_PUT,
    OP_MULTI_REMOVE,
    OP_PUT,
    OP_REMOVE,
)
from pegasus_tpu.runtime import SimLoop, SimNetwork
from pegasus_tpu.server.types import (
    IncrRequest,
    KeyValue,
    MultiPutRequest,
    MultiRemoveRequest,
)
from pegasus_tpu.storage.framed_log import (
    iter_frames,
    pack_frame,
    scan_valid_end,
)
from pegasus_tpu.utils.errors import ErrorCode
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS

OK = int(ErrorCode.ERR_OK)


def k(h, s=b""):
    return generate_key(h, s)


def mk_mu(decree, ballot=1, ts=None):
    return Mutation(ballot=ballot, decree=decree,
                    last_committed=decree - 1,
                    timestamp_us=ts or (1_000_000 + decree),
                    ops=[WriteOp(OP_PUT,
                                 (k(b"h%d" % decree, b"s"),
                                  b"v%d" % decree, 0))])


# ---- shared framed-log codec -----------------------------------------


def test_framed_log_roundtrip_and_torn_tail():
    payloads = [b"alpha", b"", b"x" * 1000]
    data = b"".join(pack_frame(p) for p in payloads)
    assert [p for p, _e in iter_frames(data)] == payloads
    assert scan_valid_end(data) is None  # fully valid
    # torn tail: a partial frame stops iteration at the boundary
    torn = data + pack_frame(b"tail")[:-3]
    assert [p for p, _e in iter_frames(torn)] == payloads
    assert scan_valid_end(torn) == len(data)
    # corrupt crc: frames past it are unreachable by contract
    corrupt = bytearray(data)
    corrupt[10] ^= 0xFF
    assert [p for p, _e in iter_frames(bytes(corrupt))] == []
    assert scan_valid_end(bytes(corrupt)) == 0


def test_mutation_log_append_batch_matches_solo(tmp_path):
    solo = MutationLog(str(tmp_path / "solo" / "m.bin"))
    batch = MutationLog(str(tmp_path / "batch" / "m.bin"))
    mus = [mk_mu(d) for d in (1, 2, 3)]
    for mu in mus:
        solo.append(mu)
    batch.append_batch(mus)
    solo.close()
    batch.close()
    with open(solo.path, "rb") as f:
        a = f.read()
    with open(batch.path, "rb") as f:
        b = f.read()
    assert a == b
    assert batch.max_decree == 3
    assert [m.decree for m in MutationLog.replay(batch.path)] == [1, 2, 3]


def test_buffered_append_visible_to_readers(tmp_path):
    """read_range/read_tail flush the append buffer first — duplication
    tailing must never miss a window's staged frames."""
    log = MutationLog(str(tmp_path / "m.bin"))
    log.append(mk_mu(1), flush=False)
    assert [m.decree for m in log.read_range(1)] == [1]
    log.append(mk_mu(2), flush=False)
    tail = log.read_tail(0)
    assert [m.decree for m, _off in tail] == [1, 2]
    log.close()


# ---- group commit: durability contract --------------------------------


def test_crash_mid_group_commit_window_loses_only_unacked(tmp_path):
    """Acked (post-commit_window) mutations survive a crash; mutations
    staged in an uncommitted window — whose acks were still deferred —
    may be lost, and recovery still sees a clean prefix."""
    path = str(tmp_path / "m.bin")
    log = MutationLog(path)
    for d in (1, 2, 3):
        log.append(mk_mu(d), flush=False)
    log.commit_window(sync=True)  # window 1 hardened: acks released
    for d in (4, 5):
        log.append(mk_mu(d), flush=False)  # window 2 never commits
    # crash: the on-disk bytes are all a dead process leaves (the
    # buffered tail lived in its userspace buffer)
    with open(path, "rb") as f:
        disk = f.read()
    crash = str(tmp_path / "crash.bin")
    # plus half a frame: a torn tail from a kill mid-write
    with open(crash, "wb") as f:
        f.write(disk + pack_frame(mk_mu(6).encode())[:-4])
    recovered = MutationLog(crash)
    assert [m.decree for m in recovered.replay(crash)] == [1, 2, 3]
    # the torn tail was truncated: appends after recovery are reachable
    recovered.append(mk_mu(7))
    assert [m.decree for m in recovered.replay(crash)] == [1, 2, 3, 7]
    recovered.close()
    log._f = open(os.devnull, "ab")  # drop the dead buffer for teardown


def test_ack_released_only_after_window_commit(tmp_path):
    """The appended-before-acked contract under group commit: the
    client callback (and the decree-ready path behind it) runs only
    after commit_window hardened the plog."""
    loop = SimLoop(seed=0)
    net = SimNetwork(loop)
    r = Replica("r1", str(tmp_path / "r1"), net,
                clock=lambda: 1_700_000_000 + loop.now)
    net.register("r1", r.on_message)
    r.assign_config(ReplicaConfig(1, "r1", []))
    window = WriteFlushWindow(net, "r1",
                              METRICS.entity("write", "test-ack"))
    r.plog_sink = window

    events = []
    orig_commit = r.log.commit_window
    r.log.commit_window = lambda sync=False: (
        events.append("commit"), orig_commit(sync))[1]
    with window:
        r.client_write([WriteOp(OP_PUT, (k(b"h", b"s"), b"v", 0))],
                       lambda res: events.append("ack"))
        events.append("staged")
    assert events == ["staged", "commit", "ack"]
    # and outside a window the legacy immediate path still acks inline
    events.clear()
    r.client_write([WriteOp(OP_PUT, (k(b"h", b"s2"), b"v2", 0))],
                   lambda res: events.append("ack"))
    assert events == ["ack"]
    r.close()


def test_group_commit_fsync_amortized(tmp_path):
    """fsync mode: one shared fsync per dirty log per window — a
    64-op write_multi costs ~#partitions fsyncs, while the same ops
    solo cost one window (and one fsync) each."""
    from pegasus_tpu.tools.cluster import SimCluster

    FLAGS.set("pegasus.replica", "plog_sync_mode", "fsync")
    try:
        c = SimCluster(str(tmp_path), n_nodes=1)
        c.create_table("t", partition_count=4, replica_count=1)
        cl = c.client("t")
        cl.refresh_config()
        fsyncs = METRICS.entity("write", "node0").counter(
            "plog_fsync_count")
        groups = {}
        for i in range(64):
            hk, sk = b"hk%03d" % i, b"s"
            ph = key_hash_parts(hk, sk)
            groups.setdefault(ph % 4, []).append(
                (OP_PUT, (k(hk, sk), b"v%d" % i, 0), ph))
        before = fsyncs.value()
        res = cl.write_multi(groups)
        batched_cost = fsyncs.value() - before
        assert all(r == 0 for rs in res.values() for r in rs)
        # one batch message -> one window -> at most one fsync per
        # partition log touched (4), plus one follow-up pass if a
        # queued run drained — nowhere near one per op
        assert batched_cost <= 8, batched_cost
        before = fsyncs.value()
        for i in range(16):
            cl.set(b"solo%03d" % i, b"s", b"v")
        solo_cost = fsyncs.value() - before
        assert solo_cost >= 16  # one window (>= one fsync) per solo op
        c.close()
    finally:
        FLAGS.set("pegasus.replica", "plog_sync_mode", "flush")


def test_restart_recovers_acked_writes_with_stale_engine_wal(tmp_path):
    """Under a window the engine-WAL frame may ride the IO buffer
    (never flushed); an acked write must STILL survive a crash because
    the plog hardened before the ack and boot replay + the reprepare
    path recommit it."""
    import shutil

    from pegasus_tpu.server.types import MultiGetRequest  # noqa: F401

    loop = SimLoop(seed=0)
    net = SimNetwork(loop)
    rdir = tmp_path / "r1"
    r = Replica("r1", str(rdir), net,
                clock=lambda: 1_700_000_000 + loop.now)
    net.register("r1", r.on_message)
    r.assign_config(ReplicaConfig(1, "r1", []))
    window = WriteFlushWindow(net, "r1",
                              METRICS.entity("write", "test-crash"))
    r.plog_sink = window
    acked = []
    with window:
        for i in range(8):
            r.client_write(
                [WriteOp(OP_PUT, (k(b"h%d" % i, b"s"), b"v%d" % i, 0))],
                lambda res, i=i: acked.append(i))
    assert acked == list(range(8))
    # crash: only on-disk bytes survive (the engine WAL's frames are
    # still in the dead process's buffer; the plog was flushed by the
    # window before the acks)
    crash_dir = tmp_path / "crash"
    shutil.copytree(rdir, crash_dir)
    r2 = Replica("r1", str(crash_dir), net,
                 clock=lambda: 1_700_000_000 + loop.now)
    # the engine alone is BEHIND (stale WAL)...
    assert r2.server.engine.last_committed_decree < 8
    # ...but the plog replay re-prepared the tail, and the promotion
    # reprepare recommits it before the replica may serve reads
    r2.assign_config(ReplicaConfig(2, "r1", []))
    assert r2.ready_to_serve()
    assert r2.last_committed_decree == 8
    for i in range(8):
        err, v = r2.server.on_get(k(b"h%d" % i, b"s"))
        assert (err, v) == (0, b"v%d" % i)
    r2.close()
    r.close()


# ---- typed overload ---------------------------------------------------


def test_write_queue_overload_raises_typed_busy(tmp_path):
    """Behind an in-flight window an atomic op queues as a mutation of
    its own; a full queue of either kind raises ReplicaBusyError (stub
    maps it to ERR_BUSY — retryable)."""
    loop = SimLoop(seed=0)
    net = SimNetwork(loop)
    r = Replica("r1", str(tmp_path / "r1"), net,
                clock=lambda: 1_700_000_000 + loop.now)
    net.register("r1", r.on_message)
    # ghost secondaries: prepares go nowhere, acks never arrive, the
    # pipeline stays in flight
    r.assign_config(ReplicaConfig(1, "r1", ["ghost1", "ghost2"]))
    for i in range(r.PIPELINE_DEPTH):
        assert r.client_write(
            [WriteOp(OP_PUT, (k(b"h%d" % i, b"s"), b"v", 0))]) > 0
    # an atomic op waits behind the in-flight round, not refused
    assert r.client_write([WriteOp(OP_INCR,
                                   IncrRequest(k(b"c", b"s"), 1, 0))]) == -1
    # batchable ops coalesce until the queue cap, then typed busy
    batch = [WriteOp(OP_PUT, (k(b"q", b"s%03d" % i), b"v", 0))
             for i in range(r.MAX_BATCH_OPS)]
    assert r.client_write(batch) == -1
    with pytest.raises(ReplicaBusyError):
        r.client_write([WriteOp(OP_PUT, (k(b"q2", b"s"), b"v", 0))])
    # atomic ops queue up to the same cap, then typed busy
    for _ in range(r.MAX_BATCH_OPS - 1):
        assert r.client_write([WriteOp(
            OP_INCR, IncrRequest(k(b"c", b"s"), 1, 0))]) == -1
    with pytest.raises(ReplicaBusyError):
        r.client_write([WriteOp(OP_INCR,
                                IncrRequest(k(b"c", b"s"), 1, 0))])
    r.close()


def test_stub_maps_busy_to_err_busy(tmp_path):
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=1)
    app_id = c.create_table("t", partition_count=1, replica_count=1)
    stub = c.stubs["node0"]
    r = stub.get_replica((app_id, 0))
    orig = r.client_write
    r.client_write = lambda *a, **kw: (_ for _ in ()).throw(
        ReplicaBusyError("full"))
    replies = []
    c.net.register("probe", lambda s, mt, p: replies.append(p))
    c.net.send("probe", "node0", "client_write", {
        "gpid": (app_id, 0), "rid": 1, "auth": None,
        "ops": [(OP_PUT, (k(b"h", b"s"), b"v", 0))],
        "partition_hash": None})
    c.loop.run_until_idle()
    assert replies and replies[0]["err"] == int(ErrorCode.ERR_BUSY)
    r.client_write = orig
    c.close()


# ---- client_write_batch RPC ------------------------------------------


def _batch_write_reply(cluster, payload):
    replies = []
    cluster.net.register("probe",
                         lambda s, mt, p: replies.append((mt, p)))
    cluster.net.send("probe", "node0", "client_write_batch", payload)
    cluster.loop.run_until_idle()
    assert replies, "no reply to client_write_batch"
    return replies[-1][1]


def test_per_op_deadline_inside_write_batch(tmp_path):
    """An expired per-op deadline fast-fails THAT op with a typed
    ERR_TIMEOUT before its 2PC starts; its window neighbors commit."""
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=1)
    app_id = c.create_table("t", partition_count=1, replica_count=1)
    cl = c.client("t")
    cl.refresh_config()
    stub = c.stubs["node0"]
    now = stub.clock()
    key_dead, key_live = k(b"dead", b"s"), k(b"live", b"s")
    reply = _batch_write_reply(c, {
        "rid": 7, "auth": None, "groups": [((app_id, 0), [
            ([(OP_PUT, (key_dead, b"x", 0))], None, now - 5.0),
            ([(OP_PUT, (key_live, b"y", 0))], None, now + 60.0),
        ])]})
    assert reply["err"] == OK
    (pidx, err, items) = reply["result"][0]
    assert (pidx, err) == (0, OK)
    assert items[0] == (int(ErrorCode.ERR_TIMEOUT), [])
    assert items[1] == (OK, [0])
    err, _v = cl.get(b"dead", b"s")
    assert err != 0  # the expired op never ran
    err, v = cl.get(b"live", b"s")
    assert (err, v) == (0, b"y")
    c.close()


def test_write_batch_partition_gate_failures_in_slot(tmp_path):
    """A stale/unhosted partition fails in ITS slot; hosted slots in
    the same message still serve."""
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=1)
    app_id = c.create_table("t", partition_count=1, replica_count=1)
    reply = _batch_write_reply(c, {
        "rid": 9, "auth": None, "groups": [
            ((app_id, 0), [([(OP_PUT, (k(b"a", b"s"), b"v", 0))],
                            None, None)]),
            ((app_id + 7, 0), [([(OP_PUT, (k(b"b", b"s"), b"v", 0))],
                                None, None)]),
        ]})
    assert reply["err"] == OK
    slots = reply["result"]
    assert slots[0][1] == OK and slots[0][2][0] == (OK, [0])
    assert slots[1][1] == int(ErrorCode.ERR_INVALID_STATE)
    assert slots[1][2] is None
    c.close()


# ---- batched vs solo identity ----------------------------------------


def _mixed_workload(n=24):
    """(tag, args) ops covering the full batchable mix + an atomic
    interleaved mid-stream."""
    ops = []
    for i in range(n):
        hk = b"user%04d" % (i // 3)
        ops.append(("set", (hk, b"s%02d" % i, b"val-%d" % i)))
        if i % 5 == 0:
            ops.append(("multi_set",
                        (hk, [(b"m0-%d" % i, b"mv0"),
                              (b"m1-%d" % i, b"mv1")])))
        if i % 7 == 3:
            ops.append(("del", (hk, b"s%02d" % (i - 1),)))
        if i == n // 2:
            ops.append(("incr", (b"counter", b"c", 11)))
        if i % 9 == 4:
            ops.append(("multi_del", (hk, [b"m0-%d" % (i - 4)])))
    return ops


def _run_solo(cl, ops):
    results = []
    for tag, args in ops:
        if tag == "set":
            results.append(cl.set(*args))
        elif tag == "multi_set":
            results.append(cl.multi_set(args[0], args[1]))
        elif tag == "del":
            results.append(cl.delete(*args))
        elif tag == "incr":
            resp = cl.incr(*args)
            results.append((resp.error, resp.new_value))
        elif tag == "multi_del":
            results.append(tuple(cl.multi_del(args[0], args[1])))
    return results


def _run_batched(cl, ops, batch=16):
    """The same logical ops through write_multi, `batch` per flush,
    preserving submission order inside each partition."""
    results = []
    pending = {}
    pending_order = []
    pending_n = 0

    def flush():
        nonlocal pending_n
        if not pending:
            return
        got = cl.write_multi({p: [op for op, _tag in lst]
                              for p, lst in pending.items()})
        for p, i in pending_order:
            res = got[p][i]
            tag = pending[p][i][1]
            if tag == "incr":
                results.append((res.error, res.new_value))
            elif tag == "multi_del":
                results.append(tuple(res))
            else:
                results.append(res)
        pending.clear()
        pending_order.clear()
        pending_n = 0

    for tag, args in ops:
        if tag == "set":
            hk, sk, v = args
            ph = key_hash_parts(hk, sk)
            op = (OP_PUT, (generate_key(hk, sk), v, 0), ph)
        elif tag == "multi_set":
            hk, kvs = args
            ph = key_hash_parts(hk)
            op = (OP_MULTI_PUT,
                  MultiPutRequest(hk, [KeyValue(a, b) for a, b in kvs],
                                  0), ph)
        elif tag == "del":
            hk, sk = args
            ph = key_hash_parts(hk, sk)
            op = (OP_REMOVE, (generate_key(hk, sk),), ph)
        elif tag == "incr":
            hk, sk, by = args
            ph = key_hash_parts(hk, sk)
            op = (OP_INCR, IncrRequest(generate_key(hk, sk), by, 0), ph)
        elif tag == "multi_del":
            hk, sks = args
            ph = key_hash_parts(hk)
            op = (OP_MULTI_REMOVE, MultiRemoveRequest(hk, list(sks)), ph)
        pidx = ph % cl.partition_count
        lst = pending.setdefault(pidx, [])
        pending_order.append((pidx, len(lst)))
        lst.append((op, tag))
        pending_n += 1
        if pending_n >= batch:
            flush()
    flush()
    return results


def _state_of(cl, ops):
    """Read back every key either path touched: (err, value) pairs."""
    keys = set()
    for tag, args in ops:
        if tag in ("set", "del"):
            keys.add((args[0], args[1]))
        elif tag == "incr":
            keys.add((args[0], args[1]))
        elif tag == "multi_set":
            keys.update((args[0], sk) for sk, _v in args[1])
        elif tag == "multi_del":
            keys.update((args[0], sk) for sk in args[1])
    return {hk + b"|" + sk: cl.get(hk, sk) for hk, sk in sorted(keys)}


def test_write_multi_identity_with_solo_across_op_mix(tmp_path):
    """Full-mix identity: per-op results AND resulting user-visible
    state of the batched path match the solo handlers exactly (two
    tables on one cluster, same logical workload)."""
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=2)
    c.create_table("solo", partition_count=4, replica_count=2)
    c.create_table("batch", partition_count=4, replica_count=2)
    cl_solo = c.client("solo", name="cs")
    cl_batch = c.client("batch", name="cb")
    cl_solo.refresh_config()
    cl_batch.refresh_config()
    ops = _mixed_workload()
    res_solo = _run_solo(cl_solo, ops)
    res_batch = _run_batched(cl_batch, ops)
    assert res_batch == res_solo
    assert _state_of(cl_batch, ops) == _state_of(cl_solo, ops)
    c.close()


def test_translate_put_run_byte_identical(tmp_path):
    """The vectorized apply's run translate emits byte-identical
    engine items to translate_put/translate_remove called per op."""
    from pegasus_tpu.server.partition_server import PartitionServer

    s = PartitionServer(str(tmp_path / "p0"))
    ws = s.write_service
    ts = 1_234_567_890_123_456
    reqs = [(k(b"h%d" % i, b"s"), b"v%d" % i, i % 3) for i in range(40)]
    run = ws.translate_put_run(reqs, ts)
    solo = [it for key, ud, ets in reqs
            for it in ws.translate_put(key, ud, ets, ts)]
    assert [(it.op, it.key, it.value, it.expire_ts) for it in run] == \
        [(it.op, it.key, it.value, it.expire_ts) for it in solo]
    keys = [key for key, _ud, _ets in reqs]
    run_rm = ws.translate_remove_run(keys)
    solo_rm = [it for key in keys for it in ws.translate_remove(key)]
    assert [(it.op, it.key, it.value, it.expire_ts) for it in run_rm] \
        == [(it.op, it.key, it.value, it.expire_ts) for it in solo_rm]
    s.close()


# ---- prepare fan-out aggregation -------------------------------------


def test_prepare_batch_aggregation_on_secondary_path(tmp_path):
    """A multi-partition write flush to a replicated table collapses
    its per-partition prepares into prepare_batch messages (and the
    acks into prepare_batch_ack) — and every write still commits."""
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=3)
    c.create_table("t", partition_count=8, replica_count=3)
    cl = c.client("t")
    cl.refresh_config()
    seen = []
    orig_send = c.net.send

    def spy(src, dst, msg_type, payload):
        if msg_type in ("prepare_batch", "prepare_batch_ack"):
            seen.append((msg_type, len(payload["items"])))
        return orig_send(src, dst, msg_type, payload)

    c.net.send = spy
    groups = {}
    for i in range(96):
        hk, sk = b"hk%04d" % i, b"s"
        ph = key_hash_parts(hk, sk)
        groups.setdefault(ph % 8, []).append(
            (OP_PUT, (k(hk, sk), b"v%d" % i, 0), ph))
    res = cl.write_multi(groups)
    c.net.send = orig_send
    assert all(r == 0 for rs in res.values() for r in rs)
    batched = [n for mt, n in seen if mt == "prepare_batch"]
    assert batched and max(batched) > 1, seen
    acks = [n for mt, n in seen if mt == "prepare_batch_ack"]
    assert acks and max(acks) > 1, seen
    for i in range(0, 96, 7):
        err, v = cl.get(b"hk%04d" % i, b"s")
        assert (err, v) == (0, b"v%d" % i)
    # the observability surface recorded the aggregation
    snap = {s["id"]: s["metrics"]
            for s in METRICS.snapshot("write")}
    sizes = [m.get("prepare_batch_size") for m in snap.values()
             if m.get("prepare_batch_size")]
    assert sizes
    c.close()


def test_pipeline_queue_depth_metric_sampled(tmp_path):
    from pegasus_tpu.tools.cluster import SimCluster

    c = SimCluster(str(tmp_path), n_nodes=1)
    c.create_table("t", partition_count=1, replica_count=1)
    cl = c.client("t")
    cl.refresh_config()
    cl.set(b"hk", b"s", b"v")
    snap = {s["id"]: s["metrics"] for s in METRICS.snapshot("write")}
    assert "pipeline_queue_depth" in snap["node0"]
    c.close()
