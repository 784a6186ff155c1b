"""Wire codec + TCP transport + multi-process onebox tests.

Parity: the message-header framing contract (rpc/rpc_message.h:81-126),
and the reference's function-test-against-onebox tier (SURVEY §4.3).
"""

import shutil
import time

import pytest

from pegasus_tpu.rpc.message import decode_message, encode_message, read_frames
from pegasus_tpu.server.types import (
    GetScannerRequest,
    IncrRequest,
    KeyValue,
    MultiGetRequest,
    MultiGetResponse,
    MultiPutRequest,
)


def roundtrip(payload):
    frame = encode_message("a", "b", "t", payload)
    buf = bytearray(frame)
    bodies = read_frames(buf)
    assert len(bodies) == 1 and not buf
    src, dst, mt, out = decode_message(bodies[0])
    assert (src, dst, mt) == ("a", "b", "t")
    return out


def test_message_roundtrip_primitives():
    for v in (None, True, False, 0, -1, 2**40, -(2**40), 2**63,
              0xFFFFFFFFFFFFFFFF, 2**100, -(2**100), 3.5, b"", b"bytes",
              "str", [1, [2, 3]], (4, (5,)), {"k": b"v", 1: None}):
        out = roundtrip(v)
        assert out == v and type(out) is type(v)


def test_message_roundtrip_dataclasses():
    req = MultiPutRequest(b"hk", [KeyValue(b"a", b"1")], 60)
    out = roundtrip({"ops": [(3, req)]})
    assert out["ops"][0][1] == req
    resp = MultiGetResponse(error=0, kvs=[KeyValue(b"x", b"y")])
    assert roundtrip(resp) == resp
    assert roundtrip(IncrRequest(b"k", -5, 0)) == IncrRequest(b"k", -5, 0)
    scan = GetScannerRequest(start_key=b"s", batch_size=7, full_scan=True)
    assert roundtrip(scan) == scan
    assert roundtrip(MultiGetRequest(b"hk", sort_keys=[b"a"])) == \
        MultiGetRequest(b"hk", sort_keys=[b"a"])


def test_partial_frames_reassemble():
    frame = encode_message("x", "y", "z", {"big": b"A" * 10_000})
    buf = bytearray()
    out = []
    for i in range(0, len(frame), 997):
        buf.extend(frame[i:i + 997])
        out.extend(read_frames(buf))
    assert len(out) == 1
    assert decode_message(out[0])[3] == {"big": b"A" * 10_000}


def test_corrupt_frame_raises():
    frame = bytearray(encode_message("x", "y", "z", b"payload"))
    frame[-1] ^= 0xFF
    with pytest.raises(ValueError):
        read_frames(frame)


def test_tcp_transport_request_reply():
    from pegasus_tpu.rpc.transport import TcpTransport

    server = TcpTransport(("127.0.0.1", 0), {})
    host, port = server.listen_addr
    client = TcpTransport(None, {"srv": (host, port)})
    got = []

    def srv_handler(src, msg_type, payload):
        got.append((src, msg_type, payload))
        # reply rides the learned inbound route — the client listens on
        # nothing
        server.send("srv", src, "pong", payload["n"] + 1)

    replies = []
    server.register("srv", srv_handler)
    client.register("cli", lambda s, mt, p: replies.append((s, mt, p)))
    client.send("cli", "srv", "ping", {"n": 41})
    deadline = time.monotonic() + 5
    while not replies and time.monotonic() < deadline:
        time.sleep(0.01)
    assert got == [("cli", "ping", {"n": 41})]
    assert replies == [("srv", "pong", 42)]
    client.close()
    server.close()


def _pair():
    """A listening server transport + client transport dialing it."""
    from pegasus_tpu.rpc.transport import TcpTransport

    server = TcpTransport(("127.0.0.1", 0), {})
    host, port = server.listen_addr
    client = TcpTransport(None, {"srv": (host, port)})
    return server, client


def _wait_for(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)
    return pred()


def test_dispatcher_fast_fails_expired_deadline():
    """A client request whose end-to-end deadline lapsed in flight is
    never served: the dispatcher answers typed ERR_TIMEOUT without
    touching the handler (abandoned work sheds itself)."""
    from pegasus_tpu.utils.errors import ErrorCode

    server, client = _pair()
    served, replies = [], []
    try:
        server.register("srv", lambda s, mt, p: served.append(p))
        client.register("cli", lambda s, mt, p: replies.append((mt, p)))
        client.send("cli", "srv", "client_read", {
            "rid": 7, "gpid": (1, 0), "op": "get", "args": b"k",
            "deadline": time.time() - 1.0})
        assert _wait_for(lambda: replies)
        mt, p = replies[0]
        assert mt == "client_read_reply"
        assert p == {"rid": 7, "err": int(ErrorCode.ERR_TIMEOUT),
                     "result": None}
        assert served == []
        # an unexpired deadline passes straight through to the handler
        client.send("cli", "srv", "client_read", {
            "rid": 8, "gpid": (1, 0), "op": "get", "args": b"k",
            "deadline": time.time() + 30.0})
        assert _wait_for(lambda: served)
    finally:
        client.close()
        server.close()


def test_read_shedding_err_busy():
    """Aged/deep-queued client reads shed with typed ERR_BUSY; writes
    are exempt (the mutation path degrades last)."""
    from pegasus_tpu.utils.errors import ErrorCode
    from pegasus_tpu.utils.flags import FLAGS

    server, client = _pair()
    served, replies = [], []
    FLAGS.set("pegasus.rpc", "read_shed_queue_age_ms", 50)
    try:
        server.register("srv", lambda s, mt, p: served.append((mt, p)))
        client.register("cli", lambda s, mt, p: replies.append((mt, p)))
        # hold the node lock so queued messages AGE in the inbox (the
        # dispatcher pops the first one pre-aging and blocks on the
        # lock; everything behind it crosses the age threshold)
        with server.lock:
            for i in range(6):
                client.send("cli", "srv", "client_read",
                            {"rid": i, "op": "get", "args": b"k"})
            client.send("cli", "srv", "client_write",
                        {"rid": 100, "gpid": (1, 0), "ops": []})
            # wait for arrival, then let them age past the threshold
            time.sleep(0.4)
        assert _wait_for(lambda: len(replies) >= 4)
        assert all(mt == "client_read_reply"
                   and p["err"] == int(ErrorCode.ERR_BUSY)
                   for mt, p in replies), replies
        # the equally-aged write was NOT shed: it reached the handler
        assert _wait_for(lambda: ("client_write", {
            "rid": 100, "gpid": (1, 0), "ops": []}) in served)
        # fresh reads after the storm drains serve normally
        client.send("cli", "srv", "client_read",
                    {"rid": 200, "op": "get", "args": b"k"})
        assert _wait_for(lambda: any(mt == "client_read"
                                     and p.get("rid") == 200
                                     for mt, p in served))
    finally:
        FLAGS.set("pegasus.rpc", "read_shed_queue_age_ms", 5000)
        client.close()
        server.close()


def test_fault_plan_drop_delay_duplicate_partition():
    """rpc/fault.FaultPlan gives the REAL transport SimNetwork's chaos
    surface, gated by the fail-point registry."""
    from pegasus_tpu.rpc.fault import FaultPlan
    from pegasus_tpu.utils.fail_point import FAIL_POINTS

    server, client = _pair()
    got = []
    try:
        server.register("srv", lambda s, mt, p: got.append(p))
        plan = FaultPlan(seed=3)
        client.install_fault_plan(plan)  # arms FAIL_POINTS too
        # drop: total loss on the link
        plan.set_drop(1.0, "cli", "srv")
        client.send("cli", "srv", "ping", 1)
        time.sleep(0.3)
        assert got == [] and plan.dropped == 1
        # delay: held by the sender for the extra latency
        plan.set_drop(0.0, "cli", "srv")
        plan.set_delay(0.25, "cli", "srv")
        t0 = time.monotonic()
        client.send("cli", "srv", "ping", 2)
        assert _wait_for(lambda: 2 in got)
        assert time.monotonic() - t0 >= 0.25
        # duplicate: redelivery TCP alone can never produce
        plan.set_delay(0.0, "cli", "srv")
        plan.set_duplicate(1.0, "cli", "srv")
        client.send("cli", "srv", "ping", 3)
        assert _wait_for(lambda: got.count(3) == 2)
        # partition: both directions dark, then heal
        plan.set_duplicate(0.0, "cli", "srv")
        plan.partition("srv")
        client.send("cli", "srv", "ping", 4)
        time.sleep(0.2)
        assert 4 not in got
        plan.heal("srv")
        client.send("cli", "srv", "ping", 5)
        assert _wait_for(lambda: 5 in got)
        # the fail-point registry is the global kill-switch: teardown
        # disarms the installed plan without un-wiring it
        FAIL_POINTS.teardown()
        plan.set_drop(1.0, "cli", "srv")
        client.send("cli", "srv", "ping", 6)
        assert _wait_for(lambda: 6 in got)
    finally:
        FAIL_POINTS.teardown()
        client.close()
        server.close()


def test_multiprocess_onebox(tmp_path):
    """The function-test tier: real processes, real TCP, kill -9 cure.

    1 meta + 3 replica processes; DDL + data ops through wire clients;
    kill -9 the primary of partition 0; acked writes survive the cure.
    """
    from pegasus_tpu.tools import onebox_cluster as ob

    d = str(tmp_path / "onebox")
    shutil.rmtree(d, ignore_errors=True)
    ob.start(d, n_replica=3)
    admin = None
    try:
        from pegasus_tpu.utils.errors import PegasusError as _PE

        admin = ob.OneboxAdmin(d)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                if len(admin.call("list_nodes", timeout=6)) == 3:
                    break
            except _PE:
                pass
            time.sleep(0.5)
        assert len(admin.call("list_nodes")) == 3
        admin.create_table("fn", partition_count=4, replica_count=3)
        c = ob.connect("fn", d)
        from pegasus_tpu.utils.errors import PegasusError

        # settle: a loaded machine can lag config propagation/leases
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if c.set(b"warm", b"s", b"w") == 0:
                    break
            except PegasusError:
                time.sleep(1)
        acked = []
        for i in range(20):
            if c.set(b"k%02d" % i, b"s", b"v%d" % i) == 0:
                acked.append(i)
        assert len(acked) == 20
        assert c.multi_set(b"mh", {b"a": b"1"}) == 0
        assert c.multi_get(b"mh") == (0, {b"a": b"1"})
        c.refresh_config()
        victim = c._configs[0]["primary"]
        ob.kill_node(victim, d)
        for i in range(20, 30):
            # a write that exhausts retries during the outage is simply
            # un-acked — only OK-acked writes must survive
            try:
                if c.set(b"k%02d" % i, b"s", b"v%d" % i) == 0:
                    acked.append(i)
            except PegasusError:
                pass
        # wait for the guardian cure to finish before verifying (the
        # FD grace + cure can take >10s on a loaded machine)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            c.refresh_config()
            if all(victim not in [pc["primary"]] + pc["secondaries"]
                   and pc["primary"] for pc in c._configs):
                break
            time.sleep(1)
        for pc in c._configs:
            assert victim not in [pc["primary"]] + pc["secondaries"]
            assert pc["primary"]
        for i in acked:
            assert c.get(b"k%02d" % i, b"s") == (0, b"v%d" % i), i
    finally:
        if admin is not None:
            admin.close()
        ob.stop(d)


def test_kill_test_harness_short(tmp_path):
    """A bounded chaos run (parity: kill_test + data_verifier): random
    kill -9s under continuous verification, zero acked-write loss."""
    from pegasus_tpu.tools import onebox_cluster as ob
    from pegasus_tpu.tools.kill_test import run_kill_test

    d = str(tmp_path / "kt")
    ob.start(d, n_replica=3)
    try:
        report = run_kill_test(d, duration_s=25, kill_every_s=10, seed=5)
        assert report["violations"] == [], report
        assert report["writes_acked"] > 20
        assert report["kills"] >= 1
    finally:
        ob.stop(d)


def test_no_write_loss_during_env_compaction(tmp_path):
    """Acked writes racing an env-triggered manual compaction must all
    survive: the replicated apply path and the async compaction thread
    share the partition's single-writer lock — without it, the
    compaction's overlay reset wiped mutations applied after its merge
    snapshot (found by the combined-chaos drive)."""
    import threading

    from pegasus_tpu.tools import onebox_cluster as ob
    from pegasus_tpu.utils.errors import PegasusError

    d = str(tmp_path / "onebox")
    ob.start(d, n_replica=1)
    try:
        admin = ob.OneboxAdmin(d)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                if len(admin.call("list_nodes", timeout=6)) == 1:
                    break
            except PegasusError:
                pass
            time.sleep(0.5)
        admin.create_table("wlapp", partition_count=4, replica_count=1)
        pc = ob.connect("wlapp", d)
        acked = {}
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            try:
                while not stop.is_set():
                    k = b"wl%05d" % i
                    if pc.set(k, b"s", b"v%d" % i) == 0:
                        acked[k] = b"v%d" % i
                    i += 1
            except Exception as exc:  # noqa: BLE001 - surfaced below
                errors.append(repr(exc))

        t = threading.Thread(target=writer)
        t.start()
        time.sleep(1.0)  # let writes accumulate in the memtables
        admin.call("update_app_envs", app_name="wlapp",
                   envs={"manual_compact.once.trigger_time":
                         str(int(time.time()))})
        # the compaction must PROVABLY run while writes flow: wait for
        # the L1 runs it publishes to appear on disk (no fixed sleep —
        # a vacuous pass would defeat the regression)
        import glob
        import os

        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if glob.glob(os.path.join(d, "data", "node0", "*", "app",
                                      "sst", "l1-*.sst")):
                break
            time.sleep(0.2)
        l1s = glob.glob(os.path.join(d, "data", "node0", "*", "app",
                                     "sst", "l1-*.sst"))
        assert l1s, "env-triggered compaction never published L1 runs"
        time.sleep(1.0)  # a little more racing traffic post-publish
        # (and as much as the count below asks for: how long the
        # compaction took decides how many writes raced it, and a fast
        # one left the count a few writes short now and then)
        deadline = time.monotonic() + 20
        while len(acked) <= 220 and time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        t.join(timeout=20)
        assert not errors, errors
        assert len(acked) > 200, len(acked)
        pc2 = ob.connect("wlapp", d)  # fresh client: server truth only
        lost = [k for k, v in acked.items() if pc2.get(k, b"s") != (0, v)]
        assert not lost, f"{len(lost)} acked writes lost: {lost[:5]}"
    finally:
        ob.stop(d)
