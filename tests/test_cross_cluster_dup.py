"""Cross-cluster duplication: WAN-shaped batched shipping.

Two layers of coverage:

- seeded SIM tests over TWO SimClusters sharing one loop+network
  (distinct name prefixes + cluster ids — the real geo topology, with
  the inter-cluster links faulted like a WAN): batched envelope
  decree-order apply + idempotent re-ship under loss, the
  origin-cluster echo filter under master-master, lost config-reply
  re-ask, late-ack convergence under sustained link delay, fail_mode=
  skip abandon-and-advance, the ship-abort state regression, governor
  backpressure, and the dup trace crossing clusters as one tree;
- the original multi-process onebox test: cluster A duplicating to
  cluster B through real TCP transports (A's address book carries B's
  nodes as external peers), now riding the compressed envelope path.
"""

import json
import os
import time

import pytest

from pegasus_tpu.utils.errors import PegasusError
from pegasus_tpu.utils.flags import FLAGS


# ---- sim harness: two clusters, one wire --------------------------------


def make_two_clusters(tmp_path, seed=0, n_nodes=2):
    from pegasus_tpu.runtime.sim import SimLoop, SimNetwork
    from pegasus_tpu.tools.cluster import SimCluster

    loop = SimLoop(seed=seed)
    net = SimNetwork(loop)
    a = SimCluster(str(tmp_path / "A"), n_nodes=n_nodes,
                   name_prefix="a-", loop=loop, net=net, cluster_id=1)
    b = SimCluster(str(tmp_path / "B"), n_nodes=n_nodes,
                   name_prefix="b-", loop=loop, net=net, cluster_id=2)
    return a, b


def step_both(a, b, rounds=1):
    """Paired step: shared virtual time advances ONCE per round while
    both clusters run their timers (beacons, dup/config-sync ticks)."""
    for _ in range(rounds):
        a.step()
        b.step(advance=False)


def inter_links(a, b):
    an = list(a.stubs) + [m.name for m in a.metas]
    bn = list(b.stubs) + [m.name for m in b.metas]
    return ([(x, y) for x in an for y in bn]
            + [(y, x) for x in an for y in bn])


def dup_session(cluster):
    """Every live dup session across the cluster's stubs."""
    out = []
    for stub in cluster.stubs.values():
        out.extend(stub._dup_sessions.values())
    return out


@pytest.fixture
def dup_flags():
    """Snapshot/restore the [pegasus.dup] knobs tests fiddle."""
    import pegasus_tpu.replica.dup_governor  # noqa: F401 - defines flags
    import pegasus_tpu.replica.duplication_cluster  # noqa: F401

    keys = ["ship_batch_mutations", "ship_batch_bytes", "ship_governor",
            "ship_max_mbps", "ship_min_mbps"]
    saved = {k: FLAGS.get("pegasus.dup", k) for k in keys}
    yield
    for k, v in saved.items():
        FLAGS.set("pegasus.dup", k, v)


def test_batched_envelopes_converge_in_decree_order_under_loss(
        tmp_path, dup_flags):
    """A window of mutations (overwrites included) ships as compressed
    dup_apply_batch envelopes; seeded loss forces idempotent re-ships;
    the follower converges to exactly the master's final content."""
    from pegasus_tpu.utils.metrics import METRICS

    a, b = make_two_clusters(tmp_path, seed=3)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=2, replica_count=2)
        b.create_table("t", partition_count=2, replica_count=2)
        ca = a.client("t")
        # overwrites across mutations: only decree-order apply (within
        # and across envelopes) lands the final values
        for rnd in range(3):
            for i in range(20):
                assert ca.set(b"k%03d" % i, b"s",
                              b"r%d-%d" % (rnd, i)) == 0
        assert ca.multi_set(b"mh", {b"a": b"1", b"b": b"2"}) == 0
        assert ca.delete(b"k000", b"s") == 0
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        # WAN: seeded loss both ways on every inter-cluster link
        for s, d in inter_links(a, b):
            a.net.set_drop(0.3, src=s, dst=d)
        step_both(a, b, 14)
        for s, d in inter_links(a, b):
            a.net.set_drop(0.0, src=s, dst=d)
        step_both(a, b, 4)
        cb = b.client("t")
        for i in range(1, 20):
            assert cb.get(b"k%03d" % i, b"s") == (0, b"r2-%d" % i), i
        assert cb.get(b"k000", b"s")[0] == 1  # delete shipped last
        assert cb.multi_get(b"mh") == (0, {b"a": b"1", b"b": b"2"})
        # the batched path actually ran: compressed envelope bytes and
        # confirmed mutations on the "duplication" entity
        shipped = confirmed = 0
        for ent in METRICS.snapshot("duplication"):
            m = ent.get("metrics", {})
            shipped += m.get("dup_shipped_bytes", {}).get("value", 0)
            confirmed += m.get("dup_confirmed_mutations",
                               {}).get("value", 0)
        assert shipped > 0 and confirmed > 0
        # lag drained to zero and reported up config-sync to meta
        stats = a.meta.duplication.dup_stats("t")
        assert stats and stats[0]["max_lag_decrees"] == 0
        assert stats[0]["shipped_bytes"] > 0
    finally:
        a.close()
        b.close()


def test_master_master_echo_filter(tmp_path, dup_flags):
    """Both clusters duplicate the same table at each other. Writes
    received FROM the peer (timetag cluster != own id) must never be
    re-shipped back — the origin-cluster filter — while each side's own
    writes reach the other."""
    a, b = make_two_clusters(tmp_path, seed=5)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=2, replica_count=2)
        b.create_table("t", partition_count=2, replica_count=2)
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        b.meta.duplication.add_duplication("t", "a-meta", "t")
        step_both(a, b, 3)
        ca, cb = a.client("t"), b.client("t")
        assert ca.set(b"from_a", b"s", b"av") == 0
        assert cb.set(b"from_b", b"s", b"bv") == 0
        step_both(a, b, 8)
        assert cb.get(b"from_a", b"s") == (0, b"av")
        assert ca.get(b"from_b", b"s") == (0, b"bv")
        # B's sessions saw A's dup writes in their logs and CONFIRMED
        # past them without shipping them back (echo filtered): after
        # convergence, more A-writes advance B's confirmed decrees with
        # ZERO new shipped bytes from B
        b_sessions = dup_session(b)
        assert b_sessions
        b_shipped0 = sum(s.stats()["shipped_bytes"] for s in b_sessions)
        for i in range(10):
            assert ca.set(b"more%02d" % i, b"s", b"v%d" % i) == 0
        step_both(a, b, 8)
        assert cb.get(b"more09", b"s") == (0, b"v9")
        b_sessions = dup_session(b)
        b_shipped1 = sum(s.stats()["shipped_bytes"] for s in b_sessions)
        assert b_shipped1 == b_shipped0, "echoed dup writes re-shipped"
        # and B confirmed past the received-dup decrees (no wedge)
        assert all(s.stats()["lag_decrees"] == 0 for s in b_sessions)
    finally:
        a.close()
        b.close()


def test_lost_config_reply_is_reasked(tmp_path, dup_flags):
    """Every follower-config reply is dropped for a while: the session
    must keep re-asking with fresh rids (not wedge on the lost one) and
    converge after the link heals."""
    a, b = make_two_clusters(tmp_path, seed=7)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=2, replica_count=2)
        b.create_table("t", partition_count=2, replica_count=2)
        ca = a.client("t")
        for i in range(10):
            assert ca.set(b"c%02d" % i, b"s", b"v%d" % i) == 0
        # silence the follower meta's replies BEFORE the dup starts
        for an in list(a.stubs):
            a.net.set_drop(1.0, src="b-meta", dst=an)
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        step_both(a, b, 6)
        sessions = dup_session(a)
        assert sessions
        assert all(s.confirmed_decree == 0 for s in sessions)
        for an in list(a.stubs):
            a.net.set_drop(0.0, src="b-meta", dst=an)
        step_both(a, b, 8)
        cb = b.client("t")
        for i in range(10):
            assert cb.get(b"c%02d" % i, b"s") == (0, b"v%d" % i), i
    finally:
        a.close()
        b.close()


def test_late_ack_convergence_under_sustained_link_delay(
        tmp_path, dup_flags):
    """Inter-cluster RTT sustained past the re-drive cadence: retained
    rids must let LATE acks complete windows (no livelock on the same
    window), and re-shipped envelopes stay idempotent."""
    a, b = make_two_clusters(tmp_path, seed=11)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=2, replica_count=2)
        b.create_table("t", partition_count=2, replica_count=2)
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        step_both(a, b, 3)
        # one-way delay > the 3-tick base re-drive limit (3s beacons)
        for s, d in inter_links(a, b):
            a.net.set_delay(5.0, src=s, dst=d)
        ca = a.client("t")
        for i in range(12):
            assert ca.set(b"d%02d" % i, b"s", b"v%d" % i) == 0
        step_both(a, b, 20)
        cb = b.client("t")
        for i in range(12):
            assert cb.get(b"d%02d" % i, b"s") == (0, b"v%d" % i), i
        assert all(s.stats()["lag_decrees"] == 0 for s in dup_session(a))
    finally:
        a.close()
        b.close()


def test_fail_mode_skip_abandons_and_advances(tmp_path, dup_flags):
    """fail_mode=skip: a poison decree (follower rejects every apply)
    is retried a bounded number of times, then LOUDLY abandoned —
    dup_skip_count ticks, confirmed advances, later mutations flow."""
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.fail_point import FAIL_POINTS
    from pegasus_tpu.utils.metrics import METRICS

    cluster = SimCluster(str(tmp_path / "c"), n_nodes=3, seed=9)
    try:
        cluster.create_table("m", partition_count=1, replica_count=2)
        cluster.create_table("f", partition_count=1, replica_count=2)
        c = cluster.client("m")
        assert c.set(b"poison", b"s", b"p") == 0
        dupid = cluster.meta.duplication.add_duplication("m", "meta", "f")
        cluster.meta.duplication.set_fail_mode(dupid, "skip")
        FAIL_POINTS.setup()
        FAIL_POINTS.cfg("dup::apply_batch", "return(13)")
        try:
            cluster.step(rounds=6)
        finally:
            FAIL_POINTS.cfg("dup::apply_batch", "off")
            FAIL_POINTS.teardown()
        skips = rejects = 0
        for ent in METRICS.snapshot("duplication"):
            m = ent.get("metrics", {})
            skips += m.get("dup_skip_count", {}).get("value", 0)
            rejects += m.get("dup_reject_count", {}).get("value", 0)
        assert skips >= 1, "abandon was not counted"
        assert rejects >= 3, "bounded retries did not run"
        # the poison decree was confirmed past (pipeline un-wedged)...
        sessions = dup_session(cluster)
        assert sessions and all(s.confirmed_decree >= 1
                                for s in sessions)
        # ...and LATER writes reach the follower while the abandoned
        # one is (operator-sanctioned) lost
        assert c.set(b"after", b"s", b"av") == 0
        cluster.step(rounds=6)
        fc = cluster.client("f")
        assert fc.get(b"after", b"s") == (0, b"av")
        assert fc.get(b"poison", b"s")[0] == 1
    finally:
        cluster.close()


def _unit_dup(tmp_path, fail_mode="slow"):
    """Fake-stub harness: a real MutationLog + ClusterDuplicator with
    every send recorded — deterministic white-box ship scenarios."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.replica.duplication_cluster import ClusterDuplicator
    from pegasus_tpu.replica.mutation import Mutation, WriteOp
    from pegasus_tpu.replica.mutation_log import MutationLog
    from pegasus_tpu.replica.replica import PartitionStatus
    from pegasus_tpu.rpc.codec import OP_PUT

    class _Net:
        def __init__(self):
            self.sent = []

        def send(self, src, dst, typ, payload):
            self.sent.append((dst, typ, payload))

    class _Replica:
        def __init__(self, log):
            self.log = log
            self.status = PartitionStatus.PRIMARY
            self.last_committed_decree = 0
            self.duplicators = []

    class _Stub:
        name = "src-node"
        auth_secret = None
        clock = None

        def __init__(self, replica):
            self.net = _Net()
            self._replica = replica

        def get_replica(self, _gpid):
            return self._replica

    log = MutationLog(os.path.join(str(tmp_path), "mlog.bin"))
    replica = _Replica(log)
    stub = _Stub(replica)
    # keys spreading over BOTH follower partitions (count=2), two
    # mutations so the window spans decrees
    for d in (1, 2):
        ops = [WriteOp(OP_PUT, (generate_key(b"hk%02d" % i, b"s"),
                                b"v", 0xFFFFFFFF))
               for i in range(d * 4, d * 4 + 4)]
        log.append(Mutation(ballot=1, decree=d, last_committed=d - 1,
                            timestamp_us=d * 1_000_000, ops=ops),
                   sync=True)
    replica.last_committed_decree = 2
    dup = ClusterDuplicator(stub, (9, 0), 1, "b-meta", "t",
                            fail_mode=fail_mode)
    return dup, stub, log


def test_ship_abort_clears_outstanding_state(tmp_path, dup_flags):
    """Regression: the mid-loop 'follower partition unowned' abort left
    `_outstanding`/`_pending_pidx` populated with rids from the aborted
    attempt — a late ack for one of them reset the re-drive clock for a
    window no longer in flight. Both must clear on abort."""
    dup, stub, log = _unit_dup(tmp_path)
    # follower config: partition 1 unowned — the ship must abort
    # mid-loop AFTER (possibly) sending partition 0's envelope
    dup._fconfig = {"app_id": 7, "partition_count": 2,
                    "configs": [{"primary": "b-node0"},
                                {"primary": ""}]}
    dup.tick()
    sent = [p for _d, t, p in stub.net.sent if t == "dup_apply_batch"]
    assert dup._outstanding == {}, "aborted rids left registered"
    assert dup._pending_pidx == set(), "aborted pidxs left pending"
    assert dup._inflight_decree is None
    assert dup._fconfig is None
    if sent:  # an envelope left before the abort: its late ack must be
        # a no-op (unknown rid), not a state reset
        dup._inflight_ticks = 2
        assert dup.on_write_reply({"rid": sent[0]["rid"],
                                   "err": 0}) is False
        assert dup._inflight_ticks == 2
    log.close()


def test_transient_rejection_does_not_pin_solo_windows(tmp_path,
                                                       dup_flags):
    """Regression: in fail_mode=skip, ONE transient follower rejection
    set `_fail_count` and nothing cleared it on the subsequent
    successful ack — every later tick shipped solo (cap_n=1) windows,
    silently giving up the whole batched-shipping win for the session's
    lifetime."""
    dup, stub, log = _unit_dup(tmp_path, fail_mode="skip")
    fconfig = {"app_id": 7, "partition_count": 2,
               "configs": [{"primary": "b-node0"},
                           {"primary": "b-node1"}]}
    dup._fconfig = dict(fconfig, configs=[dict(c) for c
                                          in fconfig["configs"]])
    dup.tick()
    sent = [p for _d, t, p in stub.net.sent if t == "dup_apply_batch"]
    assert sent and sent[0]["max_decree"] == 2  # batched window of 2
    # transient rejection (follower mid-failover)
    assert dup.on_write_reply({"rid": sent[0]["rid"], "err": 13})
    assert dup._fail_count == 1
    # re-resolve + re-ship (cooldown consumes one tick first)
    dup._fconfig = dict(fconfig, configs=[dict(c) for c
                                          in fconfig["configs"]])
    stub.net.sent.clear()
    dup.tick()  # consumes the rejection cooldown
    dup.tick()  # solo retry window while rejections are being counted
    retry = [p for _d, t, p in stub.net.sent
             if t == "dup_apply_batch"]
    assert retry and retry[0]["max_decree"] == 1  # isolated to solo
    for p in retry:
        assert dup.on_write_reply({"rid": p["rid"], "err": 0})
    assert dup._fail_count == 0  # the success CLEARED the skip state
    # the next window is batched again, not pinned solo forever
    stub.net.sent.clear()
    dup.tick()
    nxt = [p for _d, t, p in stub.net.sent if t == "dup_apply_batch"]
    assert nxt and nxt[0]["max_decree"] == 2
    log.close()


def test_governor_backoff_recovery_and_floor(dup_flags):
    """Seeded DupGovernor unit: follower pressure growth halves the
    budget (engaging from uncapped), quiet acks recover it back to
    uncapped, and the floor is never undercut."""
    from pegasus_tpu.replica.dup_governor import DupGovernor

    FLAGS.set("pegasus.dup", "ship_min_mbps", 0.5)
    now = [0.0]
    gov = DupGovernor("test-node", clock=lambda: now[0])
    assert gov.window_budget() is None  # uncapped at rest
    gov._rate_bps = 8e6  # pretend catch-up measured 8 MB/s
    gov.on_follower_pressure("f1", {"deadline_expired": 0,
                                    "read_shed": 0})
    now[0] += 1.0
    gov.on_follower_pressure("f1", {"deadline_expired": 5,
                                    "read_shed": 0})
    assert gov._throttle_mbps == pytest.approx(4.0)  # engage at half
    for k in range(6):
        now[0] += 1.0
        gov.on_follower_pressure("f1", {"deadline_expired": 6 + k,
                                        "read_shed": 5 + k})
    assert gov._throttle_mbps == pytest.approx(0.5)  # halved to floor
    assert gov.status()["backoff_count"] >= 2
    # budget is finite and refills with time while capped
    b0 = gov.window_budget()
    assert b0 is not None
    gov.note_shipped(b0 + 100_000)
    assert gov.window_budget() < b0
    # quiet acks: multiplicative recovery until fully uncapped
    for _ in range(30):
        now[0] += 2.0
        gov.on_follower_pressure("f1", {"deadline_expired": 5,
                                        "read_shed": 5})
        if gov._throttle_mbps == 0.0:
            break
    assert gov.window_budget() is None  # recovered to uncapped


def test_governor_floor_still_ships_one_mutation(tmp_path, dup_flags):
    """Forward-progress floor end-to-end: with the budget squeezed to
    zero bytes, every tick still loads (and ships) one mutation — the
    catch-up can be slowed, never stalled."""
    a, b = make_two_clusters(tmp_path, seed=13)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=1, replica_count=2)
        b.create_table("t", partition_count=1, replica_count=2)
        ca = a.client("t")
        for i in range(6):
            assert ca.set(b"f%02d" % i, b"s", b"v%d" % i) == 0
        # engage a throttle so tiny the token bucket is always empty
        FLAGS.set("pegasus.dup", "ship_max_mbps", 1e-9)
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        step_both(a, b, 12)
        cb = b.client("t")
        for i in range(6):
            assert cb.get(b"f%02d" % i, b"s") == (0, b"v%d" % i), i
    finally:
        a.close()
        b.close()


def test_dup_trace_crosses_clusters_as_one_tree(tmp_path, dup_flags):
    """A sampled write's trace context rides the dup envelope: the
    stitched tree contains the client op, the source 2PC span, the
    dup.ship hop, and the follower's dup_apply_batch dispatch span —
    one write visible crossing clusters."""
    from pegasus_tpu.utils import tracing

    tracing.reset()
    tracing.seed(4)
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    try:
        a, b = make_two_clusters(tmp_path, seed=15)
        try:
            step_both(a, b, 2)
            a.create_table("t", partition_count=1, replica_count=2)
            b.create_table("t", partition_count=1, replica_count=2)
            a.meta.duplication.add_duplication("t", "b-meta", "t")
            step_both(a, b, 3)
            ca = a.client("t")
            assert ca.set(b"traced", b"s", b"tv") == 0
            step_both(a, b, 6)
            cb = b.client("t")
            assert cb.get(b"traced", b"s") == (0, b"tv")
            spans = tracing.dump_all()
            ship = [s for s in spans if s["name"].startswith("dup.ship")]
            assert ship, "no dup.ship span recorded"
            trace_id = ship[0]["trace"]
            tree_spans = [s for s in spans if s["trace"] == trace_id]
            names = {s["name"] for s in tree_spans}
            assert any(n.startswith("2pc.") for n in names)
            assert any(n == "dup_apply_batch" for n in names)
            tree = tracing.stitch(tree_spans)
            nodes = list(tracing.walk_dict(tree))
            # the follower's dispatch span is a DESCENDANT in one tree
            assert any(n["name"] == "dup_apply_batch" for n in nodes)
        finally:
            a.close()
            b.close()
    finally:
        FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
        tracing.reset()


def test_solo_wire_flag_degrades_to_legacy_shipping(tmp_path, dup_flags):
    """ship_batch_mutations<=1 keeps the original one-mutation
    client_write shipping alive (the bench baseline + a rollback
    lever); content still converges."""
    from pegasus_tpu.utils.metrics import METRICS

    FLAGS.set("pegasus.dup", "ship_batch_mutations", 1)
    a, b = make_two_clusters(tmp_path, seed=17)
    try:
        step_both(a, b, 2)
        a.create_table("t", partition_count=1, replica_count=2)
        b.create_table("t", partition_count=1, replica_count=2)
        ca = a.client("t")
        for i in range(8):
            assert ca.set(b"s%02d" % i, b"s", b"v%d" % i) == 0
        before = {ent["id"]: ent["metrics"].get(
            "dup_shipped_bytes", {}).get("value", 0)
            for ent in METRICS.snapshot("duplication")}
        a.meta.duplication.add_duplication("t", "b-meta", "t")
        step_both(a, b, 12)
        cb = b.client("t")
        for i in range(8):
            assert cb.get(b"s%02d" % i, b"s") == (0, b"v%d" % i), i
        # solo wire still accounts shipped bytes on the dup entity
        after = sum(ent["metrics"].get("dup_shipped_bytes",
                                       {}).get("value", 0)
                    - before.get(ent["id"], 0)
                    for ent in METRICS.snapshot("duplication"))
        assert after > 0
    finally:
        a.close()
        b.close()


# ---- the original wire test: two real oneboxes over TCP -----------------


def _wait_nodes(admin, n, deadline_s=90):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            if len(admin.call("list_nodes", timeout=6)) == n:
                return
        except PegasusError:
            pass
        time.sleep(0.5)
    pytest.fail("cluster never came up")


def test_wire_duplication_between_two_oneboxes(tmp_path):
    from pegasus_tpu.tools import onebox_cluster as ob

    db = str(tmp_path / "B")
    da = str(tmp_path / "A")
    ob.start(db, n_replica=1, name_prefix="b", cluster_id=2)
    try:
        admin_b = ob.OneboxAdmin(db)
        _wait_nodes(admin_b, 1)
        admin_b.create_table("dapp", partition_count=2, replica_count=1)
        with open(os.path.join(db, "cluster.json")) as f:
            bnodes = {n: (c["host"], c["port"])
                      for n, c in json.load(f)["nodes"].items()}

        ob.start(da, n_replica=1, name_prefix="a", extra_peers=bnodes,
                 cluster_id=1)
        try:
            admin_a = ob.OneboxAdmin(da)
            _wait_nodes(admin_a, 1)
            admin_a.create_table("dapp", partition_count=2,
                                 replica_count=1)
            pa = ob.connect("dapp", da)
            for i in range(10):
                assert pa.set(b"dk%02d" % i, b"s", b"v%d" % i) == 0
            admin_a.call("add_dup", app_name="dapp",
                         follower_meta="bmeta", follower_app="dapp",
                         timeout=30)
            pb = ob.connect("dapp", db)
            deadline = time.monotonic() + 90
            missing = -1
            while time.monotonic() < deadline:
                missing = sum(pb.get(b"dk%02d" % i, b"s") !=
                              (0, b"v%d" % i) for i in range(10))
                if missing == 0:
                    break
                time.sleep(0.5)
            assert missing == 0, f"{missing} rows never converged on B"
            # live write + delete keep flowing
            assert pa.set(b"live", b"s", b"lv") == 0
            assert pa.delete(b"dk00", b"s") == 0
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (pb.get(b"live", b"s") == (0, b"lv")
                        and pb.get(b"dk00", b"s")[0] == 1):
                    break
                time.sleep(0.5)
            assert pb.get(b"live", b"s") == (0, b"lv")
            assert pb.get(b"dk00", b"s")[0] == 1
            # the wire path shipped envelopes and reports dup health
            stats = admin_a.call("dup_stats", timeout=15)
            assert stats and stats[0]["shipped_bytes"] > 0
            node_stats = admin_a.remote_command("anode0", "dup.stats",
                                                [])
            assert node_stats["sessions"]
        finally:
            ob.stop(da)
    finally:
        ob.stop(db)
