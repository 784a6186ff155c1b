"""Utility-layer tests: flags, metrics, fail points, token bucket."""

import time

import pytest

from pegasus_tpu.utils.errors import ErrorCode, PegasusError, StorageStatus
from pegasus_tpu.utils.fail_point import FAIL_POINTS, fail_point
from pegasus_tpu.utils.flags import FlagRegistry
from pegasus_tpu.utils.metrics import MetricRegistry
from pegasus_tpu.utils.token_bucket import TokenBucket, parse_throttle_env


def test_flags_define_get_set(tmp_path):
    reg = FlagRegistry()
    reg.define("pegasus.server", "rocksdb_block_cache_capacity", 1024,
               mutable=True)
    reg.define("replication", "staleness_for_commit", 20, mutable=False,
               validator=lambda v: v > 0)
    assert reg.get("pegasus.server", "rocksdb_block_cache_capacity") == 1024
    reg.set("pegasus.server", "rocksdb_block_cache_capacity", 2048)
    assert reg.get("pegasus.server", "rocksdb_block_cache_capacity") == 2048
    with pytest.raises(ValueError):
        reg.set("replication", "staleness_for_commit", 30)  # immutable

    ini = tmp_path / "config.ini"
    ini.write_text("[replication]\nstaleness_for_commit = 40\n")
    reg.load_ini(str(ini))
    assert reg.get("replication", "staleness_for_commit") == 40

    ini.write_text("[replication]\nstaleness_for_commit = -1\n")
    with pytest.raises(ValueError):
        reg.load_ini(str(ini))


def test_ini_key_of_a_removed_flag_loads_and_changes_nothing(tmp_path):
    """An operator's old config must keep booting: `compact_pipeline`
    was a flag until PR 29 (the engine now picks the compaction loop
    from the snapshot's size), and load_ini skips keys it does not
    define."""
    from pegasus_tpu.storage import compact_pipeline, engine  # noqa: F401
    from pegasus_tpu.utils.flags import FLAGS

    before = FLAGS.snapshot()
    window = compact_pipeline.pipeline_window()
    ini = tmp_path / "config.ini"
    ini.write_text("[pegasus.storage]\ncompact_pipeline = false\n"
                   "compact_pipeline_window = 4\n"
                   "compact_pipeline_depth = 9\n")
    FLAGS.load_ini(str(ini))
    assert FLAGS.snapshot() == before
    assert not [n for n in before["pegasus.storage"]
                if n.startswith("compact_pipe")]
    assert (compact_pipeline.pipeline_window(),
            compact_pipeline.pipeline_depth()) == (window, 2)


def test_metrics_entities_and_percentile():
    reg = MetricRegistry()
    ent = reg.entity("replica", "1.2", {"table": "temp"})
    ent.counter("get_requests").increment(5)
    ent.gauge("sst_count").set(3)
    p = ent.percentile("get_latency_ns")
    for v in range(100):
        p.set(float(v))
    snap = reg.snapshot(entity_type="replica")
    assert len(snap) == 1
    m = snap[0]["metrics"]
    assert m["get_requests"]["value"] == 5
    assert m["sst_count"]["value"] == 3
    assert m["get_latency_ns"]["p50"] == pytest.approx(50.0, abs=2)
    assert reg.snapshot(entity_type="table") == []


def test_volatile_counter_reader_reads_deltas_value_stays_cumulative():
    reg = MetricRegistry()
    c = reg.entity("server", "s1").volatile_counter("qps")
    c.increment(10)
    # a reader sees the increments since its own last call...
    assert c.delta_since("scraper") == 10
    assert c.delta_since("scraper") == 0
    c.increment(3)
    assert c.delta_since("scraper") == 3
    # ...but the stored value is CUMULATIVE: nothing resets under
    # other readers, and snapshots report the sum
    assert c.value() == 13
    assert c.snapshot() == {"type": "volatile_counter", "value": 13}


def test_volatile_counter_concurrent_readers_each_see_full_sum():
    """The multi-reader race regression: the recorder, the collector,
    and /metrics used to steal each other's deltas through
    reset-on-read. With per-reader cursors, two interleaved readers
    each observe the complete sum."""
    import threading

    reg = MetricRegistry()
    c = reg.entity("server", "s1").volatile_counter("ops")
    totals = {"a": 0, "b": 0}
    stop = threading.Event()

    def reader(rid):
        while not stop.is_set():
            totals[rid] += c.delta_since(rid)
        totals[rid] += c.delta_since(rid)

    threads = [threading.Thread(target=reader, args=(rid,))
               for rid in totals]
    for t in threads:
        t.start()
    n = 20_000
    for _ in range(n):
        c.increment()
    stop.set()
    for t in threads:
        t.join()
    assert totals["a"] == n
    assert totals["b"] == n
    assert c.value() == n


def test_fail_point_lifecycle():
    assert fail_point("replica::on_write") is None  # disabled: zero effect
    FAIL_POINTS.setup()
    try:
        FAIL_POINTS.cfg("replica::on_write", "return(ERR_TIMEOUT)")
        assert fail_point("replica::on_write") == "ERR_TIMEOUT"
        FAIL_POINTS.cfg("replica::on_write", "off")
        assert fail_point("replica::on_write") is None
        FAIL_POINTS.cfg("boom", "raise(injected)")
        with pytest.raises(RuntimeError):
            fail_point("boom")
    finally:
        FAIL_POINTS.teardown()
    assert fail_point("boom") is None


def test_fail_point_probabilistic_actions():
    """The reference's '<N>%action(...)' frequency syntax, backed by the
    registry's seeded RNG (fail_point.h's probabilistic fail points)."""
    FAIL_POINTS.setup()
    try:
        FAIL_POINTS.seed(42)
        FAIL_POINTS.cfg("p::ret", "30%return(shed)")
        hits = sum(1 for _ in range(2000)
                   if fail_point("p::ret") is not None)
        assert 480 < hits < 720  # ~30% of 2000, generous bounds
        # reproducible: the same seed replays the same decision stream
        FAIL_POINTS.seed(42)
        first = [fail_point("p::ret") for _ in range(50)]
        FAIL_POINTS.seed(42)
        assert [fail_point("p::ret") for _ in range(50)] == first
        # probabilistic raise: fires sometimes, not always
        FAIL_POINTS.cfg("p::raise", "50%raise(boom)")
        raised = 0
        for _ in range(200):
            try:
                fail_point("p::raise")
            except RuntimeError:
                raised += 1
        assert 50 < raised < 150
        # 100%-equivalent prefix behaves like the plain action
        FAIL_POINTS.cfg("p::always", "100%return(x)")
        assert all(fail_point("p::always") == "x" for _ in range(10))
        # probabilistic delay: a miss is a no-op, a hit sleeps; either
        # way the injected value stays None (delay never returns one)
        FAIL_POINTS.cfg("p::delay", "50%delay(1)")
        assert all(fail_point("p::delay") is None for _ in range(20))
    finally:
        FAIL_POINTS.teardown()


def test_backoff_jitter_bounds_and_determinism():
    from pegasus_tpu.utils.backoff import Backoff

    slept = []
    b = Backoff(base_ms=20, max_ms=1000, seed=7,
                sleep=lambda s: slept.append(s))
    for attempt in range(1, 12):
        d = b.sleep(attempt)
        ceiling = min(1.0, 0.020 * 2 ** (attempt - 1))
        # full-jitter window: [ceiling/2, ceiling] — never zero (a zero
        # sleep is the busy-spin this exists to kill), never past cap
        assert ceiling / 2 <= d <= ceiling, (attempt, d)
    assert slept == b.slept and len(slept) == 11
    # deterministic from the seed
    b2 = Backoff(base_ms=20, max_ms=1000, seed=7, sleep=lambda s: None)
    assert [b2.delay(a) for a in range(1, 12)] != \
        [b2.delay(a) for a in range(1, 12)]  # jitter varies per draw
    b3 = Backoff(base_ms=20, max_ms=1000, seed=7, sleep=lambda s: None)
    b4 = Backoff(base_ms=20, max_ms=1000, seed=7, sleep=lambda s: None)
    assert [b3.delay(a) for a in range(1, 12)] == \
        [b4.delay(a) for a in range(1, 12)]


def test_token_bucket():
    tb = TokenBucket(rate=1000, burst=10)
    assert all(tb.try_consume() for _ in range(10))
    # bucket drained; refill is 1 token/ms
    ok = tb.try_consume(10)
    assert not ok
    delay = tb.consume_or_delay(5)
    assert delay > 0


def test_parse_throttle_env():
    d, r = parse_throttle_env("2000*delay*100")
    assert d is not None and d.rate == 2000 and r is None
    d, r = parse_throttle_env("1000*delay*50,2000*reject*10")
    assert d.rate == 1000 and r.rate == 2000
    d, r = parse_throttle_env("100K")
    assert d.rate == 100_000
    assert parse_throttle_env("") == (None, None)


def test_error_codes():
    err = PegasusError(ErrorCode.ERR_TIMEOUT, "rpc timed out")
    assert err.code == ErrorCode.ERR_TIMEOUT
    assert "ERR_TIMEOUT" in str(err)
    assert StorageStatus.OK == 0 and StorageStatus.NOT_FOUND == 1


def test_latency_tracer_stage_chain():
    from pegasus_tpu.utils.latency_tracer import LatencyTracer, SlowQueryLog

    clock_v = [0.0]
    tr = LatencyTracer("write.1.0.d7", clock=lambda: clock_v[0])
    clock_v[0] = 0.002
    tr.add_point("prepare_local")
    clock_v[0] = 0.010
    tr.add_point("committed")
    rep = tr.report()
    assert rep["total_ms"] == 10.0
    assert [s["stage"] for s in rep["stages"]] == ["prepare_local",
                                                   "committed"]
    assert rep["stages"][1]["delta_ms"] == 8.0

    log = SlowQueryLog(threshold_ms=5.0, capacity=2)
    assert log.observe(tr)
    fast = LatencyTracer("fast", clock=lambda: clock_v[0])
    assert not log.observe(fast)
    # capacity bounds the ring
    log.observe_simple("a", 50)
    log.observe_simple("b", 60)
    dump = log.dump()
    assert len(dump) == 2 and dump[-1]["name"] == "b"


def test_command_manager_verbs():
    import pytest

    from pegasus_tpu.utils.command_manager import CommandManager

    mgr = CommandManager()
    mgr.register("echo", lambda args: list(args), "echo args")
    assert mgr.call("echo", ["a", "b"]) == ["a", "b"]
    assert "echo" in mgr.call("help", [])
    with pytest.raises(KeyError):
        mgr.call("nope", [])
    with pytest.raises(ValueError):
        mgr.register("echo", lambda a: a)


def test_slow_write_traces_recorded(tmp_path):
    """The replicated write path records stage chains for slow mutations
    and the node's remote command dumps them."""
    from pegasus_tpu.tools.cluster import SimCluster

    cluster = SimCluster(str(tmp_path / "c"), n_nodes=2)
    try:
        cluster.create_table("tr", partition_count=2, replica_count=2)
        c = cluster.client("tr")
        # force every write to be "slow" by lowering the threshold
        for stub in cluster.stubs.values():
            for r in stub.replicas.values():
                r.slow_log.threshold_ms = 0.0
        assert c.set(b"k", b"s", b"v") == 0
        cluster.step()
        dumps = []
        for stub in cluster.stubs.values():
            dumps += stub.commands.call("slow-query-dump", [])
        assert dumps, "no slow-write trace recorded"
        stages = [st["stage"] for st in dumps[0]["stages"]]
        assert "append_plog" in stages and "replied" in stages
    finally:
        cluster.close()
