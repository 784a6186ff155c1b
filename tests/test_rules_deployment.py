"""The rules deployment (BASELINE #4, rfcs/2021-05-27): the plain
reference against the device rule programs, the node's bound on
env-triggered compactions, and a small cluster serving the benchmark's
mix while an operator's triggers compact it."""

import json
import os
import threading
import time

import numpy as np
import pytest

from benchmarks import reference_rules
from benchmarks.reference import PEGASUS_EPOCH_BEGIN, Model, epoch_now
from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.ops.compaction import make_compaction_eval
from pegasus_tpu.ops.compaction_rules import compile_rules
from pegasus_tpu.ops.record_block import build_record_block
from pegasus_tpu.storage.compact_governor import ManualCompactPool
from pegasus_tpu.utils.metrics import METRICS

HERE = os.path.dirname(os.path.abspath(__file__))
NOW = 300_000_000


def _config_rules():
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "ycsb_rules_p64r3.json")) as f:
        return json.load(f)["app_envs"]["user_specified_compaction"]


def _hk(pattern, match="prefix"):
    return {"type": "hashkey_pattern", "pattern": pattern, "match": match}


def _sk(pattern, match="prefix"):
    return {"type": "sortkey_pattern", "pattern": pattern, "match": match}


def _ttl(start, stop):
    return {"type": "ttl_range", "start_ttl": start, "stop_ttl": stop}


def _update(how, value, *rules):
    return {"op": "update_ttl", "update_ttl_type": how, "value": value,
            "rules": list(rules)}


def _delete(*rules):
    return {"op": "delete_key", "rules": list(rules)}


RULESETS = {
    "config": _config_rules(),
    "empty_pattern_matches_nothing": [_delete(_hk(""))],
    "ttl_range_0_0_is_no_ttl": [_delete(_ttl(0, 0))],
    "ttl_range_window": [_delete(_ttl(100, 2000), _sk("field1"))],
    "from_current_skips_no_ttl": [
        _update("from_current", 500, _hk("user0009"))],
    "timestamp": [_update("timestamp", PEGASUS_EPOCH_BEGIN + NOW + 77,
                          _sk("9", "postfix"))],
    "anywhere_and_postfix": [_delete(_hk("r000", "anywhere"),
                                     _sk("3", "postfix"))],
    "delete_after_update_still_deletes": [
        _update("from_now", 60, _hk("user0008")),
        _delete(_hk("user0008"), _sk("field2"))],
    "updates_judged_on_the_original_ttl": [
        _update("from_now", 60, _ttl(0, 0)),
        _update("from_current", 7, _ttl(0, 0)),
        _update("from_now", 90, _ttl(1, 5000), _hk("user0009"))],
    "last_matching_update_stands": [
        _update("from_now", 60, _hk("user000")),
        _update("from_now", 90, _hk("user0009"))],
}


# around the config's prefixes user0008 / user0009
_RECORDS = (79999, 80000, 85123, 89999, 90000, 95555, 99999, 100000,
            8, 9000, 900000, 12345)


def _rows(seed, n=1500):
    """Seeded (hk, sk, expire_ts): hashkeys and sortkeys of the
    benchmark's shape from a small range, a third without TTL, some
    already expired."""
    rng = np.random.default_rng(seed)
    out = []
    for rec, field, kind, off in zip(
            rng.integers(0, 12, n), rng.integers(0, 10, n),
            rng.integers(0, 3, n), rng.integers(-50, 4000, n)):
        ets = 0 if kind == 0 else NOW + int(off)
        out.append((b"user%08d" % _RECORDS[rec], b"field%d" % field,
                    max(1, ets) if kind else 0))
    return out


def _reference(ruleset, rows):
    """(drop by a rule, expire_ts after the rules) per row."""
    rules = reference_rules.Rules(ruleset)
    drop, ets = [], []
    for hk, sk, e in rows:
        verdict = rules.matched(hk, sk, e, NOW)
        drop.append(verdict == "delete")
        ets.append(e if verdict in ("delete", None) else verdict)
    return np.array(drop), np.array(ets, dtype=np.uint32)


@pytest.mark.parametrize("name", sorted(RULESETS))
@pytest.mark.parametrize("seed", [1, 2])
def test_reference_equals_compile_rules(name, seed):
    rows = _rows(seed)
    want_drop, want_ets = _reference(RULESETS[name], rows)
    rules_filter = compile_rules(json.dumps(RULESETS[name]))
    drop, ets = rules_filter([generate_key(hk, sk) for hk, sk, _ in rows],
                             [e for _hk, _sk, e in rows], NOW)
    assert np.array_equal(np.asarray(drop), want_drop)
    # (a deleted row's expire_ts is nobody's to read)
    assert np.array_equal(np.asarray(ets)[~want_drop], want_ets[~want_drop])
    if name == "config":
        assert want_drop.any() and (want_ets != [e for *_k, e in rows]).any()


@pytest.mark.parametrize("name", sorted(RULESETS))
def test_reference_equals_bulk_eval_program(name):
    """make_compaction_eval's program (the block path) adds expiry to
    the rules' own drops: limit 0 on the mask and on expire_ts."""
    rows = _rows(3)
    want_drop, want_ets = _reference(RULESETS[name], rows)
    expired = (want_ets > 0) & (want_ets <= NOW)
    operations = compile_rules(json.dumps(RULESETS[name])).operations
    block = build_record_block([generate_key(hk, sk) for hk, sk, _ in rows],
                               [e for _hk, _sk, e in rows], capacity=2048)
    n = len(rows)
    drop, ets = make_compaction_eval(operations)(
        np.asarray(block.keys), np.asarray(block.key_len),
        np.asarray(block.hashkey_len), np.asarray(block.expire_ts),
        np.asarray(block.valid), np.zeros(1, dtype=np.uint32),
        np.uint32(NOW), np.uint32(0), np.zeros(2048, dtype=np.uint32),
        np.uint32(0), False, False, want_ets=True, pack=True)
    drop = np.unpackbits(np.asarray(drop), count=2048).astype(bool)
    assert np.array_equal(drop[:n], want_drop | expired)
    assert not drop[n:].any()
    assert np.array_equal(np.asarray(ets)[:n][~want_drop],
                          want_ets[~want_drop])


def test_reference_rejects_what_the_grammar_does_not_have():
    with pytest.raises(ValueError):
        reference_rules.Rules([{"op": "drop_table", "rules": [_hk("a")]}])
    with pytest.raises(ValueError):
        reference_rules.Rules([_delete()])
    with pytest.raises(ValueError):
        reference_rules.Rules([_delete(_hk("a", "regex"))]).matched(
            b"a", b"", 0)


def test_compile_rules_once_per_content():
    text = json.dumps(RULESETS["config"])
    assert compile_rules(text) is compile_rules(str(text))
    assert compile_rules(text) is not compile_rules(
        json.dumps(RULESETS["timestamp"]))


# ---- the node's bound ----------------------------------------------------


def _pool_counters(name):
    return {k: v["value"] for k, v in next(
        e["metrics"] for e in METRICS.snapshot()
        if e["type"] == "engine" and e["id"] == name).items()}


def _wait(until, seconds=10):
    deadline = time.monotonic() + seconds
    while not until() and time.monotonic() < deadline:
        time.sleep(0.01)
    return until()


def test_pool_bounds_running_and_starts_deferred_as_slots_free():
    pool = ManualCompactPool("test-bound")
    gate = threading.Event()
    running, peak, done = [0], [0], []
    lock = threading.Lock()

    def work(i):
        def fn():
            with lock:
                running[0] += 1
                peak[0] = max(peak[0], running[0])
            gate.wait(10)
            with lock:
                running[0] -= 1
                done.append(i)
        return fn

    for i in range(5):
        pool.submit(i, work(i), f"w{i}", limit=2)
    time.sleep(0.1)
    assert pool.running == 2 and len(done) == 0
    gate.set()      # nothing is submitted from here on
    assert _wait(lambda: len(done) == 5 and pool.running == 0)
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert peak[0] == 2 and pool.running_peak == 2
    assert _pool_counters("test-bound") == {
        "compact_deferred": 3, "compact_started": 5,
        "compact_finished": 5, "compact_running_peak": 2}


def test_pool_without_a_bound_and_what_it_remembers():
    """A count of 0 is upstream's "no limit": nothing waits. The pool
    keeps when each run finished and how long it took, and wait_idle
    returns once nothing runs or waits."""
    pool = ManualCompactPool("test-unbounded")
    gate = threading.Event()
    t0 = time.perf_counter()
    for i in range(4):
        pool.submit(i, lambda: gate.wait(10), f"u{i}", limit=0)
    assert _wait(lambda: pool.running == 4)
    assert not pool.wait_idle(0.05)
    gate.set()
    assert pool.wait_idle(10) and pool.running == 0
    assert _pool_counters("test-unbounded")["compact_deferred"] == 0
    assert pool.running_peak == 4 and len(pool.history) == 4
    assert all(t0 <= at <= time.perf_counter() and 0 <= took <= at - t0
               for at, took in pool.history)


def test_pool_survives_a_failed_run_and_drains_an_owner(capsys):
    pool = ManualCompactPool("test-fail")
    ran = []
    gate = threading.Event()

    def boom():
        gate.wait(10)
        raise RuntimeError("planted")

    pool.submit("a", boom, "boom", limit=1)
    pool.submit("b", lambda: ran.append("b"), "dropped", limit=1)
    pool.submit("c", lambda: ran.append("c"), "next", limit=1)
    assert pool.drain("b")          # it waited: forgotten, nothing to join
    assert not pool.drain("nobody")
    gate.set()
    assert not pool.drain("a")      # it ran: joined
    assert _wait(lambda: ran == ["c"] and pool.running == 0)
    assert "planted" in capsys.readouterr().err


def test_env_bound_defers_a_replica_until_the_slot_frees(tmp_path,
                                                         monkeypatch):
    """Two replicas under
    manual_compact.max_concurrent_running_count = 1: the second waits
    (running as far as triggers are concerned) and compacts when the
    first is done, with no further trigger."""
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.storage import compact_governor
    from pegasus_tpu.storage.engine import WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    pool = ManualCompactPool("test-env")
    monkeypatch.setattr(compact_governor, "MANUAL_COMPACT_POOL", pool)
    servers = [PartitionServer(str(tmp_path / f"p{i}")) for i in range(2)]
    try:
        for s in servers:
            s.engine.write_batch(
                [WriteBatchItem(OP_PUT, generate_key(b"k%d" % i, b"s"),
                                b"v", 0) for i in range(30)],
                s.engine.last_committed_decree + 1)
        envs = {"manual_compact.max_concurrent_running_count": "1",
                "manual_compact.once.trigger_time": str(int(time.time()))}
        hold = servers[0].engine.compact_lock
        hold.acquire()      # the first run stalls inside its slot
        try:
            for s in servers:
                s.update_app_envs(envs)
            time.sleep(0.2)
            assert all(s._mc_running for s in servers)
            assert pool.running == 1
            assert not servers[1].engine.lsm.l1_runs
        finally:
            hold.release()
        assert _wait(lambda: not any(s._mc_running for s in servers), 30)
        assert all(s.engine.lsm.l1_runs for s in servers)
        assert pool.running_peak == 1
        with pytest.raises(ValueError):
            servers[0].update_app_envs(
                {"manual_compact.max_concurrent_running_count": "many"})
        # a replica that closes while its run waits is forgotten
        hold.acquire()
        envs["manual_compact.once.trigger_time"] = str(int(time.time()) + 5)
        for s in servers:
            s.update_app_envs(envs)
        servers[1].close()
        assert not servers[1]._mc_running
        hold.release()
        assert _wait(lambda: not servers[0]._mc_running, 30)
        assert _pool_counters("test-env")["compact_started"] == 3
    finally:
        for s in servers[:1]:
            s.close()


def test_an_accepted_trigger_compiles_before_the_run_starts(tmp_path,
                                                           monkeypatch):
    """What the run and the reads beside it dispatch is compiled on the
    thread that delivers the trigger, before the pool gets the run:
    the block path's program at the bucket of its window, the read
    path's static mask over one block and over a stack. Nothing
    compiles after that, over a store with an overlay (spliced into
    its blocks) or over pure L1, or in a torn batch's fallback; a
    sibling with the same shapes warms nothing again."""
    import jax.monitoring as mon

    from pegasus_tpu.server import partition_server as ps
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.server.scan_coordinator import stacked_block_eval
    from pegasus_tpu.server.types import GetScannerRequest
    from pegasus_tpu.storage import compact_governor, engine
    from pegasus_tpu.storage.engine import WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    compiled = [0]

    def on_duration(event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled[0] += 1

    mon.register_event_duration_secs_listener(on_duration)
    pool = ManualCompactPool("test-warm")
    at_submit = []
    submit = pool.submit

    def submit_counted(*args, **kw):
        at_submit.append(compiled[0])
        submit(*args, **kw)

    monkeypatch.setattr(pool, "submit", submit_counted)
    monkeypatch.setattr(compact_governor, "MANUAL_COMPACT_POOL", pool)
    monkeypatch.setattr(engine, "_WARMED", set())
    monkeypatch.setattr(ps, "_WARMED_MASKS", set())
    servers = [PartitionServer(str(tmp_path / f"p{i}")) for i in range(2)]
    try:
        for s in servers:
            # two blocks in L1 and an overlay: the first run splices the
            # overlay's row into them, the next is pure L1; both on the
            # block path
            s.engine.write_batch(
                [WriteBatchItem(OP_PUT, generate_key(b"user%07d" % i, b"f9"),
                                b"v", 0) for i in range(1500)],
                s.engine.last_committed_decree + 1)
            s.manual_compact()
            s.engine.write_batch(
                [WriteBatchItem(OP_PUT, generate_key(b"zz", b"f9"), b"v", 0)],
                s.engine.last_committed_decree + 1)
            # serving has scanned it: the flavor the warm-up reads
            s.on_get_scanner_batch([GetScannerRequest(
                start_key=generate_key(b"user0000000", b""), batch_size=5,
                one_page=True, validate_partition_hash=True)])
            assert s._warm_flavors
        # (a trigger no newer than the last compaction is satisfied)
        envs = {"user_specified_compaction": json.dumps(_config_rules()),
                "manual_compact.once.trigger_time": str(int(time.time()) + 5)}
        before = compiled[0]
        servers[0].update_app_envs(envs)
        assert at_submit[0] > before         # the warm-up compiled, first
        assert pool.wait_idle(60)
        run = servers[0].engine.lsm.l1_runs[0]
        dev = servers[0]._device_cached_block(
            (run.path, run.blocks[0].offset), run.read_block(0))
        (validate, filter_key), = servers[0]._warm_flavors
        for height in (1, 2):
            list(stacked_block_eval([(i, dev, 0) for i in range(height)],
                                    validate, servers[0].partition_version,
                                    filter_key=filter_key))
        envs["manual_compact.once.trigger_time"] = str(int(time.time()) + 10)
        servers[0].update_app_envs(envs)     # pure L1 now
        servers[1].update_app_envs(envs)     # the sibling, the same shapes
        assert pool.wait_idle(60)
        # a torn batch is served request by request: over a store with
        # an overlay that is the scanner's own predicate, in buckets
        servers[0].engine.write_batch(
            [WriteBatchItem(OP_PUT, generate_key(b"zy", b"f9"), b"v", 0)],
            servers[0].engine.last_committed_decree + 1)
        for n in (10, 300, 1500):
            resp = servers[0].on_get_scanner(GetScannerRequest(
                start_key=generate_key(b"user0000000", b""), batch_size=n,
                one_page=True, validate_partition_hash=True))
            assert len(resp.kvs) == min(n, 1000)    # the iteration bound
        m = _engine_counters(servers[0])
        # the load's, the overlay's, pure L1's: none per record
        assert m["compact_path_merge"] == 0 and m["compact_path_bulk"] == 3
        # the overlay's row sorts first (a shorter hashkey): merged into
        # the first block, the undersized second packed with it
        assert m["compact_blocks_spliced"] == 2
        assert m["compact_overlay_rows"] == 1500 + 1
        assert m["compact_rows_dropped_rules"] == 0
        assert compiled[0] == at_submit[0] == at_submit[1] == at_submit[2]
    finally:
        for s in servers:
            s.close()


def _engine_counters(server):
    return {k: v["value"] for k, v in next(
        e["metrics"] for e in METRICS.snapshot()
        if e["type"] == "engine"
        and e["id"] == server.engine.data_dir).items() if "value" in v}


# ---- a small cluster under the mix ---------------------------------------

SMALL_RULES = json.loads(json.dumps(_config_rules())
                         .replace("user0009", "user0000019")
                         .replace("user0008", "user0000018"))


def _small_cell():
    from benchmarks.generator import load_json

    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           "ycsb_rules_p64r3.json")) as f:
        config = json.load(f)
    config.update(partitions=4, records=200)
    traffic = load_json("traffic", "ycsb_e_compact")
    traffic["ops"][2].update(share=0.01)
    traffic["ops"][2]["app_envs"]["user_specified_compaction"] = SMALL_RULES
    return config, traffic


def test_cluster_serves_the_mix_under_triggered_compactions(tmp_path,
                                                            monkeypatch):
    """4 partitions x 3 replicas, records 190-199's field9 rows matched
    by the delete rule, a few hundred windows of the benchmark's mix
    with the operator's share raised: every answer replayed against
    the reference (G1-G3), then G4 on every replica and the counters."""
    from benchmarks.generator import Schedule, op_module
    from benchmarks.harness import Cluster
    from benchmarks.reference import make_records

    from pegasus_tpu.storage import compact_governor

    pool = ManualCompactPool("test-cluster")
    monkeypatch.setattr(compact_governor, "MANUAL_COMPACT_POOL", pool)
    config, traffic = _small_cell()
    seed = 2_900_000_017
    cluster = Cluster(config, str(tmp_path))
    try:
        load_now = epoch_now()
        cluster.load(seed, load_now, None)
        servers = [r.server for rs in cluster.replicas_of for r in rs]
        load_done = max(s.engine.lsm.compact_finish_time for s in servers)
        ctx = {"n_records": config["records"], "fields": config["fields"],
               "field_length": config["field_length"],
               "n_partitions": config["partitions"],
               "next_record": config["records"]}
        schedule = Schedule(traffic, ctx, seed)
        mods = [op_module(o["kind"]) for o in traffic["ops"]]
        record = []
        t_end = time.monotonic() + 3.2     # triggers are a second apart
        windows = 0
        while windows < 300 or time.monotonic() < t_end:
            for k, batch in enumerate(schedule.next_window()):
                if batch:
                    record.append((k, batch,
                                   mods[k].send(cluster.client, batch, ctx)))
            windows += 1
            if windows % 40 == 0:
                cluster.sim.step()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            cluster.sim.step()      # config sync re-delivers a trigger
            # (the governor had deferred some; and a trigger of the
            # second in which the load's own compaction ended counts
            # as satisfied by it: only a later one brings the rules)
            if not any(s._mc_running for s in servers) and all(
                    s._mc_trigger_seen > load_done for s in servers):
                break
            time.sleep(0.05)
        assert not any(s._mc_running for s in servers)
        assert all(s._mc_trigger_seen > load_done for s in servers)

        model = Model(config["partitions"])
        for row in make_records(seed, config["records"], config["fields"],
                                config["field_length"],
                                config["expired_share"], load_now):
            model.put(*row)
        rules = reference_rules.Rules(SMALL_RULES)
        matched = [(hk, sk) for hk, sk, _v, ets in make_records(
            seed, config["records"], config["fields"],
            config["field_length"], config["expired_share"], load_now)
            if ets == 0 and rules.deletes(hk, sk)]
        assert 5 <= len(matched) <= 10      # records 190-199, field9, live
        now_ts = epoch_now()
        kinds = [o["kind"] for o in traffic["ops"]]
        seen = dict.fromkeys(kinds, 0)
        for k, batch, results in record:
            for args, (reply, _took) in zip(batch, results):
                assert reply is not None, kinds[k]
                assert mods[k].check(model, args, reply, now_ts) is None
                seen[kinds[k]] += 1
            for args, (reply, _took) in zip(batch, results):
                mods[k].apply(model, args)
        assert seen["compact"] >= 3 and seen["insert"] > 100
        assert seen["scan_rules"] > 5000
        state = model.rules_state
        assert state["triggered"] and state["first_without"]

        # G4: no replica still holds a matched row, each dropped each once
        for hk, sk in matched:
            p = model.partition_of(hk)
            for r in cluster.replicas_of[p]:
                srv = r.server
                assert srv.engine.get(generate_key(hk, sk)) is None, (
                    hk, sk, p, srv._mc_trigger_seen,
                    srv.engine.lsm.compact_finish_time,
                    srv._compaction_rules)
        per_partition = [0] * config["partitions"]
        for hk, _sk in matched:
            per_partition[model.partition_of(hk)] += 1
        engines = {e["id"]: e["metrics"] for e in METRICS.snapshot()
                   if e["type"] == "engine"}
        for p, rs in enumerate(cluster.replicas_of):
            for r in rs:
                m = engines[r.server.engine.data_dir]
                assert m["compact_rows_dropped_rules"]["value"] \
                    == per_partition[p]
                assert m["compact_path_merge"]["value"] \
                    + m["compact_path_bulk"]["value"] >= 2   # load's + ours
                assert m["compact_rows_ttl_rewritten"]["value"] > 0 \
                    or not any(hk.startswith(b"user0000018")
                               and model.partition_of(hk) == p
                               for hk in model._partition)
        # the bound (the mix sets 1; the three sim nodes share the
        # process's pool) held, and what it deferred ran: started ==
        # finished, nothing waits
        assert pool.running_peak == 1 and pool.running == 0
        m = engines["test-cluster"]
        assert m["compact_deferred"]["value"] > 0
        assert m["compact_started"]["value"] \
            == m["compact_finished"]["value"] >= 12
    finally:
        cluster.close()
