"""The update-heavy deployment (YCSB workload A on the headline
cluster, config `ycsb_a_p64r3`): a small cluster serving the
benchmark's own mix with every answer replayed against the plain
reference, the `update` op kind's read-back rule, and the three
always-on counters the cell's per-layer metrics read."""

import json
import os

import numpy as np
import pytest

from benchmarks import faults
from benchmarks.generator import Schedule, load_json, op_module
from benchmarks.ops import update
from benchmarks.reference import Model, epoch_now, make_records
from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.server.partition_server import PartitionServer
from pegasus_tpu.utils.errors import StorageStatus
from pegasus_tpu.utils.metrics import METRICS

HERE = os.path.dirname(os.path.abspath(__file__))
OK = int(StorageStatus.OK)


def _config(name):
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _storage(name):
    return sum(e["metrics"][name]["value"] for e in METRICS.snapshot()
               if e["type"] == "storage" and name in e["metrics"])


def test_config_is_the_headline_cluster_and_the_mix_is_workloada():
    a, e = _config("ycsb_a_p64r3"), _config("ycsb_p64r3")
    for key in ("table", "partitions", "replicas", "nodes", "records",
                "fields", "field_length", "expired_share", "chips"):
        assert a[key] == e[key], key
    for name, text in e["guarantees"].items():
        assert a["guarantees"][name] == text        # word for word
    assert set(a["guarantees"]) - set(e["guarantees"]) \
        == {"update_visible", "update_last"}
    assert sorted(a["reduced"]) == ["records", "transport"]
    assert len(a["source"]) <= 200
    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == a["name"])
    assert entry["source"] == a["source"]
    assert sorted(entry["reduced"]) == sorted(a["reduced"])

    mix = load_json("traffic", "ycsb_a")
    assert (mix["loop"], mix["clients"], mix["window_ops"],
            mix["warmup_windows"]) == ("closed", 1, 32, 1000)
    zipf = {"dist": "scrambled_zipfian", "theta": 0.99}
    assert [(o["kind"], o["role"], o["share"], o["key"], o["field"])
            for o in mix["ops"]] == [
        ("get", "read", 0.5, zipf, {"dist": "uniform"}),
        ("update", "write", 0.5, zipf, {"dist": "uniform"})]
    assert mix["trace_probe"] == load_json("traffic", "ycsb_c")["trace_probe"]


def _serve_and_replay(tmp_path, fault, windows=120):
    """A small cluster under the cell's own mix, replayed as
    benchmarks/harness.py replays a window: each call's answers checked
    against the reference before its writes are applied to it, then the
    read-back of every updated row from the primary."""
    from benchmarks.harness import Cluster, _read_back

    config = dict(_config("ycsb_a_p64r3"), partitions=8, records=300)
    traffic = load_json("traffic", "ycsb_a")
    seed = 3_200_000_017
    cluster = Cluster(config, str(tmp_path))
    try:
        load_now = epoch_now()
        cluster.load(seed, load_now, fault)
        client = faults.wrap_client(cluster.client, fault)
        ctx = {"n_records": config["records"], "fields": config["fields"],
               "field_length": config["field_length"],
               "n_partitions": config["partitions"],
               "next_record": config["records"]}
        schedule = Schedule(traffic, ctx, seed)
        mods = [op_module(o["kind"]) for o in traffic["ops"]]
        kinds = [o["kind"] for o in traffic["ops"]]
        counters = ("point_keys_resolved", "point_overlay_hits")
        before = {n: _storage(n) for n in counters}
        record = []
        for w in range(windows):
            for k, batch in enumerate(schedule.next_window()):
                if batch:
                    record.append((k, batch, mods[k].send(client, batch,
                                                          ctx)))
            if w % 40 == 39:
                cluster.sim.step()
        moved = {n: _storage(n) - before[n] for n in counters}
        spread = cluster.decree_spread()

        model = Model(config["partitions"])
        for row in make_records(seed, config["records"], config["fields"],
                                config["field_length"],
                                config["expired_share"], load_now):
            model.put(*row)
        now_ts = epoch_now()
        done = dict.fromkeys(kinds, 0)
        wrong = failed = 0
        acked_rows = []
        for k, batch, results in record:
            for args, (reply, _took) in zip(batch, results):
                if reply is None:
                    failed += 1
                elif mods[k].check(model, args, reply, now_ts) is not None:
                    wrong += 1
                else:
                    done[kinds[k]] += 1
            for args, (reply, _took) in zip(batch, results):
                if reply is not None:
                    mods[k].apply(model, args)
                    acked_rows += mods[k].readback(args)
        missing = _read_back(cluster.client, acked_rows, config)
        return {"wrong_answers": wrong, "failed_ops": failed,
                "missing_readbacks": missing,
                "replica_decree_spread": spread, "done": done,
                "acked_rows": acked_rows, "moved": moved}
    finally:
        cluster.close()


def test_cluster_serves_the_mix_like_the_reference(tmp_path):
    """8 partitions x 3 replicas, 300 records, 120 windows of the mix:
    every check the cell is held to reads 0, one read-back row a key,
    and the memtable answers gets of rows the run itself updated."""
    res = _serve_and_replay(tmp_path, None)
    assert [res[c] for c in ("wrong_answers", "failed_ops",
                             "missing_readbacks",
                             "replica_decree_spread")] == [0, 0, 0, 0]
    assert res["done"]["get"] > 1500 and res["done"]["update"] > 1500
    keys = [(hk, sk) for hk, sk, _v in res["acked_rows"]]
    assert len(keys) == len(set(keys)) < res["done"]["update"]
    # one plan a partition a call resolves each of its distinct keys
    assert 0 < res["moved"]["point_overlay_hits"] \
        <= res["moved"]["point_keys_resolved"] <= res["done"]["get"]


def test_cluster_with_lost_writes_comes_out_not_correct(tmp_path):
    """The control: an acknowledged update that was never stored is a
    wrong answer to the next get of its row, or a missing read-back."""
    res = _serve_and_replay(tmp_path, "lost_write")
    assert res["failed_ops"] == 0
    assert res["wrong_answers"] > 0 and res["missing_readbacks"] > 0


def _same_key_updates(n, ctx=None):
    ctx = ctx if ctx is not None else {}
    ctx.update(n_records=1, fields=1, field_length=8, n_partitions=4)
    rng = np.random.default_rng(5)
    spec = {"key": {"dist": "uniform"}, "field": {"dist": "uniform"}}
    return update.draw(rng, rng, n, spec, ctx)


def test_readback_is_one_row_a_key_at_its_last_acknowledged_value():
    first, lost, third = _same_key_updates(3)
    assert first[:2] == lost[:2] == third[:2]
    assert len({first[2], lost[2], third[2]}) == 3
    # the harness's replay: acknowledged operations only, in order
    rows = []
    rows += update.readback(first)
    rows += update.readback(third)      # `lost` was never acknowledged
    assert rows == [[first[0], first[1], third[2]]]
    model = Model(4)
    for args in (first, third):
        update.apply(model, args)
    assert model.get(first[0], first[1], 1) == third[2]


def test_readback_rows_belong_to_the_run_that_drew_them():
    (one,) = _same_key_updates(1)
    (other,) = _same_key_updates(1)     # another run: its own ctx
    assert update.readback(one) == [[one[0], one[1], one[2]]]
    assert update.readback(other) == [[other[0], other[1], other[2]]]
    ctx = {}
    a, b = _same_key_updates(1, ctx) + _same_key_updates(1, ctx)
    assert update.readback(a) and update.readback(b) == []


@pytest.fixture
def server(tmp_path):
    s = PartitionServer(str(tmp_path / "p0"))
    yield s
    s.close()


def _get(server, key):
    (reply,) = server.on_point_read_batch([("get", key, None)])
    return reply


def test_update_drops_the_cached_row_and_the_next_get_reads_it(server):
    key = generate_key(b"user00000001", b"field3")
    other = generate_key(b"user00000002", b"field3")
    server.on_put(key, b"loaded")
    server.flush()
    server.manual_compact()
    for _ in range(2):          # two base-resolved misses admit the row
        assert _get(server, key) == (OK, b"loaded")
    hits = _storage("row_cache_hit")
    assert _get(server, key) == (OK, b"loaded")
    assert _storage("row_cache_hit") == hits + 1
    dropped = _storage("row_cache_invalidated_rows")
    server.on_put(other, b"never cached")   # offered, nothing to drop
    assert _storage("row_cache_invalidated_rows") == dropped
    server.on_put(key, b"updated")
    assert _storage("row_cache_invalidated_rows") == dropped + 1
    assert _get(server, key) == (OK, b"updated")
    assert _storage("row_cache_hit") == hits + 1


def test_point_overlay_hits_count_the_memtable_not_the_row_cache(server):
    cached = generate_key(b"user00000001", b"field0")
    fresh = generate_key(b"user00000001", b"field1")
    server.on_put(cached, b"base")
    server.flush()
    server.manual_compact()
    resolved, overlay = (_storage("point_keys_resolved"),
                         _storage("point_overlay_hits"))
    for _ in range(3):          # miss, miss + admit, row-cache hit
        assert _get(server, cached) == (OK, b"base")
    assert _storage("point_keys_resolved") == resolved + 3
    assert _storage("point_overlay_hits") == overlay
    server.on_put(fresh, b"in the memtable")
    # one plan, the same key twice: resolved once, answered twice
    assert server.on_point_read_batch(
        [("get", fresh, None), ("get", fresh, None),
         ("get", cached, None)]) == [(OK, b"in the memtable")] * 2 \
        + [(OK, b"base")]
    assert _storage("point_keys_resolved") == resolved + 5
    assert _storage("point_overlay_hits") == overlay + 1
