"""The read-modify-write deployment (YCSB workload F on the headline
cluster, config `ycsb_f_p64r3`): a small cluster answering check_and_set
read-modify-writes like benchmarks/reference_rmw.py, with several of
one row and more than PIPELINE_DEPTH atomic ops of one partition in one
call; atomic writes queued behind the 2PC window in submission order,
and refused past the queue's bound; the mix served and replayed like
the cell's, with its control."""

import json
import os

import numpy as np
import pytest

from benchmarks import faults
from benchmarks.generator import Schedule, load_json, op_module
from benchmarks.ops import rmw
from benchmarks.reference import (Model, epoch_now, hashkey_of, make_records,
                                  make_values, sortkey_of)
from benchmarks.reference_rmw import OK, TRY_AGAIN, rmw_rounds
from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.replica import ReplicaBusyError, WriteOp
from pegasus_tpu.rpc.codec import OP_CAS, OP_INCR, OP_PUT
from pegasus_tpu.server.types import (CasCheckType, CheckAndSetRequest,
                                      IncrRequest)
from pegasus_tpu.utils.metrics import METRICS

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 4_000_000_019


def _config(name, **cut):
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           name + ".json")) as f:
        return dict(json.load(f), **cut)


def _write(name):
    return sum(e["metrics"][name]["value"] for e in METRICS.snapshot()
               if e["type"] == "write" and name in e["metrics"])


def test_config_is_the_headline_cluster_and_the_mix_is_workloadf():
    f, e = _config("ycsb_f_p64r3"), _config("ycsb_p64r3")
    for key in ("table", "partitions", "replicas", "nodes", "records",
                "fields", "field_length", "expired_share", "chips"):
        assert f[key] == e[key], key
    for name, text in e["guarantees"].items():
        assert f["guarantees"][name] == text        # word for word
    assert set(f["guarantees"]) - set(e["guarantees"]) \
        == {"rmw_atomic", "rmw_visible", "rmw_last"}
    assert sorted(f["reduced"]) == ["records", "transport"]
    assert f["reduced"]["transport"] \
        == _config("ycsb_a_p64r3")["reduced"]["transport"]
    assert len(f["source"]) <= 200 and "workloadf" in f["source"]
    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(c for c in bench["configs"] if c["name"] == f["name"])
    assert entry["source"] == f["source"]
    assert sorted(entry["reduced"]) == sorted(f["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == "ycsb_f.p64r3")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ycsb_f_p64r3", "ycsb_f", 1)

    mix = load_json("traffic", "ycsb_f")
    assert (mix["loop"], mix["clients"], mix["window_ops"],
            mix["warmup_windows"], mix["trace_slice_s"]) \
        == ("closed", 1, 32, 1000, [2.0, 3.0])
    zipf = {"dist": "scrambled_zipfian", "theta": 0.99}
    assert [(o["kind"], o["role"], o["share"], o["key"], o["field"])
            for o in mix["ops"]] == [
        ("get", "read", 0.5, zipf, {"dist": "uniform"}),
        ("rmw", "write", 0.5, zipf, {"dist": "uniform"})]
    assert mix["trace_probe"] == load_json("traffic", "ycsb_a")["trace_probe"]


# ---- the reference --------------------------------------------------------


def test_reference_orders_the_rmws_of_one_row_by_submission():
    rounds, after = rmw_rounds(b"v0", [b"a", b"b", b"c"])
    assert rounds == [[(OK, b"v0")],
                      [(TRY_AGAIN, b"a"), (OK, b"a")],
                      [(TRY_AGAIN, b"a"), (TRY_AGAIN, b"b"), (OK, b"b")]]
    assert after == b"c"
    # no row: the first writes it under CT_VALUE_NOT_EXIST
    assert rmw_rounds(None, [b"x", b"y"]) == (
        [[(OK, None)], [(TRY_AGAIN, b"x"), (OK, b"x")]], b"y")
    # out of rounds: the last RMW never lands
    rounds, after = rmw_rounds(b"v0", [b"a", b"b", b"c"], max_rounds=2)
    assert [r[-1][0] for r in rounds] == [OK, OK, TRY_AGAIN] and after == b"b"


# ---- the system against the reference ------------------------------------


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from benchmarks.harness import Cluster

    config = _config("ycsb_f_p64r3", partitions=8, records=200)
    c = Cluster(config, str(tmp_path_factory.mktemp("f")))
    load_now = epoch_now()
    c.load(SEED, load_now, None)
    model = Model(config["partitions"])
    for row in make_records(SEED, config["records"], config["fields"],
                            config["field_length"], config["expired_share"],
                            load_now):
        model.put(*row)
    yield c, model
    c.close()


def _rmws(rows, rng, n_partitions, last):
    """rmw args for (record, field) rows, in this order."""
    values = make_values(rng, len(rows), 100)
    out = []
    for (r, f), value in zip(rows, values):
        hk, sk = hashkey_of(r), sortkey_of(f)
        ph = key_hash_parts(hk, sk)
        out.append((hk, sk, value, generate_key(hk, sk), ph,
                    ph % n_partitions, last))
    return out


def _replay(model, batch, results):
    """The harness's replay of one call: check every answer against the
    model, then apply the acknowledged RMWs."""
    now = epoch_now()
    wrong = [rmw.check(model, a, reply, now)
             for a, (reply, _t) in zip(batch, results) if reply is not None]
    for a, (reply, _t) in zip(batch, results):
        if reply is not None:
            rmw.apply(model, a)
            rmw.readback(a)
    return [w for w in wrong if w is not None]


def test_rmws_answer_like_the_reference(cluster):
    """Seeded random rows, each call with two and three RMWs of one row
    and, in one call, five atomic ops of one partition: every answer is
    the reference's, in submission order, and no op was refused busy
    (the client never backed off)."""
    c, model = cluster
    n_part = c.config["partitions"]
    rng = np.random.default_rng(SEED)
    last = {}
    queued, checks_failed = _write("atomic_queued"), \
        _write("atomic_check_failed")
    applied, rejects = _write("atomic_ops"), _write("atomic_busy_rejects")
    sent = 0
    by_part = {}
    for r in range(c.config["records"]):
        by_part.setdefault(key_hash_parts(hashkey_of(r)) % n_part,
                           []).append(r)
    five = [(r, 1) for r in max(by_part.values(), key=len)[:5]]
    calls = [five] + [
        [(int(r), int(f)) for r, f in zip(rng.integers(0, 200, 10),
                                          rng.integers(0, 10, 10))]
        for _ in range(12)]
    for n, rows in enumerate(calls):
        if n:   # a row twice and another three times, interleaved
            a, b = rows[0], rows[1]
            rows = rows[:2] + [a] + rows[2:6] + [b, a] + rows[6:]
        batch = _rmws(rows, rng, n_part, last)
        results = rmw.send(c.client, batch, {})
        assert all(reply is not None for reply, _t in results)
        assert _replay(model, batch, results) == []
        sent += len(batch)
        if n:
            # the row sent three times: OK in round 1, 2 and 3
            chain = [results[i][0][1] for i in (0, 2, 8)]
            assert [[s for s, _v in rounds] for rounds in chain] == [
                [OK], [TRY_AGAIN, OK], [TRY_AGAIN, TRY_AGAIN, OK]]
    assert c.client.backoff.slept == []
    assert _write("atomic_busy_rejects") == rejects
    assert _write("atomic_queued") > queued     # the five waited
    assert _write("atomic_check_failed") > checks_failed
    # counted once an op at its primary, every round of every RMW
    rounds = _write("atomic_ops") - applied
    assert sent < rounds <= sent * 3
    from benchmarks.harness import _read_back

    assert _read_back(c.client, list(last.values()), c.config) == 0


def _primary(c, record, field):
    hk, sk = hashkey_of(record), sortkey_of(field)
    pidx = key_hash_parts(hk, sk) % c.config["partitions"]
    return c.primary_of[pidx], generate_key(hk, sk), hk, sk


def _fill_window(r, tag):
    """PIPELINE_DEPTH rounds in flight, none acked yet (the sim loop is
    not run), so the next write queues."""
    for i in range(r.PIPELINE_DEPTH):
        assert r.client_write([WriteOp(OP_PUT, (
            generate_key(b"fill-" + tag, b"%d" % i), b"x", 0))]) > 0


def test_put_queued_behind_a_queued_check_and_set_applies_after_it(cluster):
    c, model = cluster
    now = epoch_now()
    record, field = next((r, f) for r in range(7, 200) for f in range(10)
                         if model.get(hashkey_of(r), sortkey_of(f), now))
    r, key, hk, sk = _primary(c, record, field)
    old = model.get(hk, sk, now)
    _fill_window(r, b"cas")
    replies = []
    cas = CheckAndSetRequest(hk, sk, int(CasCheckType.CT_VALUE_BYTES_EQUAL),
                             old, set_sort_key=sk, set_value=b"by cas",
                             return_check_value=True)
    assert r.client_write([WriteOp(OP_CAS, cas)],
                          lambda res: replies.append(("cas", res))) == -1
    assert r.client_write([WriteOp(OP_PUT, (key, b"by put", 0))],
                          lambda res: replies.append(("put", res))) == -1
    # the put started a batch of its own behind the atomic op
    assert [n for n, _cb in r._write_queue] == [1, 1]
    c.sim.loop.run_until_idle()
    c.sim.step(rounds=2)
    assert [tag for tag, _res in replies] == ["cas", "put"]
    assert replies[0][1][0].error == OK
    assert replies[0][1][0].check_value == old
    assert c.client.get(hk, sk) == (0, b"by put")
    model.put(hk, sk, b"by put", 0)


def test_atomic_queue_past_its_bound_is_refused_typed(cluster):
    c, _model = cluster
    r, _key, _hk, _sk = _primary(c, 3, 0)
    counter = generate_key(b"counter", b"c")
    _fill_window(r, b"incr")
    rejects = _write("atomic_busy_rejects")
    got = []
    for _ in range(r.MAX_BATCH_OPS):
        assert r.client_write([WriteOp(OP_INCR, IncrRequest(counter, 1, 0))],
                              lambda res: got.append(res[0].new_value)) == -1
    with pytest.raises(ReplicaBusyError):
        r.client_write([WriteOp(OP_INCR, IncrRequest(counter, 1, 0))])
    assert _write("atomic_busy_rejects") == rejects + 1
    c.sim.loop.run_until_idle()
    c.sim.step(rounds=2)
    # each queued increment applied once, in order
    assert got == list(range(1, r.MAX_BATCH_OPS + 1))
    assert not r._write_queue and r._queued_atomic == 0


# ---- the mix, served and replayed -----------------------------------------


def _serve_and_replay(tmp_path, fault, windows=60):
    """A small cluster under the cell's own mix, replayed as
    benchmarks/harness.py replays a window: each call's answers checked
    against the reference before its writes are applied to it, then the
    read-back of every row an RMW touched, from the primary."""
    from benchmarks.harness import Cluster, _read_back

    config = _config("ycsb_f_p64r3", partitions=8, records=300)
    traffic = load_json("traffic", "ycsb_f")
    seed = 4_000_000_023
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
        record = []
        for w in range(windows):
            for k, batch in enumerate(schedule.next_window()):
                if batch:
                    record.append((k, batch, mods[k].send(client, batch,
                                                          ctx)))
            if w % 20 == 19:
                cluster.sim.step()
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
                "slept": list(cluster.client.backoff.slept)}
    finally:
        cluster.close()


def test_cluster_serves_the_mix_like_the_reference(tmp_path):
    res = _serve_and_replay(tmp_path, None)
    assert [res[c] for c in ("wrong_answers", "failed_ops",
                             "missing_readbacks",
                             "replica_decree_spread")] == [0, 0, 0, 0]
    assert res["done"]["get"] > 700 and res["done"]["rmw"] > 700
    assert res["slept"] == []


def test_cluster_with_lost_writes_comes_out_not_correct(tmp_path):
    """The control: an RMW answered OK that was never stored is a wrong
    answer to the next read of its row, or a missing read-back."""
    res = _serve_and_replay(tmp_path, "lost_write")
    assert res["failed_ops"] == 0
    assert res["wrong_answers"] > 0 and res["missing_readbacks"] > 0
