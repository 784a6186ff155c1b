"""The tenant scan (config `ycsb_prefixscan_p64r3`, mix `prefix_scan`):
a full-table scan under a hashkey-prefix filter through
ClusterClient.get_unordered_scanners, against the plain reference
(benchmarks/reference_prefix.py), on a small cluster: 8 partitions x 3
replicas, 3,000 records (30 tenants of 100; ~3,750 rows and 4 blocks a
partition, so every scanner pages). Every live row of the tenant once
and no other, each partition's in key order, across pages; with
expired rows, deleted rows, rows still in the memtable, rows in L0 and
an empty tenant. And what the cell's per-layer metrics read: the
paging path's counters, the mask cache's, and the dispatch span.
"""

import json
import os

import numpy as np
import pytest

from benchmarks.generator import load_json
from benchmarks.harness import Cluster
from benchmarks.ops import scan_prefix
from benchmarks.reference import (Model, epoch_now, hashkey_of,
                                  make_records, sortkey_of)
from benchmarks.reference_prefix import (n_tenants, prefix_of, prefix_rows,
                                         records_of, records_per_tenant)
from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
from pegasus_tpu.client.client import ScanOptions
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX
from pegasus_tpu.server.scan_coordinator import STACK_CHUNK
from pegasus_tpu.server.types import GetScannerRequest
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 3_600_000_011
PARTS = 8
RECORDS = 3000
WIDTH = 10      # bytes of a tenant's prefix: `user` + 6 digits


def _config(name):
    with open(os.path.join(HERE, "..", "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


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


def _args(pattern: bytes, batch_size: int = 1000, split: int = PARTS):
    return (pattern, ScanOptions(batch_size=batch_size,
                                 hash_key_filter_type=FT_MATCH_PREFIX,
                                 hash_key_filter_pattern=pattern), split)


def _pass(cluster, args):
    (reply, took), = scan_prefix.send(cluster.client, [args], {})
    assert took > 0
    return reply


# ---- the files --------------------------------------------------------


def test_config_is_the_headline_cluster_and_states_the_scan_guarantees():
    s, e = _config("ycsb_prefixscan_p64r3"), _config("ycsb_p64r3")
    for key in ("table", "partitions", "replicas", "nodes", "records",
                "fields", "field_length", "expired_share", "chips"):
        assert s[key] == e[key], key
    for name, text in e["guarantees"].items():
        assert s["guarantees"][name] == text        # word for word
    own = set(s["guarantees"]) - set(e["guarantees"])
    assert own == {"scan_complete", "scan_order", "scan_primaries"}
    assert [s["guarantees"][n][:2] for n in sorted(own)] \
        == ["S1", "S2", "S3"]
    assert sorted(s["reduced"]) == ["records", "transport"]
    assert {"tenant", "max_split_count", "batch_size", "values"} \
        <= set(s["assumed"])
    assert len(s["source"]) <= 200
    with open(os.path.join(HERE, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == s["name"])
    assert entry["source"] == s["source"]
    assert sorted(entry["reduced"]) == sorted(s["reduced"])
    cell = next(w for w in bench["workloads"]
                if w["name"] == "prefix_scan.p64r3")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (s["name"], "prefix_scan", 1)
    assert len(cell["why"]) <= 200


def test_mix_is_the_issues():
    mix = load_json("traffic", "prefix_scan")
    assert (mix["loop"], mix["clients"], mix["window_ops"],
            mix["warmup_windows"]) == ("closed", 1, 2, 8)
    assert "trace_probe" not in mix
    assert mix["trace_slice_s"][0] == 2.0
    (op,) = mix["ops"]
    assert (op["kind"], op["role"], op["share"], op["key"],
            op["prefix_bytes"], op["max_split_count"], op["batch_size"]) \
        == ("scan_prefix", "read", 1.0, {"dist": "uniform"}, 10, 64, 1000)
    # 1,000 tenants of 100 records at the config's size
    s = _config("ycsb_prefixscan_p64r3")
    assert n_tenants(s["records"], op["prefix_bytes"]) == 1000
    assert records_per_tenant(op["prefix_bytes"]) == 100


def test_reference_imports_nothing_of_the_program():
    for name in ("reference_prefix.py", "reference.py"):
        with open(os.path.join(HERE, "..", "benchmarks", name)) as f:
            assert "pegasus_tpu" not in f.read().replace(
                "Imports nothing from pegasus_tpu", "")
    with open(os.path.join(HERE, "..", "benchmarks", "ops",
                           "scan_prefix.py")) as f:
        imported = [ln for ln in f.read().splitlines()
                    if "pegasus_tpu" in ln and "import" in ln]
    # only what the op kind needs to *send*
    assert imported == [
        "from pegasus_tpu.client.client import ScanOptions",
        "from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX"]


# ---- the reference, by hand -------------------------------------------


@pytest.mark.parametrize("pattern, first, count", [
    (b"user000000", 0, 100), (b"user000123", 12300, 100),
    (b"user0001", 10000, 10000), (b"user00000007", 7, 1),
    (b"user9", 9 * 10 ** 7, 10 ** 7)])
def test_a_pattern_is_a_range_of_records(pattern, first, count):
    r = records_of(pattern)
    assert (r.start, len(r)) == (first, count)
    assert hashkey_of(r.start).startswith(pattern)
    assert hashkey_of(r[-1]).startswith(pattern)
    assert r.start == 0 or not hashkey_of(r.start - 1).startswith(pattern)
    assert not hashkey_of(r[-1] + 1).startswith(pattern)


@pytest.mark.parametrize("pattern", [b"usex000001", b"user00000a",
                                     b"user000000001", b"us", b"user"])
def test_a_pattern_the_reference_cannot_number_is_refused(pattern):
    with pytest.raises(ValueError):
        records_of(pattern)


def test_tenants_of_a_table():
    assert prefix_of(123, 10) == b"user000123"
    assert prefix_of(0, 12) == b"user00000000"
    assert (n_tenants(100_000, 10), n_tenants(400, 10), n_tenants(401, 10),
            n_tenants(3, 12)) == (1000, 4, 5, 3)
    with pytest.raises(ValueError):
        prefix_of(1, 4)
    drawn = scan_prefix.draw(
        np.random.default_rng(1), None, 50,
        {"key": {"dist": "uniform"}, "prefix_bytes": 10,
         "max_split_count": 64, "batch_size": 1000}, {"n_records": 400})
    assert {a[0] for a in drawn} == {prefix_of(t, 10) for t in range(4)}
    assert all(a[1].hash_key_filter_pattern == a[0]
               and a[1].hash_key_filter_type == FT_MATCH_PREFIX
               and a[1].batch_size == 1000 and a[2] == 64 for a in drawn)


def _hand_model():
    """4 partitions; records 100..102 and 110 of tenant `user000001`,
    record 200 of the next; one row expired, one with a TTL to come."""
    model = Model(4)
    for r in (100, 101, 102, 110, 200):
        for j in range(3):
            model.put(hashkey_of(r), sortkey_of(j), b"v%d.%d" % (r, j), 0)
    model.put(hashkey_of(101), sortkey_of(1), b"gone", 50)    # expired
    model.put(hashkey_of(102), sortkey_of(2), b"later", 500)  # not yet
    return model


def test_reference_rows_by_partition_in_key_order():
    model = _hand_model()
    want = prefix_rows(model, b"user000001", now=100)
    rows = [row for p in sorted(want) for row in want[p]]
    assert len(rows) == 4 * 3 - 1
    assert (hashkey_of(101), sortkey_of(1), b"gone") not in rows
    assert (hashkey_of(102), sortkey_of(2), b"later") in rows
    assert not any(hk == hashkey_of(200) for hk, _sk, _v in rows)
    for p, part in want.items():
        assert part and all(model.partition_of(hk) == p
                            for hk, _sk, _v in part)
        assert part == sorted(part)     # hashkeys of one length
    assert prefix_rows(model, b"user000003", now=100) == {}
    assert sum(map(len, prefix_rows(model, b"user0000", 100).values())) \
        == 5 * 3 - 1


# a fault in a sound reply: `b` the scanner with the most rows, `o`
# another one
CHECK_FAULTS = {
    "a row missing": lambda reply, b, o: reply[b].pop(0),
    "a row twice": lambda reply, b, o: reply[b].insert(0, reply[b][0]),
    "two rows swapped": lambda reply, b, o: reply[b].__setitem__(
        slice(0, 2), reply[b][1::-1]),
    "a value altered": lambda reply, b, o: reply[b].__setitem__(
        0, reply[b][0][:2] + (b"other",)),
    "another tenant's row": lambda reply, b, o: reply[b].append(
        (hashkey_of(200), sortkey_of(0), b"v200.0")),
    "a row under the wrong scanner": lambda reply, b, o: reply[o].append(
        reply[b].pop()),
    "a scanner missing": lambda reply, b, o: reply.pop(),
}


@pytest.mark.parametrize("fault", [None] + sorted(CHECK_FAULTS))
def test_check_holds_s1_and_s2(fault):
    model = _hand_model()
    want = prefix_rows(model, b"user000001", now=100)
    reply = [list(want.get(p, [])) for p in range(4)]
    big = max(range(4), key=lambda p: len(reply[p]))
    assert len(reply[big]) >= 2
    if fault is not None:
        CHECK_FAULTS[fault](reply, big, (big + 1) % 4)
    why = scan_prefix.check(model, _args(b"user000001", split=4), reply, 100)
    assert (why is None) == (fault is None), why
    if fault is None:
        # fewer scanners than partitions: scanner i holds i, i + n, ...
        two = [[row for p in range(i, 4, 2) for row in want.get(p, [])]
               for i in range(2)]
        assert scan_prefix.check(model, _args(b"user000001", split=2),
                                 two, 100) is None


# ---- the deployment ---------------------------------------------------


def _deploy(tmp):
    config = dict(_config("ycsb_prefixscan_p64r3"), partitions=PARTS,
                  records=RECORDS)
    cluster = Cluster(config, str(tmp))
    try:
        load_now = epoch_now()
        cluster.load(SEED, load_now, None)
    except BaseException:
        cluster.close()
        raise
    rows = list(make_records(SEED, RECORDS, config["fields"],
                             config["field_length"],
                             config["expired_share"], load_now))
    return cluster, rows


def _model(rows, deleted=()):
    model = Model(PARTS)
    for hk, sk, value, ets in rows:
        if (hk, sk) not in deleted:
            model.put(hk, sk, value, ets)
    return model


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """The table as the cell's set-up leaves it: every row in L1."""
    tracing.reset()
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    cluster, rows = _deploy(tmp_path_factory.mktemp("pscan_l1"))
    yield cluster, _model(rows)
    FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    cluster.close()
    tracing.reset()


def _n_blocks(cluster):
    return sum(len(run.blocks) for r in cluster.primary_of
               for run in r.server.engine.lsm.l1_runs)


@pytest.mark.parametrize("pattern, batch_size, split", [
    (prefix_of(0, WIDTH), 1000, PARTS),     # a page ends by rows examined
    (prefix_of(7, WIDTH), 17, PARTS),       # ... and by rows returned
    (prefix_of(29, WIDTH), 1000, 3),        # 3 scanners over 8 partitions
    (prefix_of(12, WIDTH), 1, 1),           # one scanner, a row a page
    (b"user00000", 250, PARTS),             # 10 tenants at once
    (prefix_of(999, WIDTH), 1000, PARTS),   # an empty tenant
    (hashkey_of(1234), 1000, PARTS),        # one record
])
def test_pass_over_l1_returns_the_tenants_rows(loaded, pattern, batch_size,
                                               split):
    cluster, model = loaded
    args = _args(pattern, batch_size, split)
    reply = _pass(cluster, args)
    now = epoch_now()
    assert scan_prefix.check(model, args, reply, now) is None
    want = prefix_rows(model, pattern, now)
    n_rows = sum(map(len, want.values()))
    assert sum(map(len, reply)) == n_rows
    loaded_rows = 10 * len(set(records_of(pattern)) & set(range(RECORDS)))
    # a tenth of the loaded rows had expired before the load
    assert 0.8 * loaded_rows <= n_rows <= (0.97 * loaded_rows
                                           if loaded_rows > 50
                                           else loaded_rows)


def test_paging_counters_count_contexts_and_pages(loaded, monkeypatch):
    cluster, model = loaded
    assert _n_blocks(cluster) >= 3 * PARTS      # so every scanner pages
    seen = []
    read = cluster.client._read

    def counted(op, args, pidx, **kw):
        resp = read(op, args, pidx, **kw)
        seen.append((op, resp.context_id))
        return resp

    monkeypatch.setattr(cluster.client, "_read", counted)
    before = _counters("storage")
    args = _args(prefix_of(3, WIDTH))
    assert scan_prefix.check(model, args, _pass(cluster, args),
                             epoch_now()) is None
    d = _delta("storage", before)
    opened = sum(1 for op, cid in seen if op == "get_scanner" and cid >= 0)
    pages = sum(1 for op, _cid in seen if op == "scan")
    assert opened == PARTS and pages >= 2 * PARTS
    assert d["scan_contexts_opened"] == opened
    assert d["scan_pages_served"] == pages
    # a page ends after the block that spends the iteration budget
    assert _n_blocks(cluster) - PARTS <= pages <= _n_blocks(cluster)
    # each page counts its whole look-ahead window of blocks again
    assert d["scan_rows_evaluated"] > 2 * 0.9 * 10 * RECORDS
    assert d["scan_rows_returned"] == sum(
        map(len, prefix_rows(model, args[0], epoch_now()).values()))

    # a scan answered in one page leaves no context and serves no page
    seen.clear()
    before = _counters("storage")
    rows = list(cluster.client.get_scanner(hashkey_of(5)))
    assert 8 <= len(rows) <= 10
    d = _delta("storage", before)
    assert seen == [("get_scanner", -1)]
    assert "scan_contexts_opened" not in d and "scan_pages_served" not in d

    # a context the server no longer holds is no page served
    before = _counters("storage")
    resp = cluster.client.scan_page(0, 987654321)
    assert resp.context_id == -2
    assert "scan_pages_served" not in _delta("storage", before)


def test_mask_cache_counters_count_hits_and_misses(loaded):
    cluster, model = loaded
    n_blocks = _n_blocks(cluster)
    # a partition of b blocks: at most ceil(b / STACK_CHUNK) programs
    most_programs = sum(
        -(-sum(len(run.blocks) for run in r.server.engine.lsm.l1_runs)
          // STACK_CHUNK) for r in cluster.primary_of)
    fresh, other = _args(prefix_of(20, WIDTH)), _args(prefix_of(21, WIDTH))

    def moved(args):
        before, programs = _counters("storage"), _counters("engine")
        assert scan_prefix.check(model, args, _pass(cluster, args),
                                 epoch_now()) is None
        d = _delta("storage", before)
        return (d.get("mask_cache_hit", 0), d.get("mask_cache_miss", 0),
                d.get("mask_fill_blocks", 0),
                _delta("engine", programs).get("mask_programs", 0))

    hit1, miss1, fill1, programs1 = moved(fresh)
    # a fresh pattern: every block's mask is computed once, a window's
    # misses and the blocks that fill their stack in one program, and
    # looked up again by each later page whose window still holds it
    assert miss1 + fill1 == n_blocks and hit1 > 0
    assert PARTS <= programs1 <= most_programs
    # the same pattern again: the same look-ups, every one a hit
    assert moved(fresh) == (hit1 + miss1, 0, 0, 0)
    # another fresh pattern misses as the first did
    assert moved(other) == (hit1, miss1, fill1, programs1)


def test_batched_scan_path_counts_its_mask_lookups_too(loaded):
    cluster, _model_ = loaded
    groups = {}
    for r in (40, 41, 1500, 2999):
        hk = hashkey_of(r)
        req = GetScannerRequest(
            start_key=generate_key(hk, b""), start_inclusive=True,
            batch_size=20, validate_partition_hash=True, one_page=True,
            hash_key_filter_type=FT_MATCH_PREFIX,
            hash_key_filter_pattern=b"user0000")
        groups.setdefault(key_hash_parts(hk) % PARTS, []).append(req)
    before = _counters("storage")
    cluster.client.scan_multi(groups)
    d = _delta("storage", before)
    first = d.get("mask_cache_hit", 0) + d.get("mask_cache_miss", 0)
    assert first > 0 and d.get("mask_cache_miss", 0) > 0
    before = _counters("storage")
    cluster.client.scan_multi(groups)
    d = _delta("storage", before)
    assert d.get("mask_cache_hit", 0) == first
    assert "mask_cache_miss" not in d


def test_a_traced_pass_has_a_dispatch_share_and_self_times_sum(loaded):
    cluster, model = loaded
    args = _args(prefix_of(25, WIDTH))      # fresh: programs dispatched
    before = _counters("layer")
    spans0 = _counters("tracing").get("span_count", 0)
    programs = _counters("engine")
    FLAGS.set("pegasus.tracing", "sample_ratio", 1.0)
    try:
        reply = _pass(cluster, args)
    finally:
        FLAGS.set("pegasus.tracing", "sample_ratio", 0.0)
    assert scan_prefix.check(model, args, reply, epoch_now()) is None
    d = _delta("layer", before)
    n_spans = _counters("tracing")["span_count"] - spans0
    traced = d.pop("traced_us")
    assert traced > 0 and n_spans > 0
    # the call of the mask programs and the wait for their masks
    assert d["dispatch_self_us"] > 0
    # one thread: every root's time is some frame's or stage's self
    # time, up to the remainders the counters hold back
    assert abs(sum(d.values()) - traced) <= n_spans
    assert d.get("other_self_us", 0) == 0
    assert {"client", "rpc", "gate", "coord", "decode", "dispatch"} \
        <= {k[:-len("_self_us")] for k in d}
    moved = _delta("engine", programs)
    assert moved["mask_programs_traced"] == moved["mask_programs"] > 0
    assert moved["mask_bytes_traced"] == moved["mask_bytes"] > 0


# ---- with an overlay: deletes, memtable rows, then L0 -----------------

TENANT = 3              # records 300..399
NEW_TENANT = 30         # records 3000..: not loaded, written below


@pytest.fixture(scope="module")
def overlaid(tmp_path_factory):
    """The loaded table with, on top of it in the memtables: rows of
    tenant 3 deleted (half a record, a whole record), a new sortkey, an
    overwritten value, an expired row written again, a row with a TTL
    to come; and a tenant that exists in the memtables alone."""
    cluster, rows = _deploy(tmp_path_factory.mktemp("pscan_overlay"))
    try:
        cl = cluster.client
        deleted = set()
        for j in range(5):
            assert cl.delete(hashkey_of(310), sortkey_of(j)) == 0
            deleted.add((hashkey_of(310), sortkey_of(j)))
        for j in range(10):
            assert cl.delete(hashkey_of(311), sortkey_of(j)) == 0
            deleted.add((hashkey_of(311), sortkey_of(j)))
        model = _model(rows, deleted)
        now = epoch_now()
        expired = next((hk, sk) for hk, sk, _v, ets in rows
                       if ets and hashkey_of(320) <= hk < hashkey_of(400))
        writes = [(hashkey_of(312), b"fieldA", b"a new sortkey", 0),
                  (hashkey_of(313), sortkey_of(2), b"overwritten", 0),
                  (expired[0], expired[1], b"written again", 0),
                  (hashkey_of(314), sortkey_of(0), b"with a ttl", 1000)]
        writes += [(hashkey_of(3000 + r), sortkey_of(j), b"new %d.%d" % (r, j),
                    0) for r in range(3) for j in range(4)]
        for hk, sk, value, ttl in writes:
            assert cl.set(hk, sk, value, ttl_seconds=ttl) == 0
            model.put(hk, sk, value, now + ttl if ttl else 0)
        yield cluster, model
    finally:
        cluster.close()


OVERLAY_CASES = [
    (prefix_of(TENANT, WIDTH), 1000), (prefix_of(TENANT, WIDTH), 23),
    (prefix_of(NEW_TENANT, WIDTH), 1000), (prefix_of(5, WIDTH), 1000),
    (prefix_of(999, WIDTH), 1000), (b"user00000", 300)]


def _check_overlaid(cluster, model, pattern, batch_size):
    args = _args(pattern, batch_size)
    reply = _pass(cluster, args)
    now = epoch_now()
    assert scan_prefix.check(model, args, reply, now) is None
    got = {(hk, sk): v for rows in reply for hk, sk, v in rows}
    if pattern == prefix_of(TENANT, WIDTH):
        assert not any(hk == hashkey_of(311) for hk, _sk in got)
        assert {sk for hk, sk in got if hk == hashkey_of(310)} \
            <= {sortkey_of(j) for j in range(5, 10)}
        assert got[(hashkey_of(312), b"fieldA")] == b"a new sortkey"
        assert got[(hashkey_of(313), sortkey_of(2))] == b"overwritten"
        assert got[(hashkey_of(314), sortkey_of(0))] == b"with a ttl"
        assert b"written again" in got.values()
    if pattern == prefix_of(NEW_TENANT, WIDTH):
        assert len(got) == 12
    if pattern == prefix_of(999, WIDTH):
        assert got == {}


@pytest.mark.parametrize("pattern, batch_size", OVERLAY_CASES)
def test_pass_over_memtable_rows_and_tombstones(overlaid, pattern,
                                                batch_size):
    cluster, model = overlaid
    assert any(len(r.server.engine.lsm.memtable) for r in cluster.primary_of)
    _check_overlaid(cluster, model, pattern, batch_size)


@pytest.mark.parametrize("pattern, batch_size", OVERLAY_CASES[:4])
def test_pass_over_l0_rows_and_tombstones(overlaid, pattern, batch_size):
    cluster, model = overlaid
    for rs in cluster.replicas_of:
        for r in rs:
            r.server.flush()
    assert not any(len(r.server.engine.lsm.memtable)
                   for r in cluster.primary_of)
    _check_overlaid(cluster, model, pattern, batch_size)


def test_control_a_lost_loaded_row_is_a_wrong_pass(tmp_path):
    """The cell's control: 1 loaded row in 997 acknowledged and never
    stored, so a tenant of ~1,000 rows nearly always misses one."""
    config = dict(_config("ycsb_prefixscan_p64r3"), partitions=PARTS,
                  records=400)
    cluster = Cluster(config, str(tmp_path))
    try:
        load_now = epoch_now()
        cluster.load(SEED, load_now, "lost_write")
        model = Model(PARTS)
        for row in make_records(SEED, 400, 10, 100, 0.1, load_now):
            model.put(*row)
        wrong = 0
        for t in range(4):
            args = _args(prefix_of(t, WIDTH))
            wrong += scan_prefix.check(model, args, _pass(cluster, args),
                                       epoch_now()) is not None
        assert wrong >= 2
    finally:
        cluster.close()
