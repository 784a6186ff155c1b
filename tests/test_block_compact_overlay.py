"""The block path over an overlay (storage/lsm.py bulk_compact_snapshot,
Splice): a store with L0 tables — chained by key range, or overlapping
L1 and each other — is compacted block-wise, and gives row for row and
counter for counter what `LSMStore.compact` (the per-record merge)
gives over the same snapshot.

Every case builds one store, copies it, compacts one copy on each path
in snapshot mode (`publish_lock` given, as PartitionServer.manual_compact
calls it) at one pinned `now`, and compares the visible rows (key, value
with its patched expire_ts header, expire_ts) and the engine's counters.
"""

import json
import math
import shutil
import threading

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import extract_expire_ts, generate_value
from pegasus_tpu.ops.compaction_rules import compile_rules
from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
from pegasus_tpu.storage.lsm import CHAIN_MIN_BLOCKS, Splice
from pegasus_tpu.storage.wal import OP_DEL, OP_PUT
from pegasus_tpu.utils.metrics import METRICS

CAP = 64
NOW = 400_000_000
COUNTERS = ("compact_rows_in", "compact_rows_dropped_rules",
            "compact_rows_dropped_ttl", "compact_rows_ttl_rewritten")

# the shape of benchmarks/configs/ycsb_rules_p64r3.json's ruleset, on
# hashkeys every store below holds
RULES = [
    {"op": "delete_key", "rules": [
        {"type": "hashkey_pattern", "pattern": "user0001",
         "match": "prefix"},
        {"type": "sortkey_pattern", "pattern": "field9",
         "match": "prefix"}]},
    {"op": "update_ttl", "update_ttl_type": "from_now", "value": 2592000,
     "rules": [{"type": "hashkey_pattern", "pattern": "user0003",
                "match": "prefix"}]},
]


def _key(i: int) -> bytes:
    """Four ids a record (fields 80, 81, 90, 91), 200 ids a hashkey
    prefix `user0000`, `user0001`, ...: in key order."""
    return generate_key(b"user%04d%04d" % (i // 200, i % 200 // 4),
                        b"field%d%d" % (8 + i // 2 % 2, i % 2))


def _put(i: int, gen: int = 0) -> WriteBatchItem:
    # one row in seven has run out, one in five never expires
    ets = NOW - 50 if i % 7 == 3 else (0 if i % 5 == 0 else NOW + 10_000)
    return WriteBatchItem(
        OP_PUT, _key(i), generate_value(1, b"v%d.%d" % (i, gen) * 3, ets),
        ets)


def _del(i: int) -> WriteBatchItem:
    return WriteBatchItem(OP_DEL, _key(i))


class _Builder:
    def __init__(self, path: str) -> None:
        self.eng = StorageEngine(path, block_capacity=CAP,
                                 values_carry_expire_header=True)
        self.eng.auto_compact = False
        self.decree = 0

    def flush(self, items) -> None:
        for off in range(0, len(items), 500):
            self.decree += 1
            self.eng.write_batch(items[off:off + 500], self.decree)
        self.eng.flush()

    def to_l1(self) -> None:
        self.eng.manual_compact(now=1)   # nothing has run out at 1


def _lone_l0(b):
    b.flush([_put(i) for i in range(0, 1000, 2)])


def _three_chained_l0(b):
    for lo in (0, 700, 1400):
        b.flush([_put(i) for i in range(lo, lo + 600, 2)])


def _l0_after_l1(b):
    b.flush([_put(i) for i in range(0, 1000, 2)])
    b.to_l1()
    b.flush([_put(i) for i in range(1000, 1600, 2)])      # chains


def _small_l0_after_l1(b):
    b.flush([_put(i) for i in range(0, 1000, 2)])
    b.to_l1()
    b.flush([_put(i) for i in range(1000, 1060, 2)])      # packs


def _l0_interleaved(b):
    b.flush([_put(i) for i in range(0, 4000, 2)])
    b.to_l1()
    b.flush([_put(i) for i in range(1, 2000, 38)]           # new between
            + [_put(i, gen=1) for i in range(0, 2000, 46)]  # updates
            + [_del(i) for i in range(10, 2000, 106)]       # tombstones
            + [_del(4001), _del(777)])                      # of absent keys


def _two_overlapping_l0(b):
    b.flush([_put(i) for i in range(0, 1200, 2)])
    b.flush([_put(i, gen=1) for i in range(300, 900, 3)]
            + [_del(i) for i in range(302, 900, 30)])


def _overlapping_l0_over_l1(b):
    b.flush([_put(i) for i in range(0, 1600, 2)])
    b.to_l1()
    b.flush([_put(i, gen=1) for i in range(100, 1700, 5)])
    b.flush([_put(i, gen=2) for i in range(50, 1500, 7)]
            + [_del(i) for i in range(100, 1700, 40)])


# shape -> (builder, blocks chained > 0, blocks spliced > 0)
SHAPES = {
    "lone_l0": (_lone_l0, True, False),
    "three_chained_l0": (_three_chained_l0, True, False),
    "l0_after_l1": (_l0_after_l1, True, False),
    "small_l0_after_l1": (_small_l0_after_l1, True, True),
    "l0_interleaved": (_l0_interleaved, True, True),
    "two_overlapping_l0": (_two_overlapping_l0, True, True),
    "overlapping_l0_over_l1": (_overlapping_l0_over_l1, False, True),
}

FILTERS = {
    "no_rule": {},
    "default_ttl": {"default_ttl": 3600},
    "rules": {"rules_filter": compile_rules(json.dumps(RULES))},
    "stale_split": {"validate_hash": True, "partition_version": 3,
                    "pidx": 1},
}


def _counters(eng) -> dict:
    return {k: v["value"] for k, v in next(
        e["metrics"] for e in METRICS.snapshot()
        if e["type"] == "engine" and e["id"] == eng.data_dir).items()
        if "value" in v}


def _compact(path: str, merge_path: bool, **kwargs):
    """Compact the store at `path` in snapshot mode on one path ->
    (rows, counters, blocks a run)."""
    eng = StorageEngine(path, block_capacity=CAP,
                        values_carry_expire_header=True)
    if merge_path:
        eng.lsm.bulk_compact_snapshot = lambda frozen=False: None
    before = _counters(eng)     # a registry entry outlives its engine
    eng.manual_compact(now=NOW, publish_lock=threading.Lock(), **kwargs)
    assert not eng.lsm.l0
    rows = list(eng.iterate())
    for _k, v, e in rows:
        assert extract_expire_ts(1, v) == e   # the header is patched
    blocks = [[bm.count for bm in t.blocks] for t in eng.lsm.l1_runs]
    m = {k: v - before[k] for k, v in _counters(eng).items()}
    eng.close()
    return rows, m, blocks


@pytest.mark.parametrize("filt", list(FILTERS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_block_path_equals_merge_path(tmp_path, shape, filt):
    build, chained, spliced = SHAPES[shape]
    b = _Builder(str(tmp_path / "src"))
    build(b)
    n_l0 = len(b.eng.lsm.l0)
    b.eng.close()
    shutil.copytree(str(tmp_path / "src"), str(tmp_path / "merge"))
    kwargs = FILTERS[filt]

    rows, m, blocks = _compact(str(tmp_path / "src"), False, **kwargs)
    rows_ref, m_ref, _ = _compact(str(tmp_path / "merge"), True, **kwargs)

    assert n_l0 >= 1
    assert m["compact_path_bulk"] == 1 and m["compact_path_merge"] == 0
    assert m_ref["compact_path_merge"] == 1
    assert (m["compact_blocks_chained"] > 0) == chained
    assert (m["compact_blocks_spliced"] > 0) == spliced
    assert (m["compact_overlay_rows"] > 0) == spliced
    assert len(rows) > 100
    assert rows == rows_ref
    if filt == "stale_split":
        # the block path counts every row it drops; the per-record path
        # leaves stale split rows uncounted (engine._merge_record_filter)
        assert m["compact_rows_in"] == m_ref["compact_rows_in"]
        assert m["compact_rows_dropped_rules"] == 0
        assert m["compact_rows_dropped_ttl"] \
            == m["compact_rows_in"] - len(rows) \
            > m_ref["compact_rows_dropped_ttl"] > 0
    else:
        assert {c: m[c] for c in COUNTERS} \
            == {c: m_ref[c] for c in COUNTERS}
        assert m["compact_rows_in"] - len(rows) \
            == m["compact_rows_dropped_rules"] + m["compact_rows_dropped_ttl"]
    if filt == "rules":
        assert m["compact_rows_dropped_rules"] > 0
        assert m["compact_rows_ttl_rewritten"] > 0
    if filt == "default_ttl":
        assert m["compact_rows_ttl_rewritten"] > 0
    # no block over capacity, and a splice leaves all but its last full
    assert all(n <= CAP for run in blocks for n in run)


def test_snapshot_splits_tables_by_key_range(tmp_path):
    """Which tables chain and which are spliced is read off the
    snapshot's own key ranges and sizes."""
    b = _Builder(str(tmp_path / "s"))
    lsm = b.eng.lsm
    assert lsm.bulk_compact_snapshot() is None        # nothing on disk
    _lone_l0(b)
    snap = lsm.bulk_compact_snapshot()
    assert snap.chain == lsm.l0 and not snap.overlay and not snap.runs
    b.to_l1()
    assert lsm.bulk_compact_eligible() and not lsm.l0
    b.flush([_put(i) for i in range(1000, 1600, 2)])     # after L1: chains
    b.flush([_put(i) for i in range(1600, 1640, 2)])     # small: spliced
    assert len(lsm.l0[0].blocks) < CHAIN_MIN_BLOCKS <= len(lsm.l0[1].blocks)
    b.flush([_put(i, gen=1) for i in range(0, 1000, 3)])  # overlaps L1
    snap = lsm.bulk_compact_snapshot()
    assert snap.chain == lsm.l1_runs + [lsm.l0[2]]
    assert snap.overlay == lsm.l0[:2] and snap.l0 == lsm.l0
    entries = lsm.bulk_compact_entries(snap)
    assert any(isinstance(e, Splice) for e in entries)
    assert any(not isinstance(e, Splice) for e in entries)
    # a live memtable: only a caller that froze it may take the path
    b.eng.write_batch([_put(5000)], b.decree + 1)
    assert not lsm.bulk_compact_eligible()
    assert lsm.bulk_compact_eligible(frozen=True)
    b.eng.close()


def test_writes_between_freeze_and_publish_survive(tmp_path):
    """Snapshot mode: a write (and a flush of it) that lands after the
    freeze flush neither changes the path nor is lost at publish; it
    keeps shadowing the new base."""
    b = _Builder(str(tmp_path / "s"))
    _l0_interleaved(b)
    eng, lsm = b.eng, b.eng.lsm
    late = [_put(0, gen=7), _put(4001, gen=7), _del(4)]
    flushed = [_put(2, gen=8), _put(4003, gen=8)]
    real_rewrite = lsm.bulk_compact_rewrite

    def rewrite(per_block, *args, **kwargs):
        # while the compaction runs off the write lock: one batch is
        # flushed to a newer L0, one stays in the memtable
        eng.write_batch(flushed, b.decree + 2)
        eng.flush()
        eng.write_batch(late, b.decree + 3)
        return real_rewrite(per_block, *args, **kwargs)

    lsm.bulk_compact_rewrite = rewrite
    # a write after the freeze flush, before the path is chosen
    eng.write_batch([_put(6, gen=9)], b.decree + 1)
    eng.manual_compact(now=NOW, publish_lock=threading.Lock())
    m = _counters(eng)
    assert m["compact_path_bulk"] == 2 and m["compact_path_merge"] == 0
    assert len(lsm.l0) == 1 and len(lsm.memtable) == 3
    for item in flushed + late[:2] + [_put(6, gen=9)]:
        assert eng.get(item.key) == (item.value, item.expire_ts)
    assert eng.get(_key(4)) is None
    assert eng.get(_key(8))[0] == _put(8).value     # the compacted base
    eng.close()
    # the manifest and the WAL agree with what was served
    eng = StorageEngine(str(tmp_path / "s"), block_capacity=CAP)
    for item in flushed + late[:2]:
        assert eng.get(item.key) == (item.value, item.expire_ts)
    assert eng.get(_key(4)) is None
    eng.close()


@pytest.mark.parametrize("rows_a_pass", [10, 50, 150])
def test_insert_then_compact_leaves_no_trail_of_small_blocks(
        tmp_path, rows_a_pass):
    """20 passes of insert-then-compact, the inserts numbered on from
    the loaded rows as a YCSB insert is: the run holds at most
    ceil(rows / capacity) + 1 blocks after every pass."""
    b = _Builder(str(tmp_path / "s"))
    b.flush([_put(i) for i in range(0, 1000)])
    nxt = 1000
    for _ in range(20):
        b.flush([_put(i) for i in range(nxt, nxt + rows_a_pass)])
        nxt += rows_a_pass
        b.eng.manual_compact(now=1, publish_lock=threading.Lock())
        (run,) = b.eng.lsm.l1_runs
        assert run.total_count == nxt
        assert len(run.blocks) <= math.ceil(nxt / CAP) + 1
    assert _counters(b.eng)["compact_path_merge"] == 0
    assert [k for k, _v, _e in b.eng.iterate()] \
        == sorted(_key(i) for i in range(nxt))
    b.eng.close()


def test_chained_tombstones_never_reach_l1(tmp_path):
    """A chained L0 table's tombstones (deletes of keys no older table
    holds) drop on the block path as bottommost tombstones do, counted
    as no row."""
    b = _Builder(str(tmp_path / "s"))
    b.flush([_put(i) if i % 4 else _del(i) for i in range(0, 600)])
    assert len(b.eng.lsm.l0[0].blocks) >= CHAIN_MIN_BLOCKS
    b.eng.manual_compact(now=1, publish_lock=threading.Lock())
    m = _counters(b.eng)
    assert m["compact_path_bulk"] == 1 and m["compact_blocks_spliced"] == 0
    assert m["compact_rows_in"] == 450 and m["compact_rows_dropped_ttl"] == 0
    for run in b.eng.lsm.l1_runs:
        for i in range(len(run.blocks)):
            assert not np.any(run.read_block(i).flags)
    assert len(list(b.eng.iterate())) == 450
    b.eng.close()


def test_pool_is_for_stores_of_more_than_one_window(tmp_path, monkeypatch,
                                                    started_threads):
    """compact_partitions_parallel compacts a store of at most one
    pipeline window of blocks on the calling thread (its compaction is
    interpreter-bound: a pool only queues for the lock), a larger one
    on the pool; one window also runs its stages inline."""
    from pegasus_tpu.client.table import compact_partitions_parallel
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.storage import compact_pipeline

    servers = [PartitionServer(str(tmp_path / f"p{i}")) for i in range(3)]
    where = {}
    try:
        def noting(s, real):
            def manual_compact(**kw):
                where[id(s)] = threading.current_thread().name
                real(**kw)
            return manual_compact

        for n, s in zip((300, 300, 5000), servers):
            for i in range(n):
                s.on_put(_key(i), b"v%d" % i)
            s.manual_compact = noting(s, s.manual_compact)
        # a window of 2 blocks: 300 rows are inside one, 5,000 are not
        monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", 2)
        compact_partitions_parallel(servers)
        here = threading.current_thread().name
        assert where[id(servers[0])] == where[id(servers[1])] == here
        assert where[id(servers[2])] != here
        assert all(s.engine.lsm.l1_runs and not s.engine.lsm.l0
                   for s in servers)
        # the stage threads started once: for the store of two windows
        assert compact_pipeline.stage_threads_enabled()
        assert started_threads.count("compact-read") == 1
    finally:
        for s in servers:
            s.close()


@pytest.mark.parametrize("seed", range(6))
def test_random_overlays_equal_the_merge_path(tmp_path, seed):
    """Flushes of random ranges, strides, updates and deletes, before
    and after a compaction to L1: whatever the snapshot's shape, both
    paths keep the same rows and count the same."""
    rng = np.random.default_rng(seed)
    b = _Builder(str(tmp_path / "src"))
    gen = 0
    for step in range(int(rng.integers(2, 7))):
        lo = int(rng.integers(0, 3000))
        ids = range(lo, lo + int(rng.integers(20, 900)),
                    int(rng.integers(1, 6)))
        gen += 1
        b.flush([_del(i) if rng.random() < 0.15 else _put(i, gen=gen)
                 for i in ids])
        if step and rng.random() < 0.3:
            b.to_l1()
    if not b.eng.lsm.l0:
        b.flush([_put(i, gen=99) for i in range(0, 3000, 17)])
    b.eng.close()
    shutil.copytree(str(tmp_path / "src"), str(tmp_path / "merge"))
    kwargs = FILTERS["rules" if seed % 2 else "default_ttl"]
    rows, m, blocks = _compact(str(tmp_path / "src"), False, **kwargs)
    rows_ref, m_ref, _ = _compact(str(tmp_path / "merge"), True, **kwargs)
    assert m["compact_path_bulk"] == 1 and m_ref["compact_path_merge"] == 1
    assert rows == rows_ref
    assert {c: m[c] for c in COUNTERS} == {c: m_ref[c] for c in COUNTERS}
    assert all(n <= CAP for run in blocks for n in run)
