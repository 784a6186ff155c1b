"""Storage engine tests: memtable, WAL, SSTable, LSM, engine discipline."""

import os
import struct

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key, generate_next_bytes
from pegasus_tpu.storage import (
    LSMStore,
    Memtable,
    OP_DEL,
    OP_PUT,
    SSTable,
    SSTableWriter,
    StorageEngine,
    TOMBSTONE,
    WalRecord,
    WriteAheadLog,
    WriteBatchItem,
)


def k(h, s=""):
    return generate_key(h.encode() if isinstance(h, str) else h,
                        s.encode() if isinstance(s, str) else s)


# ---- memtable ---------------------------------------------------------


def test_memtable_basic():
    mt = Memtable()
    mt.put(k("b"), b"v1")
    mt.put(k("a"), b"v2")
    mt.put(k("c"), b"v3", expire_ts=7)
    assert mt.get(k("a")) == (b"v2", 0)
    assert mt.get(k("c")) == (b"v3", 7)
    assert mt.get(k("zzz")) is None
    mt.delete(k("b"))
    assert mt.get(k("b")) == (TOMBSTONE, 0)
    keys = [key for key, _, _ in mt.items_sorted()]
    assert keys == sorted(keys)


def test_memtable_range_and_reverse():
    mt = Memtable()
    for i in range(10):
        mt.put(k("h", "s%02d" % i), b"v%d" % i)
    got = [v for _, v, _ in mt.iterate(k("h", "s03"), k("h", "s07"))]
    assert got == [b"v3", b"v4", b"v5", b"v6"]
    rev = [v for _, v, _ in mt.iterate(k("h", "s03"), k("h", "s07"),
                                       reverse=True)]
    assert rev == [b"v6", b"v5", b"v4", b"v3"]


# ---- WAL --------------------------------------------------------------


def test_memtable_key_order_across_interleaved_writes_and_reads():
    # the order is read between writes of new keys, overwrites and
    # deletes, front, middle and back: every read sees every key once,
    # sorted, and the ranged accessor agrees with a plain sorted list
    mt = Memtable()
    model = {}

    def check():
        want = sorted(model)
        assert mt.keys_in() == want
        assert [(k, v) for k, v, _e in mt.iterate()] == \
            [(k, model[k]) for k in want]
        for lo, hi in ((b"k03", b"k07"), (b"k05", None), (b"", b"k05"),
                       (b"k07", b"k03"), (b"k051", b"k052"), (b"z", None)):
            ranged = [k for k in want
                      if k >= lo and (hi is None or k < hi)]
            assert mt.keys_in(lo, hi) == ranged
            assert [k for k, _v, _e in mt.iterate(lo, hi)] == ranged
            assert [k for k, _v, _e in
                    mt.iterate(lo, hi, reverse=True)] == ranged[::-1]

    check()                                       # empty
    for i in (5, 3, 8):
        mt.put(b"k%02d" % i, b"v%d" % i)
        model[b"k%02d" % i] = b"v%d" % i
    check()
    mt.put(b"k05", b"again")                      # overwrite: no new key
    model[b"k05"] = b"again"
    check()
    mt.delete(b"k04")                             # tombstone of a new key
    model[b"k04"] = None
    mt.put(b"k01", b"front")
    model[b"k01"] = b"front"
    check()
    mt.delete(b"k08")                             # tombstone over a put
    model[b"k08"] = None
    mt.put(b"k09", b"back")
    model[b"k09"] = b"back"
    mt.put(b"k051", b"between")
    model[b"k051"] = b"between"
    check()
    mt.put(b"k04", b"back from the dead")
    model[b"k04"] = b"back from the dead"
    check()
    got = mt.keys_in(b"k03", b"k07")
    got.append(b"mine")                           # a copy, not the order
    assert b"mine" not in mt.keys_in()
    assert len(mt) == len(model) == 7


def test_wal_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append_batch(1, [WalRecord(OP_PUT, k("a"), b"va", 0)])
    wal.append_batch(2, [WalRecord(OP_PUT, k("b"), b"vb", 9),
                         WalRecord(OP_DEL, k("a"), b"", 0)])
    wal.close()

    batches = list(WriteAheadLog.replay(path))
    assert [d for d, _ in batches] == [1, 2]
    assert batches[1][1][1].op == OP_DEL

    # torn tail: append garbage half-frame — replay must stop cleanly
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 1000, 0) + b"short")
    assert [d for d, _ in WriteAheadLog.replay(path)] == [1, 2]

    # corrupt a crc in the middle: replay stops before it
    data = bytearray(open(path, "rb").read())
    data[4] ^= 0xFF  # crc of first frame
    open(path, "wb").write(bytes(data))
    assert list(WriteAheadLog.replay(path)) == []


def test_wal_appends_after_torn_tail_survive(tmp_path):
    # regression: a frame appended after a torn tail must be replayable —
    # the torn garbage is truncated when the WAL reopens.
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append_batch(1, [WalRecord(OP_PUT, k("a"), b"va", 0)])
    wal.close()
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 1000, 0) + b"torn")
    wal2 = WriteAheadLog(path)  # must truncate the garbage
    wal2.append_batch(2, [WalRecord(OP_PUT, k("b"), b"vb", 0)])
    wal2.close()
    assert [d for d, _ in WriteAheadLog.replay(path)] == [1, 2]


# ---- SSTable ----------------------------------------------------------


def test_sstable_roundtrip(tmp_path):
    path = str(tmp_path / "t.sst")
    w = SSTableWriter(path, block_capacity=4, meta={"last_flushed_decree": 42})
    records = [(k("h%02d" % i, "s"), b"val%d" % i, i * 10) for i in range(11)]
    for key, v, e in records:
        w.add(key, v, e)
    w.finish()

    t = SSTable(path)
    assert t.total_count == 11
    assert t.meta["last_flushed_decree"] == 42
    assert len(t.blocks) == 3  # 4+4+3
    for key, v, e in records:
        assert t.get(key) == (v, e)
    assert t.get(k("nope")) is None
    got = list(t.iterate())
    assert [key for key, _, _ in got] == [key for key, _, _ in records]
    # range iterate
    sub = list(t.iterate(k("h03", "s"), k("h07", "s")))
    assert [v for _, v, _ in sub] == [b"val3", b"val4", b"val5", b"val6"]
    # reverse
    rev = [v for _, v, _ in t.iterate(reverse=True)]
    assert rev == [v for _, v, _ in records][::-1]
    t.close()


def test_sstable_hash_lo_column_matches_key_hash(tmp_path):
    # the writer's precomputed crc column IS what validate_hash scans
    # compare against — it must equal pegasus_key_hash's lo lane, including
    # the empty-hashkey fallback
    from pegasus_tpu.base.key_schema import key_hash
    path = str(tmp_path / "t.sst")
    w = SSTableWriter(path, block_capacity=4)
    keys = sorted([k("user%d" % i, "s%d" % i) for i in range(9)]
                  + [k("", "sortonly")])
    for key in keys:
        w.add(key, b"v")
    w.finish()
    t = SSTable(path)
    got = {}
    for _, blk in t.iter_blocks():
        for i in range(blk.count):
            got[blk.key_at(i)] = int(blk.hash_lo[i])
    for key in keys:
        assert got[key] == (key_hash(key) & 0xFFFFFFFF), key
    t.close()


def test_sstable_rejects_unsorted(tmp_path):
    w = SSTableWriter(str(tmp_path / "x.sst"))
    w.add(k("b"), b"v")
    with pytest.raises(ValueError):
        w.add(k("a"), b"v")
    w.abandon()


def test_sstable_tombstone_and_blocks(tmp_path):
    path = str(tmp_path / "t.sst")
    w = SSTableWriter(path, block_capacity=8)
    w.add(k("a"), b"", tombstone=True)
    w.add(k("b"), b"vb", 5)
    w.finish()
    t = SSTable(path)
    assert t.get(k("a")) == (None, 0)
    blocks = list(t.iter_blocks())
    assert len(blocks) == 1
    _, blk = blocks[0]
    assert blk.count == 2
    assert blk.is_tombstone(0) and not blk.is_tombstone(1)
    assert blk.key_at(1) == k("b") and blk.value_at(1) == b"vb"
    t.close()


# ---- LSM --------------------------------------------------------------


def test_lsm_shadowing_and_merge(tmp_path):
    lsm = LSMStore(str(tmp_path / "d"))
    lsm.put(k("a"), b"v1")
    lsm.put(k("b"), b"v1")
    lsm.flush()
    lsm.put(k("a"), b"v2")       # newer L0 shadows older
    lsm.delete(k("b"))
    lsm.flush()
    lsm.put(k("c"), b"v3")       # memtable newest
    assert lsm.get(k("a")) == (b"v2", 0)
    assert lsm.get(k("b")) is None
    assert lsm.get(k("c")) == (b"v3", 0)
    merged = [(key, v) for key, v, _ in lsm.iterate()]
    assert merged == [(k("a"), b"v2"), (k("c"), b"v3")]
    lsm.close()


def test_lsm_compact_drops_tombstones(tmp_path):
    lsm = LSMStore(str(tmp_path / "d"))
    for i in range(20):
        lsm.put(k("h", "s%02d" % i), b"v%d" % i)
    lsm.flush()
    for i in range(0, 20, 2):
        lsm.delete(k("h", "s%02d" % i))
    lsm.compact()
    assert lsm.l1_runs and not lsm.l0 and len(lsm.memtable) == 0
    assert sum(t.total_count for t in lsm.l1_runs) == 10
    assert lsm.get(k("h", "s00")) is None
    assert lsm.get(k("h", "s01")) == (b"v1", 0)
    assert lsm.sorted_runs() is not None
    lsm.put(k("h", "zzz"), b"x")
    assert lsm.sorted_runs() is None  # overlay disqualifies the fast path
    lsm.close()


def test_lsm_reopen(tmp_path):
    d = str(tmp_path / "d")
    lsm = LSMStore(d)
    lsm.put(k("a"), b"v1")
    lsm.flush()
    lsm.put(k("a"), b"v2")
    lsm.flush()
    lsm.close()
    lsm2 = LSMStore(d)
    assert lsm2.get(k("a")) == (b"v2", 0)  # L0 recency order preserved
    lsm2.close()


def test_lsm_crash_between_compact_and_cleanup(tmp_path):
    # simulate a crash after the new L1 landed but before old files were
    # deleted: on reload, obsolete inputs (seq < L1 seq) must be purged so
    # compaction-dropped records don't resurrect.
    import shutil
    d = str(tmp_path / "d")
    lsm = LSMStore(d)
    lsm.put(k("a"), b"old")
    lsm.flush()
    # preserve the pre-compaction files to "restore the crash state" after
    backup = str(tmp_path / "backup")
    shutil.copytree(d, backup)
    lsm.delete(k("a"))
    lsm.compact()  # tombstone drops 'a' entirely
    assert lsm.get(k("a")) is None
    lsm.close()
    # put back the old L0 next to the new L1 (as if removal never ran)
    for name in os.listdir(backup):
        dst = os.path.join(d, name)
        if not os.path.exists(dst):
            shutil.copy(os.path.join(backup, name), dst)
    lsm2 = LSMStore(d)
    assert lsm2.get(k("a")) is None  # old L0 was purged, not resurrected
    assert not lsm2.l0
    lsm2.close()


def test_engine_data_version_recovery_prefers_newest(tmp_path):
    d = str(tmp_path / "e")
    eng = StorageEngine(d, data_version=1)
    eng.write_batch([WriteBatchItem(OP_PUT, k("a"), b"v")], decree=1)
    eng.manual_compact()  # L1 meta: data_version=1, decree=1
    eng.data_version = 2  # schema upgrade
    eng.write_batch([WriteBatchItem(OP_PUT, k("b"), b"v")], decree=2)
    eng.flush()           # L0 meta: data_version=2, decree=2
    eng.close()
    eng2 = StorageEngine(d)
    assert eng2.data_version == 2  # newest watermark wins, not L1's v1
    eng2.close()


# ---- engine -----------------------------------------------------------


def test_engine_decree_discipline_and_recovery(tmp_path):
    d = str(tmp_path / "e")
    eng = StorageEngine(d)
    eng.write_batch([WriteBatchItem(OP_PUT, k("a"), b"va")], decree=1)
    eng.write_batch([WriteBatchItem(OP_PUT, k("b"), b"vb", 9)], decree=2)
    eng.flush()
    assert eng.last_flushed_decree == 2
    eng.write_batch([WriteBatchItem(OP_PUT, k("c"), b"vc")], decree=3)
    eng.write_batch([WriteBatchItem(OP_DEL, k("a"))], decree=4)
    with pytest.raises(ValueError):
        eng.write_batch([WriteBatchItem(OP_PUT, k("x"), b"v")], decree=4)
    eng.close()

    # crash before flush: WAL replay must restore decrees 3-4
    eng2 = StorageEngine(d)
    assert eng2.last_flushed_decree == 2
    assert eng2.last_committed_decree == 4
    assert eng2.get(k("a")) is None
    assert eng2.get(k("b")) == (b"vb", 9)
    assert eng2.get(k("c")) == (b"vc", 0)
    eng2.close()


def test_engine_manual_compact_ttl(tmp_path):
    from pegasus_tpu.base.value_schema import epoch_now
    now = epoch_now()
    eng = StorageEngine(str(tmp_path / "e"))
    items = [
        WriteBatchItem(OP_PUT, k("h", "live"), b"v", expire_ts=now + 10_000),
        WriteBatchItem(OP_PUT, k("h", "dead"), b"v", expire_ts=now - 10),
        WriteBatchItem(OP_PUT, k("h", "eternal"), b"v", expire_ts=0),
    ]
    eng.write_batch(items, decree=1)
    eng.manual_compact(now=now)
    assert eng.get(k("h", "dead")) is None
    assert eng.get(k("h", "live")) is not None
    assert eng.get(k("h", "eternal")) is not None
    assert eng.lsm.l1_runs[0].meta["last_flushed_decree"] == 1
    eng.close()


def test_engine_manual_compact_default_ttl_rewrite(tmp_path):
    eng = StorageEngine(str(tmp_path / "e"))
    eng.write_batch([WriteBatchItem(OP_PUT, k("h", "x"), b"v", expire_ts=0)],
                    decree=1)
    eng.manual_compact(default_ttl=100, now=1000)
    # no-TTL record got expire_ts = now + default_ttl
    assert eng.get(k("h", "x")) == (b"v", 1100)
    eng.close()


def test_engine_manual_compact_stale_split(tmp_path):
    from pegasus_tpu.base.key_schema import key_hash
    eng = StorageEngine(str(tmp_path / "e"))
    pc = 8
    keys = [k("user_%d" % i, "s") for i in range(40)]
    eng.write_batch([WriteBatchItem(OP_PUT, key, b"v") for key in keys],
                    decree=1)
    pidx = 2
    eng.manual_compact(validate_hash=True, pidx=pidx, partition_version=pc - 1)
    for key in keys:
        mine = (key_hash(key) & (pc - 1)) == pidx
        assert (eng.get(key) is not None) == mine
    eng.close()


def test_engine_compact_pv_negative_keeps_all(tmp_path):
    # check_if_stale_split_data: pv < 0 -> keep (opposite of scan path)
    eng = StorageEngine(str(tmp_path / "e"))
    keys = [k("user_%d" % i, "s") for i in range(10)]
    eng.write_batch([WriteBatchItem(OP_PUT, key, b"v") for key in keys],
                    decree=1)
    eng.manual_compact(validate_hash=True, pidx=0, partition_version=-1)
    assert all(eng.get(key) is not None for key in keys)
    eng.close()


def test_multi_run_l1_compaction_and_recovery(tmp_path):
    """Range-capped compaction: output splits into non-overlapping runs,
    reads/scans stay correct, and the manifest makes recovery exact."""
    from pegasus_tpu.storage.lsm import LSMStore

    d = str(tmp_path / "lsm")
    lsm = LSMStore(d, l1_run_capacity=100)
    for i in range(350):
        lsm.put(b"k%05d" % i, b"v%d" % i)
    lsm.flush()
    for i in range(350, 700):
        lsm.put(b"k%05d" % i, b"v%d" % i)
    lsm.flush()
    lsm.compact()
    assert len(lsm.l1_runs) == 7  # 700 records / 100-cap runs
    # non-overlapping + ordered
    for a, b in zip(lsm.l1_runs, lsm.l1_runs[1:]):
        assert a.last_key < b.first_key
    # point reads route to the right run
    for i in (0, 99, 100, 350, 699):
        assert lsm.get(b"k%05d" % i) == (b"v%d" % i, 0)
    # ranged scans merge across run boundaries
    got = [k for k, _v, _e in lsm.iterate(b"k00095", b"k00105")]
    assert got == [b"k%05d" % i for i in range(95, 105)]
    assert lsm.sorted_runs() is not None and len(lsm.sorted_runs()) == 7
    lsm.close()

    # recovery via manifest: all runs come back
    lsm2 = LSMStore(d, l1_run_capacity=100)
    assert len(lsm2.l1_runs) == 7
    assert lsm2.get(b"k00500") == (b"v500", 0)
    # a second compaction after more writes keeps working
    lsm2.put(b"k00500", b"updated")
    lsm2.delete(b"k00000")
    lsm2.flush()
    lsm2.compact()
    assert lsm2.get(b"k00500") == (b"updated", 0)
    assert lsm2.get(b"k00000") is None
    lsm2.close()


def test_manifest_cleans_crash_leftovers(tmp_path):
    """An l1 file not in the manifest (incomplete compaction output) is
    removed at boot; l0 files older than the horizon too."""
    import os

    from pegasus_tpu.storage.lsm import LSMStore

    d = str(tmp_path / "lsm")
    lsm = LSMStore(d, l1_run_capacity=50)
    for i in range(120):
        lsm.put(b"a%04d" % i, b"v")
    lsm.flush()
    lsm.compact()
    runs_before = [os.path.basename(t.path) for t in lsm.l1_runs]
    lsm.close()
    # simulate a crashed compaction: an orphan l1 output + stale l0 input
    open(os.path.join(d, "l1-9999.sst"), "wb").write(b"garbage")
    open(os.path.join(d, "l0-0.sst"), "wb").write(b"garbage")
    lsm2 = LSMStore(d)
    assert sorted(os.path.basename(t.path) for t in lsm2.l1_runs) == \
        sorted(runs_before)
    assert not os.path.exists(os.path.join(d, "l1-9999.sst"))
    assert not os.path.exists(os.path.join(d, "l0-0.sst"))
    assert lsm2.get(b"a0050") == (b"v", 0)
    lsm2.close()


def test_checkpoint_carries_manifest(tmp_path):
    """A checkpoint of a multi-run store restores with ALL runs (the
    manifest travels with the SSTs)."""
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT
    from pegasus_tpu.base.value_schema import generate_value

    eng = StorageEngine(str(tmp_path / "e"))
    eng.lsm._l1_run_capacity = 50
    items = [WriteBatchItem(OP_PUT, b"c%04d" % i,
                            generate_value(1, b"v%d" % i, 0), 0)
             for i in range(160)]
    eng.write_batch(items, 1)
    eng.manual_compact()
    assert len(eng.lsm.l1_runs) > 1
    ck = str(tmp_path / "ckpt")
    eng.checkpoint(ck)
    restored = StorageEngine.restore_from_checkpoint(
        ck, str(tmp_path / "r"))
    assert len(restored.lsm.l1_runs) == len(eng.lsm.l1_runs)
    for i in (0, 70, 159):
        hit = restored.lsm.get(b"c%04d" % i)
        assert hit is not None
    eng.close()
    restored.close()


def test_empty_compaction_keeps_seq_horizon(tmp_path):
    """Review regression: an all-tombstone compaction leaves no .sst files;
    the next boot must still honor the manifest's seq horizon or freshly
    flushed L0 files get deleted as 'consumed compaction inputs'."""
    from pegasus_tpu.storage.lsm import LSMStore

    d = str(tmp_path / "lsm")
    lsm = LSMStore(d)
    lsm.put(b"k", b"v")
    lsm.flush()
    lsm.delete(b"k")
    lsm.flush()
    lsm.compact()
    assert not lsm.l1_runs  # everything dropped
    lsm.close()

    lsm2 = LSMStore(d)
    lsm2.put(b"new", b"data")
    lsm2.flush()
    lsm2.close()

    lsm3 = LSMStore(d)  # the boot that used to eat the fresh flush
    assert lsm3.get(b"new") == (b"data", 0)
    lsm3.close()


def test_auto_flush_and_compact_bound_growth(tmp_path):
    """A write-heavy engine flushes at the memtable trigger and compacts
    at the L0 trigger without any manual call (the rocksdb write-buffer +
    level-0 trigger parity)."""
    from pegasus_tpu.base.value_schema import generate_value
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    eng = StorageEngine(str(tmp_path / "e"))
    eng.memtable_flush_trigger = 500
    d = 0
    for batch in range(12):
        items = [WriteBatchItem(
            OP_PUT, b"a%06d" % (batch * 200 + i),
            generate_value(1, b"v", 0), 0) for i in range(200)]
        d += 1
        eng.write_batch(items, d)
    # flush trigger fired (memtable bounded) and at least one compaction
    assert len(eng.lsm.memtable) < 500
    assert eng._ev_flush_count._value >= 3
    assert eng._ev_compact_count._value >= 1
    assert len(eng.lsm.l0) < 4 + 1
    # everything still readable
    assert eng.get(b"a000000") is not None
    assert eng.get(b"a%06d" % (12 * 200 - 1)) is not None
    eng.close()


def test_usage_scenarios_rewire_maintenance(tmp_path):
    from pegasus_tpu.server.partition_server import PartitionServer

    srv = PartitionServer(str(tmp_path / "p"))
    srv.update_app_envs({"rocksdb.usage_scenario": "bulk_load"})
    assert srv.engine.auto_compact is False
    assert srv.engine.memtable_flush_trigger == 500_000
    srv.update_app_envs({"rocksdb.usage_scenario": "prefer_write"})
    assert srv.engine.auto_compact and srv.engine.lsm._l0_trigger == 8
    srv.update_app_envs({"rocksdb.usage_scenario": "normal"})
    assert srv.engine.lsm._l0_trigger == 4
    import pytest as _pytest

    with _pytest.raises(ValueError):
        srv.update_app_envs({"rocksdb.usage_scenario": "warp_speed"})
    srv.close()
