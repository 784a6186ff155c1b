"""Filtered-read layer tests: per-SSTable bloom filters (build /
persist / probe / legacy degrade), batched probe pruning on the
point-read path, the node row cache (admission, write-through and
publish invalidation, byte cap), and the block-cache LRU fix.

The load-bearing regressions: a bloom may never produce a FALSE
NEGATIVE (results must stay byte-identical to the unfiltered path),
and the row cache may never serve a value a completed write replaced.
"""

import gc
import os
import sys
import threading
import time

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import generate_value
from pegasus_tpu.server import PartitionServer
from pegasus_tpu.server.page import probe_nat
from pegasus_tpu.server.row_cache import ROW_CACHE, RowCache
from pegasus_tpu.storage.bloom import BloomFilter
from pegasus_tpu.storage.lsm import LSMStore
from pegasus_tpu.storage.sstable import (
    BLOCK_OBJECT_BYTES,
    SSTable,
    SSTableWriter,
    block_cache_budget,
)
from pegasus_tpu.utils.errors import StorageStatus
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS

OK = int(StorageStatus.OK)
NOT_FOUND = int(StorageStatus.NOT_FOUND)


@pytest.fixture
def server(tmp_path):
    s = PartitionServer(str(tmp_path / "p0"))
    yield s
    s.close()


@pytest.fixture
def no_row_cache():
    old = FLAGS.get("pegasus.server", "row_cache_bytes")
    FLAGS.set("pegasus.server", "row_cache_bytes", 0)
    yield
    FLAGS.set("pegasus.server", "row_cache_bytes", old)


def _write_sst(path, n, tag=b"k"):
    w = SSTableWriter(str(path))
    for i in range(n):
        w.add(tag + b"%08d" % i, b"v%d" % i, 0)
    w.finish()
    return SSTable(str(path))


# ---- bloom filter core ------------------------------------------------


def test_bloom_roundtrip_and_fp_rate(tmp_path):
    """Persisted filter reloads with the run; every present key passes
    (no false negatives, ever); absent-key FP rate under a bound that
    ~10 bits/key comfortably meets (theory ~0.8%)."""
    t = _write_sst(tmp_path / "a.sst", 5000)
    assert t.bloom is not None
    for i in range(0, 5000, 17):
        assert t.bloom.may_contain(b"k%08d" % i)
    absent = [b"x%08d" % i for i in range(4000)]
    fps = sum(t.bloom.may_contain(k) for k in absent)
    assert fps / len(absent) < 0.03
    # the vectorized batch probe agrees with the scalar probe
    from pegasus_tpu.ops.predicates import bloom_key_hashes, bloom_probe_rows

    sample = [b"k%08d" % i for i in range(0, 200, 7)] + absent[:200]
    hs = bloom_key_hashes(sample)
    batch = bloom_probe_rows(t.bloom, hs)
    scalar = np.array([t.bloom.may_contain(k) for k in sample])
    assert (batch == scalar).all()
    t.close()


def test_multi_probe_matches_scalar():
    """The one-call (keys x filters) matrix — native when built, scalar
    fallback otherwise — must agree cell-for-cell with per-filter
    scalar probes."""
    from pegasus_tpu.storage.bloom import MultiProbe

    rng = np.random.default_rng(3)
    filters = []
    for t in range(5):
        hs = rng.integers(1, 2**63, size=200 + 37 * t).astype(np.uint64)
        filters.append(BloomFilter.build(hs, 10))
    mp = MultiProbe(filters)
    probes = rng.integers(1, 2**63, size=64).astype(np.uint64)
    mat = mp.probe(probes)
    assert len(mat) == 64 * 5
    for i, h in enumerate(probes):
        for t, f in enumerate(filters):
            assert mat[i * 5 + t] == f.may_contain_hash(int(h))
    # fallback path agrees with whatever path mp took
    mp2 = MultiProbe(filters)
    mp2._native = None
    assert mp2.probe(probes) == mat


def test_bloom_bytes_roundtrip():
    hashes = np.arange(1, 1001, dtype=np.uint64) * np.uint64(0x9E3779B9)
    bf = BloomFilter.build(hashes, 10)
    bf2 = BloomFilter.from_bytes(bf.to_bytes(), bf.m, bf.k)
    assert (bf2.may_contain_hashes(hashes)).all()
    assert BloomFilter.from_bytes(bf.to_bytes()[:-1], bf.m, bf.k) is None


def test_legacy_sst_without_filter_still_readable(tmp_path):
    """Files written with filters off (pre-existing data) load with
    bloom=None and serve exactly as before; a store mixing filtered and
    filterless runs answers correctly for both."""
    FLAGS.set("pegasus.server", "bloom_bits_per_key", 0)
    try:
        legacy = _write_sst(tmp_path / "legacy.sst", 100)
    finally:
        FLAGS.set("pegasus.server", "bloom_bits_per_key", 10)
    assert legacy.bloom is None
    assert legacy.may_contain(b"anything")  # filterless: always maybe
    assert legacy.get(b"k%08d" % 3) == (b"v3", 0)
    legacy.close()

    store = LSMStore(str(tmp_path / "mixed"))
    FLAGS.set("pegasus.server", "bloom_bits_per_key", 0)
    try:
        for i in range(50):
            store.put(b"old%04d" % i, b"ov%d" % i)
        store.flush()
    finally:
        FLAGS.set("pegasus.server", "bloom_bits_per_key", 10)
    for i in range(50):
        store.put(b"new%04d" % i, b"nv%d" % i)
    store.flush()
    assert store.l0[0].bloom is not None and store.l0[1].bloom is None
    for i in range(50):
        assert store.get(b"old%04d" % i) == (b"ov%d" % i, 0)
        assert store.get(b"new%04d" % i) == (b"nv%d" % i, 0)
        assert store.get(b"abs%04d" % i) is None
    store.close()


def test_bloom_built_for_flush_compact_and_bulk_outputs(server):
    """Acceptance: flush, merge-compaction, and bulk block-level
    compaction outputs all carry filters."""
    for i in range(600):
        server.on_put(generate_key(b"hk%04d" % i, b"s"), b"v%d" % i)
    server.flush()
    lsm = server.engine.lsm
    assert all(t.bloom is not None for t in lsm.l0)
    server.manual_compact()  # the flush's L0, block-wise, into L1
    assert lsm.l1_runs and all(r.bloom is not None for r in lsm.l1_runs)
    assert lsm.bulk_compact_eligible()
    server.manual_compact()  # bulk block-level rewrite path
    assert lsm.l1_runs and all(r.bloom is not None for r in lsm.l1_runs)
    # filters answer for the compacted keys
    assert all(r.get(b"absent") is None for r in lsm.l1_runs)
    err, v = server.on_get(generate_key(b"hk0007", b"s"))
    assert (err, v) == (OK, b"v7")


def test_batched_identity_filtered_vs_unfiltered(server, no_row_cache):
    """The whole point of a bloom layer: byte-identical results, fewer
    block probes. Compare the batched path's answers with probing on
    vs off over hits, misses, and deep-L0 state."""
    for i in range(200):
        server.on_put(generate_key(b"hk%04d" % i, b"s"), b"base-%d" % i)
    server.flush()
    server.manual_compact()
    # deep L0: three overlay flushes interleaved across the keyspace
    for gen in range(3):
        for i in range(gen, 200, 50):
            server.on_put(generate_key(b"hk%04d" % i, b"x%d" % gen),
                          b"l0-%d-%d" % (gen, i))
        server.flush()
    ops = []
    for i in range(0, 300, 3):  # past 200: misses
        ops.append(("get", generate_key(b"hk%04d" % i, b"s"), None))
        ops.append(("get", generate_key(b"hk%04d" % i, b"x1"), None))
    # indexed runs answer through the perfect-hash index (which prunes
    # AND locates); filter-only runs keep the bloom — either way the
    # sidecar layer must have pruned probes
    useful0 = server._bloom_useful.value() + server._phash_useful.value()
    on = server.on_point_read_batch(list(ops))
    assert server._bloom_useful.value() \
        + server._phash_useful.value() > useful0  # sidecars did work
    FLAGS.set("pegasus.server", "bloom_probe", False)
    try:
        server._point_cache = None  # drop locations learned with filters
        off = server.on_point_read_batch(list(ops))
    finally:
        FLAGS.set("pegasus.server", "bloom_probe", True)
    assert on == off
    # solo path agrees too
    for (op, key, _ph), r in zip(ops, on):
        assert server.on_get(key) == r


def test_l0_fence_short_circuit(tmp_path, no_row_cache):
    """Out-of-range L0 tables cost a compare, not a block lookup."""
    store = LSMStore(str(tmp_path / "s"))
    for i in range(50):
        store.put(b"aa%04d" % i, b"v")
    store.flush()
    calls = []
    orig = store.l0[0].get
    store.l0[0].get = lambda k, **kw: calls.append(k) or orig(k, **kw)
    assert store.get(b"zz0001") is None  # above the fence
    assert store.get(b"a") is None       # below the fence
    assert not calls
    assert store.get(b"aa0001") == (b"v", 0)
    assert calls == [b"aa0001"]
    store.close()


# ---- block cache LRU --------------------------------------------------


def test_block_cache_true_lru(tmp_path):
    """A hit refreshes recency: the old FIFO popped insertion order, so
    a hot block died to any cold streak."""
    old_codec = FLAGS.get("pegasus.storage", "block_codec")
    FLAGS.set("pegasus.storage", "block_codec", "none")
    try:
        w = SSTableWriter(str(tmp_path / "t.sst"), block_capacity=4)
        for i in range(16):  # 4 blocks of 4
            w.add(b"k%04d" % i, b"v", 0)
        w.finish()
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old_codec)
    # learn one block's cache charge, then budget exactly two blocks
    t = SSTable(str(tmp_path / "t.sst"))
    t.read_block(0)
    one = t._cache[0][1]
    t.close()
    t = SSTable(str(tmp_path / "t.sst"), cache_bytes=2 * one + 16)
    t.read_block(0)
    t.read_block(1)
    t.read_block(0)   # refresh block 0
    t.read_block(2)   # must evict block 1, NOT block 0
    assert set(t._cache) == {0, 2}
    t.close()


def _ycsb_table(path, blocks):
    """A file of the benchmark's record shape (ycsb_c.p4r1, PERF.md
    §4): hashkey user%08d, sortkeys field0..9 (key width 32), values of
    100 random printable bytes behind the value header; 1,024 rows a
    block."""
    rng = np.random.default_rng(11)
    n = blocks * 1024
    flat = rng.integers(32, 127, size=n * 100, dtype=np.uint8).tobytes()
    w = SSTableWriter(path)
    for i in range(n):
        w.add(generate_key(b"user%08d" % (i // 10), b"field%d" % (i % 10)),
              generate_value(1, flat[i * 100:(i + 1) * 100], 0), 0)
    w.finish()
    return SSTable(path)


def _resident_gauge():
    return METRICS.entity("storage", "node").gauge(
        "block_cache_resident_bytes").value()


def test_block_cache_holds_a_cell_sized_file(tmp_path):
    """One L1 file of ycsb_c.p4r1 (245 blocks, ~31 MB on disk) stays
    resident under the default 32 MiB: a block is charged its key
    matrix and five columns (n*(32+4+4+4+1) + 4(n+1) + the objects),
    not the ~105 KB RAW heap it views over the mmap nor a key list and
    probe table nobody built. Every block is decoded once."""
    ent = METRICS.entity("storage", "node")
    t = _ycsb_table(str(tmp_path / "t.sst"), 245)
    assert len(t.blocks) == 245 and t.codec == "dcz2"
    gc.collect()  # earlier tests' tables leave the gauge first
    g0 = _resident_gauge()
    m0 = ent.counter("block_cache_miss").value()
    e0 = ent.counter("block_cache_evict_bytes").value()
    for _round in range(3):
        for i in range(245):
            t.read_block(i)
    assert ent.counter("block_cache_miss").value() - m0 == 245
    assert ent.counter("block_cache_evict_bytes").value() == e0
    n = 1024
    assert t._cache[7][1] == (BLOCK_OBJECT_BYTES + n * (32 + 4 + 4 + 4 + 1)
                              + 4 * (n + 1))
    assert len(t._cache) == 245
    assert t._cache_bytes == 245 * t._cache[7][1] < block_cache_budget() / 2
    assert _resident_gauge() - g0 == t._cache_bytes
    t.clear_block_cache()
    assert _resident_gauge() == g0
    t.close()


def test_block_cache_charge_follows_lazy_parts_and_evicts(tmp_path):
    """key_list() and the point-probe table are charged when built, at
    the block's next hit, and the growth evicts from the cold end; the
    node's gauge moves with every charge and loses a table's when the
    table is collected."""
    ent = METRICS.entity("storage", "node")
    t = _ycsb_table(str(tmp_path / "t.sst"), 4)
    one = t.read_block(0).resident
    t.close()
    del t
    gc.collect()
    g0 = _resident_gauge()
    budget = 4 * one + 4096
    t = SSTable(str(tmp_path / "t.sst"), cache_bytes=budget)
    blks = [t.read_block(i) for i in range(4)]
    assert list(t._cache) == [0, 1, 2, 3]
    assert t._cache_bytes == 4 * one == _resident_gauge() - g0
    e0 = ent.counter("block_cache_evict_bytes").value()
    # the probe table's int64 lengths: 8 KB, over the slack of 4 KB
    probe_nat(blks[3])
    assert blks[3].resident == one + 8 * 1024
    assert t._cache_bytes == 4 * one, "charged before the next hit"
    assert t.read_block(3) is blks[3]
    assert list(t._cache) == [1, 2, 3]
    assert t._cache_bytes == 3 * one + 8 * 1024 == _resident_gauge() - g0
    assert ent.counter("block_cache_evict_bytes").value() - e0 == one
    # a key list is larger than a block: it takes another with it
    blks[2].key_list()
    assert blks[2].resident > 2 * one
    t.read_block(2)
    assert list(t._cache) == [3, 2]
    assert t._cache_bytes == blks[3].resident + blks[2].resident <= budget
    assert _resident_gauge() - g0 == t._cache_bytes
    # a block the cache let go of is not charged again by a late hit
    blks[0].key_list()
    t._charge(0, blks[0], fresh=False)
    assert list(t._cache) == [3, 2]
    assert _resident_gauge() - g0 == t._cache_bytes
    t.close()
    del t, blks
    gc.collect()
    assert _resident_gauge() == g0


def test_block_cache_accounting_under_threads(tmp_path):
    """Serving and compaction threads share a table's cache: misses,
    hits, evictions and lazy builds race on two tables at once, and
    neither a table's byte count nor the node's gauge may lose an
    update (a lost one would stay for the process's life)."""
    a = _ycsb_table(str(tmp_path / "a.sst"), 6)
    one = a.read_block(0).resident
    a.close()
    del a
    gc.collect()
    g0 = _resident_gauge()
    budget = 3 * one + 16384
    tables = [SSTable(str(tmp_path / "a.sst"), cache_bytes=budget),
              SSTable(str(tmp_path / "a.sst"), cache_bytes=budget)]
    deadline = time.monotonic() + 1.5
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            while time.monotonic() < deadline:
                t = tables[int(rng.integers(2))]
                blk = t.read_block(int(rng.integers(6)))
                roll = rng.random()
                if roll < 0.2:
                    probe_nat(blk)
                elif roll < 0.25:
                    blk.key_list()
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2 * (os.cpu_count() or 4))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(th.is_alive() for th in threads)
    for t in tables:
        assert t._cache_bytes == sum(nb for _b, nb in t._cache.values())
        # over the budget only by a single block larger than it
        assert t._cache_bytes <= budget or len(t._cache) == 1
    assert _resident_gauge() - g0 == sum(t._cache_bytes for t in tables)
    for t in tables:
        t.close()


# ---- row cache --------------------------------------------------------


def test_row_cache_serves_identical_and_counts(server):
    key = generate_key(b"hot", b"s")
    server.on_put(key, b"payload")
    server.flush()
    server.manual_compact()
    solo = server.on_get(key)
    h0 = server._row_cache_hits.value()
    for _ in range(4):  # touch 1 counts, touch 2 admits, then hits
        assert server.on_point_read_batch([("get", key, None)]) == [solo]
    assert server._row_cache_hits.value() > h0
    assert ROW_CACHE.stats()["entries"] >= 1


def test_row_cache_write_invalidation(server):
    key = generate_key(b"w", b"s")
    server.on_put(key, b"v1")
    server.flush()
    server.manual_compact()
    for _ in range(3):
        server.on_point_read_batch([("get", key, None)])
    assert server.on_point_read_batch([("get", key, None)]) == [(OK, b"v1")]
    server.on_put(key, b"v2")  # write-through invalidation
    assert server.on_point_read_batch([("get", key, None)]) == [(OK, b"v2")]
    assert server.on_get(key) == (OK, b"v2")
    server.on_remove(key)
    assert server.on_point_read_batch([("get", key, None)]) == \
        [(NOT_FOUND, b"")]


def test_row_cache_publish_and_flush_invalidation(server):
    key = generate_key(b"p", b"s")
    server.on_put(key, b"v1")
    server.flush()
    server.manual_compact()
    for _ in range(3):
        server.on_point_read_batch([("get", key, None)])
    server.on_put(key, b"v2")
    server.flush()            # generation bump orphans the old entry
    server.manual_compact()   # publish drops this gid wholesale
    assert server.on_point_read_batch([("get", key, None)]) == [(OK, b"v2")]


def test_row_cache_no_stale_under_concurrent_writes(server):
    """Monotonic-read check: a writer advances a counter value while a
    reader hammers the batched path; an answer may lag the in-flight
    write but may NEVER go backwards (a backwards value = a stale cache
    serve after an acked overwrite)."""
    key = generate_key(b"race", b"s")
    server.on_put(key, b"%08d" % 0)
    server.flush()
    server.manual_compact()
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            server.on_put(key, b"%08d" % i)

    def reader():
        last = 0
        while not stop.is_set():
            err, v = server.on_point_read_batch([("get", key, None)])[0]
            if err != OK:
                errors.append(("err", err))
                return
            cur = int(v)
            if cur < last:
                errors.append(("stale", cur, last))
                return
            last = cur

    th_w = threading.Thread(target=writer)
    th_r = threading.Thread(target=reader)
    th_w.start()
    th_r.start()
    import time as _t

    _t.sleep(1.0)
    stop.set()
    th_w.join()
    th_r.join()
    assert not errors


def test_row_cache_byte_cap_and_eviction():
    rc = RowCache()
    old = FLAGS.get("pegasus.server", "row_cache_bytes")
    FLAGS.set("pegasus.server", "row_cache_bytes", 2048)
    try:
        gid = (9, 0)
        for i in range(50):
            k = b"k%04d" % i
            assert rc.note_and_check(gid, k) is False  # first touch
            assert rc.note_and_check(gid, k) is True   # second admits
            rc.admit(gid, 1, 1, k, b"v" * 100, 0)
        st = rc.stats()
        assert st["bytes"] <= 2048
        assert 0 < st["entries"] < 50  # evictions happened
    finally:
        FLAGS.set("pegasus.server", "row_cache_bytes", old)


def test_row_cache_disable_frees_resident_bytes():
    """Turning the mutable knob to 0 must free already-admitted rows
    (the knob caps memory, not just serving)."""
    rc = RowCache()
    old = FLAGS.get("pegasus.server", "row_cache_bytes")
    FLAGS.set("pegasus.server", "row_cache_bytes", 1 << 20)
    try:
        gid = (9, 7)
        for i in range(20):
            k = b"d%04d" % i
            rc.note_and_check(gid, k)
            rc.note_and_check(gid, k)
            rc.admit(gid, 1, 1, k, b"v" * 50, 0)
        assert rc.stats()["bytes"] > 0
        FLAGS.set("pegasus.server", "row_cache_bytes", 0)
        assert rc.enabled is False  # the disable path clears
        assert rc.stats()["bytes"] == 0 and rc.stats()["entries"] == 0
    finally:
        FLAGS.set("pegasus.server", "row_cache_bytes", old)


def test_row_cache_gid_index_consistent_after_churn():
    """Per-gid wholesale invalidation drops exactly that partition's
    rows (and survives interleaved admits/evictions/invalidations)."""
    rc = RowCache()
    old = FLAGS.get("pegasus.server", "row_cache_bytes")
    FLAGS.set("pegasus.server", "row_cache_bytes", 4096)
    try:
        for gid in ((1, 0), (1, 1)):
            for i in range(30):
                k = b"g%04d" % i
                rc.note_and_check(gid, k)
                rc.note_and_check(gid, k)
                rc.admit(gid, 1, 1, k, b"v" * 30, 0)
        rc.invalidate((1, 0), 1, 1, [b"g0029"])
        rc.invalidate_gid((1, 0))
        st = rc.stats()
        assert "(1, 0)" not in st["per_gid"]
        assert st["entries"] == sum(
            g["entries"] for g in st["per_gid"].values())
        rc.invalidate_gid((1, 1))
        assert rc.stats()["entries"] == 0
        assert rc.stats()["bytes"] == 0
    finally:
        FLAGS.set("pegasus.server", "row_cache_bytes", old)


def test_row_cache_admission_epoch_guard():
    """An invalidation between the observed epoch and the admit voids
    the admission — the populate race can never cache a stale row."""
    rc = RowCache()
    gid = (9, 1)
    epoch = rc.epoch(gid)
    rc.invalidate(gid, 1, 1, [b"k"])  # concurrent write lands
    rc.admit(gid, 1, 1, b"k", b"stale", 0, epoch=epoch)
    assert rc.get(gid, 1, 1, b"k") is None


def test_row_cache_disabled_window_write_voids_admission():
    """A write landing while the knob is toggled OFF must still void a
    plan that observed the enabled cache — even for a gid that was
    never invalidated before (implicit epoch 0)."""
    rc = RowCache()
    old = FLAGS.get("pegasus.server", "row_cache_bytes")
    FLAGS.set("pegasus.server", "row_cache_bytes", 1 << 20)
    try:
        gid = (9, 3)
        epoch = rc.epoch(gid)  # plan starts against the enabled cache
        FLAGS.set("pegasus.server", "row_cache_bytes", 0)
        rc.invalidate(gid, 1, 1, [b"k"])  # write in the disabled window
        FLAGS.set("pegasus.server", "row_cache_bytes", 1 << 20)
        rc.admit(gid, 1, 1, b"k", b"stale", 0, epoch=epoch)
        assert rc.get(gid, 1, 1, b"k") is None
    finally:
        FLAGS.set("pegasus.server", "row_cache_bytes", old)


def test_row_cache_hotkey_fast_admit(server):
    """A FINISHED hotkey detection fast-admits its hashkey on first
    touch (no repeat gate)."""
    from pegasus_tpu.server.hotkey import HotkeyState

    key = generate_key(b"viral", b"s")
    server.on_put(key, b"v")
    server.flush()
    server.manual_compact()
    hc = server.hotkey_collectors["read"]
    hc.state = HotkeyState.FINISHED
    hc.result = b"viral"
    try:
        server.on_point_read_batch([("get", key, None)])  # single touch
        assert ROW_CACHE.get((server.app_id, server.pidx),
                             server.engine.lsm.store_uid,
                             server.engine.lsm.generation, key) is not None
    finally:
        hc.state = HotkeyState.STOPPED
        hc.result = None


# ---- shell observability ----------------------------------------------


def test_shell_storage_stats(tmp_path, capsys):
    import json

    from pegasus_tpu.tools.shell import main as shell_main

    root = str(tmp_path / "box")
    assert shell_main(["--root", root, "create_app", "demo",
                       "-p", "2"]) == 0
    for i in range(20):
        assert shell_main(["--root", root, "set", "demo",
                           "hk%d" % i, "sk", "v%d" % i]) == 0
    assert shell_main(["--root", root, "flush", "demo"]) == 0
    capsys.readouterr()
    assert shell_main(["--root", root, "storage_stats", "demo"]) == 0
    stats = json.loads(capsys.readouterr().out)
    assert len(stats["partitions"]) == 2
    assert all(p["runs_with_bloom"] >= 1 for p in stats["partitions"]
               if p["l0_tables"] + p["l1_runs"] > 0)
    assert "bloom_useful_count" in stats["storage"] or stats["storage"]
    assert "capacity" in stats["row_cache"]


# ---- crc64_rows (the probe hash kernel) -------------------------------


def test_crc64_rows_matches_scalar():
    from pegasus_tpu.base.crc import crc64, crc64_batch, crc64_rows

    keys = [b"\x00\x04hashsort%03d" % i for i in range(40)]
    w = max(len(k) for k in keys)
    mat = np.zeros((len(keys), w), dtype=np.uint8)
    lens = np.zeros(len(keys), dtype=np.int64)
    for i, k in enumerate(keys):
        mat[i, :len(k)] = np.frombuffer(k, dtype=np.uint8)
        lens[i] = len(k)
    rows = crc64_rows(mat, lens)
    assert (rows == crc64_batch(mat, lens)).all()
    assert (rows == np.array([crc64(k) for k in keys],
                             dtype=np.uint64)).all()
