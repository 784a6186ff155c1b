"""Compressed SST blocks (codec dcz) + direct compute on encoded data.

Gates for the round-11 format change: byte-identity of every decoded
column vs the raw layout, legacy/mixed stores serving unmodified,
format-version refusal on unknown codecs, encoded-probe equivalence
with the device predicate kernels, the byte-capped block cache, and
compaction identity (including the verbatim compressed-copy path).
"""

import json
import mmap
import os
import struct
import sys

import numpy as np
import pytest

from pegasus_tpu.base.crc import crc32
from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import epoch_now
from pegasus_tpu.storage.block_codec import EncodedBlock, encode_block
from pegasus_tpu.server.page import probe_nat
from pegasus_tpu.storage.lsm import LSMStore
from pegasus_tpu.storage.sstable import (
    BLOCK_OBJECT_BYTES,
    FOOTER,
    MAGIC,
    SSTable,
    SSTableWriter,
)
from pegasus_tpu.utils.errors import StorageCorruptionError
from pegasus_tpu.utils.flags import FLAGS
from pegasus_tpu.utils.metrics import METRICS


@pytest.fixture
def codec_flag():
    """Save/restore the codec flag around tests that flip it."""
    old = FLAGS.get("pegasus.storage", "block_codec")
    yield
    FLAGS.set("pegasus.storage", "block_codec", old)


def _write(path, codec, n_hash=40, n_sort=5, block_capacity=64,
           ttl_every=0):
    old = FLAGS.get("pegasus.storage", "block_codec")
    FLAGS.set("pegasus.storage", "block_codec", codec)
    try:
        w = SSTableWriter(path, block_capacity=block_capacity)
        now = epoch_now()
        i = 0
        for h in range(n_hash):
            for s in range(n_sort):
                ets = (now - 30 if ttl_every and i % ttl_every == 0
                       else 0)
                w.add(generate_key(b"hash%05d" % h, b"sk%03d" % s),
                      b"value|%05d|%03d|" % (h, s) * 2, ets)
                i += 1
        w.finish()
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old)
    return SSTable(path)


def _assert_blocks_identical(ta, tb):
    assert len(ta.blocks) == len(tb.blocks)
    for i in range(len(ta.blocks)):
        a, b = ta.read_block(i), tb.read_block(i)
        for col in ("keys", "key_len", "expire_ts", "hash_lo", "flags",
                    "value_offs"):
            ca, cb = getattr(a, col), getattr(b, col)
            assert np.array_equal(ca, cb), (i, col)
            assert ca.dtype == cb.dtype, (i, col)
        assert np.array_equal(np.asarray(a.value_heap),
                              np.asarray(b.value_heap)), i


# ---- round trip + identity --------------------------------------------


def test_dcz_roundtrip_byte_identical_to_raw(tmp_path):
    ta = _write(str(tmp_path / "raw.sst"), "none", ttl_every=7)
    tb = _write(str(tmp_path / "dcz.sst"), "dcz", ttl_every=7)
    assert ta.codec is None and tb.codec == "dcz"
    _assert_blocks_identical(ta, tb)
    assert list(ta.iterate()) == list(tb.iterate())
    for h in (0, 13, 39):
        key = generate_key(b"hash%05d" % h, b"sk%03d" % 2)
        assert ta.get(key) == tb.get(key)
    # the codec genuinely shrinks the file, and the stats record it
    st = tb.codec_stats
    assert st and st["stored_bytes"] < st["raw_bytes"]
    assert os.path.getsize(tb.path) < os.path.getsize(ta.path)
    # block CRCs cover the ON-DISK (encoded) bytes: scrub verify works
    for i in range(len(tb.blocks)):
        assert tb.verify_block(i) is True
    tb.verify_index_consistency()
    ta.close()
    tb.close()


def test_malformed_keys_roundtrip_raw_rows(tmp_path, codec_flag):
    """Keys the pegasus codec would never produce (short / lying
    header) take the sentinel raw-row path and still round-trip
    byte-for-byte."""
    for codec in ("none", "dcz"):
        FLAGS.set("pegasus.storage", "block_codec", codec)
        w = SSTableWriter(str(tmp_path / f"{codec}.sst"),
                          block_capacity=8)
        w.add(generate_key(b"aa", b"s"), b"v1")  # normal
        w.add(b"\x00\x50ab", b"v2")      # header beyond the body
        w.add(b"\x01", b"v3")            # shorter than the header
        w.finish()
    ta = SSTable(str(tmp_path / "none.sst"))
    tb = SSTable(str(tmp_path / "dcz.sst"))
    _assert_blocks_identical(ta, tb)
    enc = tb.read_block_encoded(0)
    assert enc.has_malformed
    assert enc.key_at(1) == b"\x00\x50ab"
    # the encoded probe refuses malformed blocks (device-kernel
    # semantics differ there) — the caller falls back to the device
    from pegasus_tpu.ops.predicates import (
        FT_NO_FILTER,
        encoded_static_keep,
    )

    assert encoded_static_keep(enc, False, 0, -1,
                               (FT_NO_FILTER, b"", FT_NO_FILTER, b"")) \
        is None
    ta.close()
    tb.close()


# ---- legacy + mixed stores --------------------------------------------


def test_codec_none_is_bitwise_legacy_format(tmp_path):
    """block_codec=none must emit the pre-codec layout exactly: no
    codec keys in the index, and the first block parses as the raw
    columnar struct."""
    t = _write(str(tmp_path / "t.sst"), "none")
    with open(t.path, "rb") as f:
        data = f.read()
    index_offset, index_size, _crc, magic = FOOTER.unpack(
        data[-FOOTER.size:])
    assert magic == MAGIC
    index = json.loads(data[index_offset:index_offset + index_size])
    assert "codec" not in index and "codec_stats" not in index
    bm = t.blocks[0]
    n, width, heap = struct.unpack_from("<IIQ", data, bm.offset)
    assert n == bm.count and width == bm.key_width
    t.close()


def test_mixed_legacy_and_compressed_store_serves(tmp_path, codec_flag):
    """Runs written before the codec existed keep serving beside
    compressed runs in ONE store — no rewrite required — and the next
    compaction converges the store onto the configured codec."""
    store = LSMStore(str(tmp_path / "s"), block_capacity=32)
    FLAGS.set("pegasus.storage", "block_codec", "none")
    for h in range(30):
        store.put(generate_key(b"old%04d" % h, b"s"), b"legacy-%04d" % h)
    store.flush()
    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    for h in range(30):
        store.put(generate_key(b"new%04d" % h, b"s"), b"fresh-%04d" % h)
    store.flush()
    codecs = {t.codec for t in store.l0}
    assert codecs == {None, "dcz"}
    for h in range(30):
        assert store.get(generate_key(b"old%04d" % h, b"s")) == \
            (b"legacy-%04d" % h, 0)
        assert store.get(generate_key(b"new%04d" % h, b"s")) == \
            (b"fresh-%04d" % h, 0)
    before = list(store.iterate())
    store.compact()
    assert list(store.iterate()) == before
    assert all(t.codec == "dcz" for t in store.l1_runs)
    store.close()


def test_unknown_codec_refused_at_open(tmp_path):
    t = _write(str(tmp_path / "t.sst"), "dcz")
    t.close()
    with open(str(tmp_path / "t.sst"), "rb") as f:
        data = f.read()
    index_offset, index_size, _crc, magic = FOOTER.unpack(
        data[-FOOTER.size:])
    index = json.loads(data[index_offset:index_offset + index_size])
    index["codec"] = "zstd-99"
    blob = json.dumps(index).encode()
    with open(str(tmp_path / "t.sst"), "wb") as f:
        f.write(data[:index_offset])
        f.write(blob)
        f.write(FOOTER.pack(index_offset, len(blob), crc32(blob), magic))
    with pytest.raises(StorageCorruptionError, match="unsupported"):
        SSTable(str(tmp_path / "t.sst"))


# ---- direct compute ----------------------------------------------------


def test_encoded_probe_matches_device_masks(tmp_path):
    from pegasus_tpu.ops.predicates import (
        FT_MATCH_ANYWHERE,
        FT_MATCH_POSTFIX,
        FT_MATCH_PREFIX,
        FT_NO_FILTER,
        FilterSpec,
        encoded_static_keep,
        static_block_predicate,
    )
    from pegasus_tpu.ops.record_block import block_from_columns

    t = _write(str(tmp_path / "t.sst"), "dcz", n_hash=50, n_sort=6,
               block_capacity=128)
    flavors = [
        (FT_NO_FILTER, b"", FT_NO_FILTER, b""),
        (FT_MATCH_PREFIX, b"hash0001", FT_NO_FILTER, b""),
        (FT_NO_FILTER, b"", FT_MATCH_PREFIX, b"sk00"),
        (FT_MATCH_ANYWHERE, b"sh000", FT_MATCH_POSTFIX, b"3"),
        (FT_MATCH_POSTFIX, b"21", FT_MATCH_ANYWHERE, b"k0"),
        (FT_MATCH_PREFIX, b"hash00013zzzz", FT_MATCH_PREFIX,
         b"sk00333"),
    ]
    probe0 = METRICS.entity("storage", "node").counter(
        "encoded_probe_count").value()
    for i in range(len(t.blocks)):
        blk = t.read_block(i)
        enc = t.read_block_encoded(i)
        dev = block_from_columns(blk.keys, blk.key_len, blk.expire_ts,
                                 hash_lo=blk.hash_lo)
        for validate, pidx, pv in ((False, 0, -1), (True, 1, 3),
                                   (True, 5, 3)):
            for fk in flavors:
                want = np.asarray(static_block_predicate(
                    dev, hash_filter=FilterSpec.make(fk[0], fk[1]),
                    sort_filter=FilterSpec.make(fk[2], fk[3]),
                    validate_hash=validate, pidx=pidx,
                    partition_version=pv))
                got = encoded_static_keep(enc, validate, pidx, pv, fk)
                assert got is not None
                assert np.array_equal(want, got), (i, validate, fk)
    assert METRICS.entity("storage", "node").counter(
        "encoded_probe_count").value() > probe0
    t.close()


def test_encoded_probe_python_fallback(tmp_path, monkeypatch):
    """Without the native library the probe + key-matrix rebuild fall
    back to numpy/scalar paths with identical results."""
    import pegasus_tpu.native as native
    from pegasus_tpu.ops.predicates import (
        FT_MATCH_POSTFIX,
        FT_MATCH_PREFIX,
        encoded_static_keep,
    )

    t = _write(str(tmp_path / "t.sst"), "dcz", n_hash=12, n_sort=4,
               block_capacity=32)
    enc = t.read_block_encoded(0)
    fk = (FT_MATCH_PREFIX, b"hash0000", FT_MATCH_POSTFIX, b"2")
    with_native = encoded_static_keep(enc, True, 1, 3, fk)
    km_native = enc.key_matrix()
    monkeypatch.setattr(native, "region_filter_fn", lambda: None)
    monkeypatch.setattr(native, "cblock_decode_keys_fn", lambda: None)
    assert np.array_equal(with_native,
                          encoded_static_keep(enc, True, 1, 3, fk))
    assert np.array_equal(km_native, enc.key_matrix())
    t.close()


def test_lazy_heap_defers_inflate_to_value_access(tmp_path):
    t = _write(str(tmp_path / "t.sst"), "dcz")
    blk = t.read_block(0)
    # key-side work happens without inflating the value heap
    assert blk.key_at(0) == generate_key(b"hash00000", b"sk000")
    assert blk.alive_mask(epoch_now()).all()
    assert callable(blk._vh), "heap inflated before any value access"
    v = blk.value_at(0)
    assert v == b"value|%05d|%03d|" % (0, 0) * 2
    assert not callable(blk._vh)
    t.close()


# ---- byte-capped block cache ------------------------------------------


def test_block_cache_byte_cap_and_evict_counter(tmp_path):
    t = _write(str(tmp_path / "t.sst"), "dcz", n_hash=64, n_sort=4,
               block_capacity=16)
    assert len(t.blocks) >= 8
    # learn one decoded block's charge, then budget two of them
    t.read_block(0)
    one = t._cache[0][1]
    t.close()
    ent = METRICS.entity("storage", "node")
    d0 = ent.counter("compressed_block_decode_count").value()
    e0 = ent.counter("block_cache_evict_bytes").value()
    t = SSTable(str(tmp_path / "t.sst"), cache_bytes=2 * one + 64)
    for i in range(len(t.blocks)):
        t.read_block(i)
    assert len(t._cache) <= 2
    assert t._cache_bytes <= 2 * one + 64
    ent2 = METRICS.entity("storage", "node")
    assert ent2.counter(
        "compressed_block_decode_count").value() >= d0 + len(t.blocks)
    assert ent2.counter("block_cache_evict_bytes").value() > e0
    # a re-read of an evicted block decodes again (counted)
    d1 = ent2.counter("compressed_block_decode_count").value()
    t.read_block(0)
    assert ent2.counter(
        "compressed_block_decode_count").value() == d1 + 1
    t.close()


# ---- what a resident block is charged --------------------------------


def _owned_reference(blk):
    """The bytes `blk` keeps allocated, from its arrays: each buffer an
    array owns, or the bytes object it was cut from, once; nothing for
    a view that ends in the file's mmap. Plus the key list, object by
    object."""
    heap = blk._vh.stored if callable(blk._vh) else blk._vh
    arrays = [blk.keys, blk.key_len, blk.expire_ts, blk.hash_lo,
              blk.flags, blk.value_offs, heap]
    arrays += list(blk._probe or ())
    owners = {}
    for a in arrays:
        if a is None:
            continue
        while isinstance(a, np.ndarray) and not a.flags.owndata:
            a = a.base
            if isinstance(a, memoryview):
                a = a.obj
        if isinstance(a, mmap.mmap):
            continue
        assert isinstance(a, (np.ndarray, bytes)), type(a)
        owners[id(a)] = a.nbytes if isinstance(a, np.ndarray) else len(a)
    total = sum(owners.values())
    if blk._key_list is not None:
        total += sys.getsizeof(blk._key_list) + sum(
            sys.getsizeof(k) for k in blk._key_list)
    return total


def _write_heap_mode(path, codec, heap, monkeypatch):
    """A file of 4 blocks whose value heaps the codec stores RAW
    (random printable bytes), or deflated with zstd or zlib."""
    from pegasus_tpu.storage import block_codec as bc

    rng = np.random.default_rng(7)
    if heap == "zlib":
        monkeypatch.setattr(bc._Zstd, "_lib", None)
        monkeypatch.setattr(bc._Zstd, "_tried", True)
    old = FLAGS.get("pegasus.storage", "block_codec")
    FLAGS.set("pegasus.storage", "block_codec", codec)
    try:
        w = SSTableWriter(path, block_capacity=256)
        for i in range(1024):
            value = (rng.integers(32, 127, size=100, dtype=np.uint8)
                     .tobytes() if heap == "raw"
                     else b"value|%05d|" % (i // 7) * 8)
            w.add(generate_key(b"user%08d" % (i // 10),
                               b"field%d" % (i % 10)), value,
                  epoch_now() + 1000 if i % 3 == 0 else 0)
        w.finish()
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old)
        monkeypatch.undo()
    if codec != "none":
        t = SSTable(path)
        want = {"raw": bc._HEAP_RAW, "zstd": bc._HEAP_ZSTD,
                "zlib": bc._HEAP_ZLIB}[heap]
        assert {t.read_block_encoded(i).heap_mode
                for i in range(len(t.blocks))} == {want}
        t.close()


@pytest.mark.parametrize("mapped", [True, False],
                         ids=["mmap", "read_copy"])
@pytest.mark.parametrize("codec,heap", [
    ("none", "raw"), ("dcz", "raw"), ("dcz", "zstd"),
    ("dcz2", "raw"), ("dcz2", "zstd"), ("dcz2", "zlib")])
def test_block_charge_is_what_the_block_owns(tmp_path, monkeypatch,
                                             codec, heap, mapped):
    """The cache's charges sum to the bytes its resident blocks
    allocate, before and after each lazy part is built; a RAW heap over
    the mmap adds nothing, a read() copy adds the copy."""
    path = str(tmp_path / "t.sst")
    _write_heap_mode(path, codec, heap, monkeypatch)
    if not mapped:
        def no_mmap(*_a, **_k):
            raise OSError("no mmap on this filesystem")
        monkeypatch.setattr(mmap, "mmap", no_mmap)
    t = SSTable(path)
    monkeypatch.undo()
    assert (t._mv is not None) == mapped

    def check():
        blks = [t.read_block(i) for i in range(len(t.blocks))]
        assert t._cache_bytes == sum(nb for _b, nb in t._cache.values())
        for i, blk in enumerate(blks):
            assert t._cache[i][1] == blk.resident \
                == BLOCK_OBJECT_BYTES + _owned_reference(blk), (i, codec)
        return blks

    blks = check()
    n = blks[0].count
    heap_len = int(blks[0].value_offs[-1])
    owned = blks[0].resident - BLOCK_OBJECT_BYTES
    if not mapped:
        # every view into the block's bytes pins the one read() copy
        assert owned >= t.blocks[0].size
    elif codec == "none":
        assert owned == 0  # views over the mmap, all of them
    else:
        # the rebuilt key matrix and columns; a RAW heap is a view
        # over the mmap and a deflated one is not inflated yet
        assert n * blks[0].keys.shape[1] <= owned < heap_len
    if heap != "raw":
        assert callable(blks[0]._vh), "deflated heap inflated early"
    before = blks[0].resident
    assert blks[0].value_at(3)  # inflates a deflated heap
    grew = blks[0].resident - before
    if heap == "raw":
        assert grew == 0
    elif mapped:
        assert grew == heap_len
    else:
        # the inflated heap is owned now; the read() copy stays pinned
        # only while another column still views it
        assert grew in (heap_len, heap_len - t.blocks[0].size)
    check()
    before = blks[1].resident
    probe_nat(blks[1])
    assert blks[1].resident == before + 8 * n  # the int64 lengths
    check()
    before = blks[2].resident
    kl = blks[2].key_list()
    assert blks[2].resident >= before + n * 33 + sum(map(len, kl))
    check()
    t.close()


# ---- compaction --------------------------------------------------------


def _build_engine(data_dir, codec, expired_every=4):
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    old = FLAGS.get("pegasus.storage", "block_codec")
    FLAGS.set("pegasus.storage", "block_codec", codec)
    try:
        eng = StorageEngine(data_dir, block_capacity=64)
        now = epoch_now()
        d = 0
        for h in range(60):
            items = []
            for s in range(8):
                i = h * 8 + s
                ets = int(now - 40) if i % expired_every == 0 else 0
                items.append(WriteBatchItem(
                    OP_PUT, generate_key(b"user%05d" % h, b"s%02d" % s),
                    b"payload|%05d|%02d|" % (h, s) * 3, ets))
            d += 1
            eng.write_batch(items, d)
        eng.flush()
        eng.manual_compact()        # the L0 -> compressed L1
        assert eng.lsm.bulk_compact_eligible()
        eng.manual_compact()        # bulk path (encoded drop masks)
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old)
    return eng


def test_bulk_compact_identity_and_verbatim_copy(tmp_path, codec_flag):
    ea = _build_engine(str(tmp_path / "raw"), "none")
    eb = _build_engine(str(tmp_path / "dcz"), "dcz")
    assert list(ea.iterate()) == list(eb.iterate())
    assert all(t.codec == "dcz" for t in eb.lsm.l1_runs)
    # with the expired rows already dropped, a second bulk compaction
    # copies every compressed block VERBATIM (no decode): the decode
    # counter must not move while the output stays identical
    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    before = list(eb.iterate())
    for t in eb.lsm.l1_runs:
        t.clear_block_cache()
    d0 = METRICS.entity("storage", "node").counter(
        "compressed_block_decode_count").value()
    eb.manual_compact()
    assert METRICS.entity("storage", "node").counter(
        "compressed_block_decode_count").value() == d0
    assert list(eb.iterate()) == before
    # iterate() above re-decoded blocks; that's expected — only the
    # compaction itself must stay decode-free
    ea.close()
    eb.close()


def test_bulk_compact_ttl_drop_on_compressed_matches_raw(tmp_path,
                                                         codec_flag):
    """default_ttl rewrite + expiry on the encoded fast path produces
    the same surviving records as the raw/device path."""
    ea = _build_engine(str(tmp_path / "raw"), "none", expired_every=3)
    eb = _build_engine(str(tmp_path / "dcz"), "dcz", expired_every=3)
    ra, rb = list(ea.iterate()), list(eb.iterate())
    assert ra == rb
    assert len(ra) == 60 * 8 - (60 * 8 + 2) // 3
    ea.close()
    eb.close()


def test_writer_finish_sites_all_stamp_codec(tmp_path, codec_flag):
    """flush, merge-compact, bulk-compact, ingest: every site produces
    codec-stamped files with working bloom filters."""
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    eng = StorageEngine(str(tmp_path / "e"), block_capacity=32)
    for d in range(1, 41):
        eng.write_batch([WriteBatchItem(
            OP_PUT, generate_key(b"fk%04d" % d, b"s"),
            b"v%04d" % d)], d)
    eng.flush()
    assert eng.lsm.l0[0].codec == "dcz"           # flush site
    assert eng.lsm.l0[0].bloom is not None
    eng.manual_compact()
    assert all(t.codec == "dcz" for t in eng.lsm.l1_runs)  # merge site
    eng.manual_compact()                           # bulk site
    assert all(t.codec == "dcz" and t.bloom is not None
               for t in eng.lsm.l1_runs)

    ext = str(tmp_path / "ext.sst")
    w = SSTableWriter(ext, block_capacity=16)
    for i in range(20):
        w.add(generate_key(b"ing%04d" % i, b"s"), b"iv%04d" % i)
    w.finish()
    eng.ingest_sst_file(ext, decree=100)           # ingest site
    assert eng.lsm.l0[0].codec == "dcz"
    assert eng.get(generate_key(b"ing0007", b"s")) == (b"iv0007", 0)
    assert eng.get(generate_key(b"fk0011", b"s")) == (b"v0011", 0)
    eng.close()


def test_subset_single_survivor_fence_keys(tmp_path, codec_flag):
    """A subset keeping exactly ONE row must report that key as BOTH
    first and last key — a zeroed last-key slot corrupts the published
    block fence and point reads silently skip the block."""
    from pegasus_tpu import native

    fn = native.cblock_subset_fn()
    if fn is None:
        pytest.skip("native library unavailable")

    n = 16
    keys = np.zeros((n, 32), dtype=np.uint8)
    recs = [generate_key(b"hk%02d" % i, b"s") for i in range(n)]
    kl = np.array([len(r) for r in recs], np.int32)
    for i, r in enumerate(recs):
        keys[i, :len(r)] = np.frombuffer(r, np.uint8)
    offs = np.zeros(n + 1, np.uint32)
    offs[1:] = np.cumsum([8] * n)
    raw = encode_block(keys, kl, np.zeros(n, np.uint32),
                       np.arange(n, dtype=np.uint32),
                       np.zeros(n, np.uint8), offs, b"v" * (8 * n))
    src = EncodedBlock.parse(raw)
    keep = np.zeros(n, np.uint8)
    keep[7] = 1
    res = fn(raw, src.raw_heap_len, 32, keep, None, False, False)
    assert res is not None
    _buf, _h, m, _vsub, fk, lk = res
    assert m == 1
    assert fk == recs[7] and lk == recs[7]

    # end-to-end: TTL-expire all but one record per store, bulk
    # compact, and the lone survivor must still be point-readable
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    eng = StorageEngine(str(tmp_path / "e"), block_capacity=64)
    now = epoch_now()
    for d in range(1, 41):
        ets = 0 if d == 17 else int(now - 40)
        eng.write_batch([WriteBatchItem(
            OP_PUT, generate_key(b"sk%04d" % d, b"s"),
            b"val%04d" % d, ets)], d)
    eng.flush()
    eng.manual_compact()
    assert eng.lsm.bulk_compact_eligible()
    eng.manual_compact()
    assert eng.get(generate_key(b"sk0017", b"s")) == (b"val0017", 0)
    assert list(eng.iterate()) != []
    eng.close()


def test_bulk_compact_all_dropped_publishes_no_runs(tmp_path,
                                                    codec_flag):
    """Every record expired -> bulk compaction must publish ZERO L1
    runs (the encoded subset path must not instantiate a writer for
    fully-dropped blocks)."""
    from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT

    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    eng = StorageEngine(str(tmp_path / "e"), block_capacity=32)
    now = epoch_now()
    for d in range(1, 41):
        eng.write_batch([WriteBatchItem(
            OP_PUT, generate_key(b"gone%04d" % d, b"s"),
            b"v%04d" % d, int(now - 40))], d)
    eng.flush()
    eng.manual_compact()
    if eng.lsm.bulk_compact_eligible():
        eng.manual_compact()
    assert list(eng.iterate()) == []
    empties = [t for t in eng.lsm.l1_runs if t.record_count == 0] \
        if eng.lsm.l1_runs and hasattr(eng.lsm.l1_runs[0],
                                       "record_count") else []
    assert not empties
    assert not eng.lsm.l1_runs, [t.path for t in eng.lsm.l1_runs]
    eng.close()


def test_encode_block_rejects_nonzero_offset_base():
    with pytest.raises(ValueError):
        encode_block(np.zeros((1, 32), np.uint8),
                     np.array([4], np.int32), np.zeros(1, np.uint32),
                     np.zeros(1, np.uint32), np.zeros(1, np.uint8),
                     np.array([2, 3], np.uint32), b"abc")


def test_zlib_heap_blocks_still_decode_and_compact(tmp_path,
                                                   monkeypatch):
    """The heap compressor moved zlib -> zstd; blocks whose value heap
    was deflated with zlib (heap_mode=1) must keep decoding byte-for-
    byte, and the native subset kernel must take them (re-compressing
    the surviving heap forward to zstd when libzstd resolves)."""
    from pegasus_tpu.storage import block_codec as bc

    # force the encoder onto the zlib fallback for one file
    monkeypatch.setattr(bc._Zstd, "_lib", None)
    monkeypatch.setattr(bc._Zstd, "_tried", True)
    ta = _write(str(tmp_path / "zlib.sst"), "dcz", n_hash=30, n_sort=4)
    monkeypatch.undo()
    assert bc._Zstd.lib() is not None, "container lost libzstd"

    tb = _write(str(tmp_path / "zstd.sst"), "dcz", n_hash=30, n_sort=4)
    _assert_blocks_identical(ta, tb)
    assert list(ta.iterate()) == list(tb.iterate())

    # at least one heap must actually be compressed in each file, and
    # with different compressors (mode 1 vs mode 2)
    def modes(t):
        return {t.read_block_encoded(i).heap_mode
                for i in range(len(t.blocks))}
    ma, mb = modes(ta), modes(tb)
    assert bc._HEAP_ZLIB in ma and bc._HEAP_ZSTD not in ma
    assert bc._HEAP_ZSTD in mb and bc._HEAP_ZLIB not in mb
    ta.close()
    tb.close()


def test_native_subset_takes_both_heap_modes(monkeypatch):
    """pegasus_cblock_subset inflates zlib AND zstd heaps; the subset
    of a zlib-heap block re-compresses forward to zstd."""
    from pegasus_tpu import native
    from pegasus_tpu.storage import block_codec as bc

    fn = native.cblock_subset_fn()
    if fn is None:
        pytest.skip("native library unavailable")

    n = 64
    keys = np.zeros((n, 32), dtype=np.uint8)
    recs = [generate_key(b"hk%02d" % (i // 8), b"s%02d" % (i % 8))
            for i in range(n)]
    kl = np.array([len(r) for r in recs], np.int32)
    for i, r in enumerate(recs):
        keys[i, :len(r)] = np.frombuffer(r, np.uint8)
    vals = b"".join(b"compressible-value-%04d|" % i for i in range(n))
    offs = np.zeros(n + 1, np.uint32)
    offs[1:] = np.cumsum([24] * n)
    ets = np.zeros(n, np.uint32)
    hlo = np.arange(n, dtype=np.uint32)
    flags = np.zeros(n, np.uint8)

    for forced_zlib in (True, False):
        if forced_zlib:
            monkeypatch.setattr(bc._Zstd, "_lib", None)
            monkeypatch.setattr(bc._Zstd, "_tried", True)
        raw = encode_block(keys, kl, ets, hlo, flags, offs, vals)
        if forced_zlib:
            monkeypatch.undo()
        src = EncodedBlock.parse(raw)
        want = bc._HEAP_ZLIB if forced_zlib else bc._HEAP_ZSTD
        assert src.heap_mode == want
        keep = np.zeros(n, np.uint8)
        keep[::2] = 1
        res = fn(raw, src.raw_heap_len, 32, keep, None, False, True)
        assert res is not None
        sub = EncodedBlock.parse(res[0])
        assert sub.n == n // 2
        # surviving heap re-compresses with zstd regardless of source
        assert sub.heap_mode == bc._HEAP_ZSTD
        blk = sub.decode()
        got = [blk.key_at(j) for j in range(sub.n)]
        assert got == recs[::2]
        assert np.asarray(blk.value_heap).tobytes() == b"".join(
            b"compressible-value-%04d|" % i for i in range(0, n, 2))


def test_encoded_block_parse_roundtrip_fields():
    keys = np.zeros((3, 32), dtype=np.uint8)
    recs = [generate_key(b"hk", b"a"), generate_key(b"hk", b"b"),
            generate_key(b"zz", b"a")]
    kl = np.array([len(r) for r in recs], np.int32)
    for i, r in enumerate(recs):
        keys[i, :len(r)] = np.frombuffer(r, np.uint8)
    ets = np.array([0, 5, 0], np.uint32)
    hlo = np.array([1, 1, 2], np.uint32)
    flags = np.array([0, 1, 0], np.uint8)
    offs = np.array([0, 2, 2, 5], np.uint32)
    raw = encode_block(keys, kl, ets, hlo, flags, offs, b"abcde")
    enc = EncodedBlock.parse(raw)
    assert enc.n == 3 and not enc.has_malformed
    assert [enc.key_at(i) for i in range(3)] == recs
    assert enc.dict_entries() == [b"hk", b"zz"]    # 2 unique hashkeys
    blk = enc.decode()
    assert np.array_equal(blk.keys, keys)
    assert np.array_equal(blk.flags, flags)
    assert np.array_equal(blk.value_offs, offs)
    assert bytes(np.asarray(blk.value_heap)) == b"abcde"
