"""Columnar SSTable — TPU-friendly sorted runs on disk.

Role parity: RocksDB SST files in the reference. The layout difference IS
the design: instead of row-oriented key/value entries, each block stores

    keys        uint8[count, key_width]  (padded rows, width bucketed pow2)
    key_len     int32[count]
    expire_ts   uint32[count]            (decoded from the value header)
    hash_lo     uint32[count]            (low lane of crc64(pegasus_key_hash),
                                          precomputed at write time so the
                                          scan path validates partition
                                          ownership with ONE compare instead
                                          of a per-byte crc loop on device)
    flags       uint8[count]             (bit0 = tombstone)
    value_offs  uint32[count+1]
    value_heap  bytes                    (full pegasus-encoded values)

so a scan or compaction hands `keys/key_len/expire_ts` straight to the
device predicate kernels (ops/record_block.block_from_columns) with zero
per-record host decoding — the reference instead re-parses every key/value
in scalar C++ per record (src/server/pegasus_server_impl.cpp:643).

File layout:  magic | block* | index(JSON) | footer.
The JSON index carries per-block offsets + first/last keys and a `meta`
dict (data_version, last_flushed_decree, ...) — the meta-column-family
analogue (src/base/meta_store.h:41).
"""

from __future__ import annotations

import bisect
import json
import os
import struct
import sys
import threading
import weakref
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

import numpy as np

from pegasus_tpu.storage.vfs import fsync_dir, fsync_file, open_data_file

from pegasus_tpu.base.crc import crc32, crc64, crc64_batch, crc64_rows
from pegasus_tpu.ops.record_block import next_bucket
from pegasus_tpu.storage.block_codec import (
    CODEC_DCZ2,
    CODEC_NONE,
    KNOWN_CODECS,
    EncodedBlock,
    block_version,
    codec_accepts,
    encode_block,
    raw_block_size,
)
from pegasus_tpu.storage.bloom import (
    BloomFilter,
    bloom_build_bits,
    bloom_probe_enabled,
)
from pegasus_tpu.storage.phash import (
    KNOWN_PHASH_VERSIONS,
    PHASH_BUILD_FAIL,
    PHASH_HIT,
    PHASH_USEFUL,
    PHashIndex,
    phash_build_enabled,
    phash_probe_enabled,
)
from pegasus_tpu.utils.errors import StorageCorruptionError
from pegasus_tpu.utils.flags import FLAGS, define_flag
from pegasus_tpu.utils.metrics import METRICS

define_flag("pegasus.storage", "block_crc", True,
            "write a crc32 per data block into new SST files and "
            "verify it on every block decode (cache misses only — "
            "cached hits already paid); files written without block "
            "CRCs keep serving unverified", mutable=True)

define_flag("pegasus.storage", "block_codec", "dcz2",
            "per-block compression codec stamped into new SST files "
            "at every writer finish site (flush / merge-compact / "
            "bulk-compact / ingest): 'dcz2' = dictionary-coded hashkey "
            "column + packed sortkeys + compressed value heap (zstd-1, "
            "zlib-1 fallback) + FOR/delta expire_ts + dict-indexed "
            "hash_lo, with direct compute on the encoded form; 'dcz' = "
            "the PR 7 layout (raw uint32 predicate columns); 'none' = "
            "the legacy raw columnar layout, bit-for-bit. Files "
            "written before this flag existed (or with an unknown "
            "codec) keep serving / are refused at open respectively",
            mutable=True)

define_flag("pegasus.storage", "block_cache_bytes", 33_554_432,
            "per-table decoded-block cache budget in bytes (LRU). "
            "Replaces the old fixed 256-block count cap: compressed "
            "blocks decode into real allocations of wildly varying "
            "size, so only a byte budget bounds memory. A resident "
            "block is charged what it allocates: the arrays it owns "
            "(key matrix, rebuilt columns), a value heap once "
            "inflated, the key list and point-probe table once "
            "built, and a read() copy of its file bytes where the "
            "file is not mmapped; views over the file's mmap are "
            "free", mutable=True)


def block_crc_enabled() -> bool:
    return bool(FLAGS.get("pegasus.storage", "block_crc"))


def block_codec() -> str:
    codec = str(FLAGS.get("pegasus.storage", "block_codec"))
    if codec != CODEC_NONE and codec not in KNOWN_CODECS:
        raise ValueError(f"unknown block_codec {codec!r}")
    return codec


def block_cache_budget() -> int:
    return int(FLAGS.get("pegasus.storage", "block_cache_bytes"))


# Block checksums use zlib's slice-by-8 CRC-32 (~1 GB/s) rather than
# the repo's table-loop CRC-32C (~235 MB/s): the block CRC is a private
# file-format field with no wire-parity constraint — unlike the routing
# crc64 / framing crc32, which stay bit-compatible with the reference —
# and it sits on every cold block decode, where a 4x cheaper check is
# the difference between "noise" and a measurable read regression
# (rocksdb likewise offers kxxHash behind the same per-block slot).
from zlib import crc32 as _block_crc32  # noqa: E402

# node-wide storage observability (parity: the rocksdb block-cache /
# filter tickers the reference exports per server): relaxed counters —
# these tick once per block read / filter probe, the hottest loops in
# the process, so they trade perfect cross-thread accuracy for zero
# lock traffic
_STORAGE_METRICS = METRICS.entity("storage", "node")
_BLOCK_CACHE_HIT = _STORAGE_METRICS.relaxed_counter("block_cache_hit")
_BLOCK_CACHE_MISS = _STORAGE_METRICS.relaxed_counter("block_cache_miss")
_BLOOM_USEFUL = _STORAGE_METRICS.relaxed_counter("bloom_useful_count")
# codec observability: how often the read path pays a full decode of a
# compressed block, and how many bytes the byte-capped cache evicts
_COMPRESSED_DECODE = _STORAGE_METRICS.relaxed_counter(
    "compressed_block_decode_count")
_BLOCK_EVICT_BYTES = _STORAGE_METRICS.relaxed_counter(
    "block_cache_evict_bytes")
# the budget's use beside them: the sum of the charges in every live
# table's block cache. A sum of deltas from many tables, so (unlike the
# relaxed counters) it is updated under a lock: a lost update would
# stay in a gauge for the process's life. Misses, evictions and lazy
# builds move it; a hit does not
_BLOCK_RESIDENT = _STORAGE_METRICS.gauge("block_cache_resident_bytes")
_BLOCK_RESIDENT_LOCK = threading.Lock()


def _resident_add(delta: int) -> None:
    if delta:
        with _BLOCK_RESIDENT_LOCK:
            _BLOCK_RESIDENT.set(_BLOCK_RESIDENT.value() + delta)


def _drop_charges(cache) -> None:
    """Empty one table's block cache and take its charges out of the
    node's gauge. Also the table's finalizer: a superseded run is
    released by GC, not closed (LSMStore publish), and its blocks go
    with it."""
    _resident_add(-sum(nb for _blk, nb in cache.values()))
    cache.clear()

from pegasus_tpu.utils.tracing import annotate as _trace_annotate  # noqa: E402
from pegasus_tpu.utils.perf_context import current as _perf_current  # noqa: E402

MAGIC = b"PGT2"
MAGIC_V1 = b"PGT1"  # pre-hash_lo format, still readable
FOOTER = struct.Struct("<QII4s")  # index_offset, index_size, index_crc, magic
_BLOCK_HDR = struct.Struct("<IIQ")  # count, key_width, value_heap_size

BLOCK_CAPACITY = 1024

FLAG_TOMBSTONE = 1


@dataclass
class BlockMeta:
    offset: int
    size: int
    count: int
    key_width: int
    first_key: bytes
    last_key: bytes
    # crc32 of the block's on-disk bytes (header + columns + heap);
    # None for files written before the block-checksum layer — those
    # keep serving unverified (parity: rocksdb's per-block checksum,
    # which the reference trusts for every data block read)
    crc: Optional[int] = None


# what a resident Block costs before any array: the Block, its seven
# ndarray headers (112-128 bytes each) and the cache's entry. It keeps
# a file of pure mmap views (codec none) from being cached without end
BLOCK_OBJECT_BYTES = 1024


def _owned_nbytes(parts) -> int:
    """Bytes of the allocations that `parts` (arrays, or the buffers
    behind them) keep alive, each allocation counted once and whole: an
    array that owns its data, or the bytes object a view was cut from
    (a read() copy of the block, an inflated heap). A view over the
    file's mmap keeps no allocation: those pages are the page cache's."""
    owned = {}
    for a in parts:
        while isinstance(a, np.ndarray) and a.base is not None:
            a = a.base
        if isinstance(a, memoryview):
            a = a.obj
        if isinstance(a, np.ndarray):
            owned[id(a)] = a.nbytes
        elif isinstance(a, (bytes, bytearray)):
            owned[id(a)] = len(a)
    return sum(owned.values())


class Block:
    """A decoded columnar block; arrays are views over the file bytes\n    (plus, for blocks that prove hot, one lazily materialized Python\n    key list — see key_list()).

    Blocks decoded from COMPRESSED files may carry their value heap as
    a zero-arg thunk: the heap decompression runs on first value access, so
    key-only work (point probes, bloom builds, fence walks, no-value
    scans) over a compressed block never pays the heap decode —
    materialization is deferred to the rows that actually serve.

    `resident` is what the block allocates as it stands, the charge it
    carries in its table's block cache. The block recounts it whenever
    a lazy part is built (the heap inflated, key_list(), the point-probe
    table); the cache re-reads it on the next hit. Not counted: the
    alive mask (a byte a row, replaced each second, never added to)."""

    __slots__ = ("keys", "key_len", "expire_ts", "hash_lo", "flags",
                 "value_offs", "_vh", "_key_list", "_gets",
                 "_nat", "_cmp", "_probe", "resident")

    def __init__(self, keys, key_len, expire_ts, hash_lo, flags, value_offs,
                 value_heap):
        self._key_list = None
        self._gets = 0
        self._probe = None  # point-probe entry table (page.probe_nat)
        self.keys = keys              # uint8[N, W]
        self.key_len = key_len        # int32[N]
        self.expire_ts = expire_ts    # uint32[N]
        self.hash_lo = hash_lo        # uint32[N]
        self.flags = flags            # uint8[N]
        self.value_offs = value_offs  # uint32[N+1]
        self._vh = value_heap         # uint8[heap] view, or lazy thunk
        self.recount()

    def recount(self) -> None:
        """Set `resident` from what the block holds now. Callers: the
        block's own lazy builders, and page.probe_nat after it sets
        `_probe`."""
        vh = self._vh
        parts = [self.keys, self.key_len, self.expire_ts, self.hash_lo,
                 self.flags, self.value_offs,
                 # a deflated heap pins its stored bytes until inflated
                 vh.stored if callable(vh) else vh]
        if self._probe is not None:
            parts.extend(self._probe)
        total = BLOCK_OBJECT_BYTES + _owned_nbytes(parts)
        kl = self._key_list
        if kl is not None:
            total += (sys.getsizeof(kl) + len(kl) * sys.getsizeof(b"")
                      + int(self.key_len.sum()))
        self.resident = total

    @property
    def value_heap(self):
        vh = self._vh
        if callable(vh):
            vh = self._vh = vh()
            self.recount()
        return vh

    @property
    def count(self) -> int:
        return self.keys.shape[0]

    def key_at(self, i: int) -> bytes:
        return self.keys[i, :self.key_len[i]].tobytes()

    def alive_mask(self, now: int):
        """bool[count] TTL-alive mask, cached per `now` second — every
        batch in the same second reuses it (TTL validity granularity is
        one second)."""
        cached = getattr(self, "_cmp", None)
        if cached is not None and cached[0] == now:
            return cached[1]
        from pegasus_tpu.ops.predicates import host_alive_mask

        mask = host_alive_mask(self.expire_ts, now)
        self._cmp = (now, mask)
        return mask

    def key_list(self) -> list:
        """All keys as a sorted Python list, materialized at most once
        per cached block (trades ~key bytes of heap for slice-free
        bisects — worth it only on blocks that are read repeatedly, so
        callers on one-shot paths should not force it)."""
        kl = self._key_list
        if kl is None:
            keys, lens = self.keys, self.key_len
            kl = [keys[i, :lens[i]].tobytes()
                  for i in range(keys.shape[0])]
            self._key_list = kl
            self.recount()
        return kl

    def value_at(self, i: int) -> bytes:
        return self.value_heap[
            self.value_offs[i]:self.value_offs[i + 1]].tobytes()

    def is_tombstone(self, i: int) -> bool:
        return bool(self.flags[i] & FLAG_TOMBSTONE)


class SSTableWriter:
    """Writes a sorted record stream into a columnar SST.

    `async_io=True` moves file writes onto a background thread (bounded
    queue): the caller's (single) core keeps gathering/evaluating while
    the kernel drains the write stream — the IO half of the compaction
    double-buffering. Ordering per writer is preserved (one thread, one
    FIFO); finish() joins the queue before writing the index, so the
    durability contract (data before index before rename) is unchanged."""

    def __init__(self, path: str, block_capacity: int = BLOCK_CAPACITY,
                 meta: Optional[dict] = None,
                 async_io: bool = False) -> None:
        self.path = path
        self._block_capacity = block_capacity
        self._meta = dict(meta or {})
        self._f = open_data_file(path + ".tmp", "wb")
        self._blocks: List[BlockMeta] = []
        self._pending: List[Tuple[bytes, bytes, int, int]] = []
        self._last_key: Optional[bytes] = None
        self._count = 0
        self._offset = 0  # logical file position (writes may be queued)
        self._io_q = None
        self._io_thread = None
        self._io_err: List[BaseException] = []
        # SIDECAR structures (bloom filter + perfect-hash index) both
        # consume the same full-key crc64 hash columns, accumulated
        # per block by ONE shared helper (_sidecar_note) at every add
        # path — flush, merge-compact, bulk-compact and ingest all
        # route through these four adds, so the accumulation cannot
        # drift across writer-finish sites. Both build knobs are
        # latched HERE so a mutable flag flip mid-write cannot tear
        # one table's sidecars
        self._bloom_bits_per_key = bloom_build_bits()
        self.bloom_enabled = self._bloom_bits_per_key > 0
        self.phash_enabled = phash_build_enabled()
        self.sidecar_hashes = self.bloom_enabled or self.phash_enabled
        # block-checksum latch, same reasoning: one table is either
        # fully checksummed or fully legacy, never mixed
        self._block_crc = block_crc_enabled()
        # codec latch: one file is wholly one codec (the index names it
        # once); a mutable flag flip mid-write cannot tear a table
        self.codec = block_codec()
        # block format version this writer EMITS; the file may still
        # verbatim-carry older versions its codec accepts
        self.codec_version = 2 if self.codec == CODEC_DCZ2 else 1
        self._codec_raw_bytes = 0     # logical (raw-format) bytes
        self._codec_stored_bytes = 0  # bytes actually written
        self._key_hashes: List[np.ndarray] = []
        if async_io:
            import queue
            import threading

            self._io_q = queue.Queue(maxsize=8)
            self._io_thread = threading.Thread(
                target=self._io_loop, name="sst-io", daemon=True)
            self._io_thread.start()
        self._write(MAGIC)

    def _io_loop(self) -> None:
        while True:
            buf = self._io_q.get()
            if buf is None:
                return
            try:
                if not self._io_err:
                    self._f.write(buf)
            except BaseException as e:  # noqa: BLE001 - surfaced at join
                self._io_err.append(e)

    def _write(self, buf) -> None:
        self._offset += len(buf)
        if self._io_q is not None:
            self._io_q.put(buf)
        else:
            self._f.write(buf)

    def _join_io(self) -> None:
        if self._io_thread is not None:
            self._io_q.put(None)
            self._io_thread.join()
            self._io_thread = None
            if self._io_err:
                raise self._io_err[0]

    def _sidecar_note(self, keys: np.ndarray, key_len: np.ndarray,
                      hashes: Optional[np.ndarray] = None) -> None:
        """Record one block's full-key crc64 column for the sidecar
        structures built at finish() (bloom + phash share the ONE
        vectorized hash pass). `hashes` lets callers that already
        derived the column (the native subset kernel) skip the
        crc64_rows pass. The per-block arrays stay segmented — their
        boundaries ARE the (block, slot) numbering the phash maps to."""
        if not self.sidecar_hashes:
            return
        self._key_hashes.append(hashes if hashes is not None
                                else crc64_rows(keys, key_len))

    def add(self, key: bytes, value: bytes, expire_ts: int = 0,
            tombstone: bool = False) -> None:
        if self._last_key is not None and key <= self._last_key:
            raise ValueError("keys must be added in strictly increasing order")
        self._last_key = key
        self._pending.append((key, value, expire_ts,
                              FLAG_TOMBSTONE if tombstone else 0))
        self._count += 1
        if len(self._pending) >= self._block_capacity:
            self._flush_block()

    def _flush_block(self) -> None:
        if not self._pending:
            return
        recs = self._pending
        self._pending = []
        n = len(recs)
        width = next_bucket(max(len(k) for k, *_ in recs))
        keys = np.zeros((n, width), dtype=np.uint8)
        key_len = np.zeros(n, dtype=np.int32)
        ets = np.zeros(n, dtype=np.uint32)
        flags = np.zeros(n, dtype=np.uint8)
        offs = np.zeros(n + 1, dtype=np.uint32)
        heap_parts = []
        pos = 0
        for i, (k, v, e, fl) in enumerate(recs):
            keys[i, :len(k)] = np.frombuffer(k, dtype=np.uint8)
            key_len[i] = len(k)
            ets[i] = e
            flags[i] = fl
            offs[i] = pos
            heap_parts.append(v)
            pos += len(v)
        offs[n] = pos
        heap = b"".join(heap_parts)

        # pegasus_key_hash lo lane: crc64 of the hashkey region (or the
        # sortkey region when the hashkey is empty) — write-time work that
        # removes the crc loop from every future scan of this block
        hkl = (keys[:, 0].astype(np.int64) << 8) | keys[:, 1].astype(np.int64)
        region_len = np.where(hkl > 0, hkl, key_len.astype(np.int64) - 2)
        hash_lo = (crc64_batch(keys, region_len, start=2)
                   & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        # full-key hash column for the sidecars (bloom + phash): one
        # vectorized pass per block, folded into both at finish
        self._sidecar_note(keys, key_len)

        offset = self._offset
        # ONE buffer per block: a single kernel copy + syscall instead of
        # eight, and a single unit for the async-IO queue — and the one
        # pass the end-to-end block checksum rides (crc32 over exactly
        # the bytes that hit the disk)
        if self.codec == CODEC_NONE:
            buf = b"".join((
                _BLOCK_HDR.pack(n, width, len(heap)), keys.tobytes(),
                key_len.tobytes(), ets.tobytes(), hash_lo.tobytes(),
                flags.tobytes(), offs.tobytes(), heap))
        else:
            buf = encode_block(keys, key_len, ets, hash_lo, flags,
                               offs, heap, version=self.codec_version)
            self._codec_raw_bytes += raw_block_size(n, width, len(heap))
            self._codec_stored_bytes += len(buf)
        self._write(buf)
        self._blocks.append(BlockMeta(
            offset=offset, size=self._offset - offset, count=n,
            key_width=width, first_key=recs[0][0], last_key=recs[-1][0],
            crc=_block_crc32(buf) if self._block_crc else None))

    def add_block_columnar(self, keys: np.ndarray, key_len: np.ndarray,
                           ets: np.ndarray, hash_lo: np.ndarray,
                           flags: np.ndarray, value_offs: np.ndarray,
                           heap: bytes) -> None:
        """Append a block from ALREADY-COLUMNAR arrays (bulk compaction's
        rewrite path): no per-record Python, and hash_lo is carried over
        from the source block instead of recomputed."""
        n = int(keys.shape[0])
        if n == 0:
            return
        self._flush_block()
        first_key = bytes(keys[0, :int(key_len[0])])
        last_key = bytes(keys[-1, :int(key_len[-1])])
        if self._last_key is not None and first_key <= self._last_key:
            raise ValueError("blocks must be added in key order")
        width = int(keys.shape[1])
        self._sidecar_note(keys, key_len)
        offset = self._offset
        if self.codec == CODEC_NONE:
            buf = b"".join((
                _BLOCK_HDR.pack(n, width, len(heap)),
                np.ascontiguousarray(keys, dtype=np.uint8).tobytes(),
                np.ascontiguousarray(key_len, dtype=np.int32).tobytes(),
                np.ascontiguousarray(ets, dtype=np.uint32).tobytes(),
                np.ascontiguousarray(hash_lo, dtype=np.uint32).tobytes(),
                np.ascontiguousarray(flags, dtype=np.uint8).tobytes(),
                np.ascontiguousarray(value_offs,
                                     dtype=np.uint32).tobytes(),
                heap))
        else:
            buf = encode_block(keys, key_len, ets, hash_lo, flags,
                               value_offs, heap,
                               version=self.codec_version)
            self._codec_raw_bytes += raw_block_size(n, width, len(heap))
            self._codec_stored_bytes += len(buf)
        self._write(buf)
        self._blocks.append(BlockMeta(
            offset=offset, size=self._offset - offset, count=n,
            key_width=width, first_key=first_key, last_key=last_key,
            crc=_block_crc32(buf) if self._block_crc else None))
        self._count += n
        self._last_key = last_key

    def add_block_encoded(self, enc: EncodedBlock) -> None:
        """Append an ALREADY-ENCODED block verbatim — bulk compaction's
        untouched-block fast path on compressed stores: the on-disk
        bytes stream straight to the output with no value-heap inflate,
        no re-encode, and no re-deflate; only the bloom filter's
        full-key hashes re-derive (from the cheap key-matrix rebuild,
        which never touches the heap)."""
        if self.codec == CODEC_NONE:
            raise ValueError("writer codec is 'none'; encoded blocks "
                             "must decode first")
        n = enc.n
        if n == 0:
            return
        if not codec_accepts(self.codec, enc.version):
            # a 'dcz' writer may not embed a v2 block (an old build
            # reading the file would misparse it): transcode down
            # through the columnar path — decode never inflates the
            # value heap until the encoder's compress probe reads it
            blk = enc.decode()
            self.add_block_columnar(blk.keys, blk.key_len,
                                    blk.expire_ts, blk.hash_lo,
                                    blk.flags, blk.value_offs,
                                    blk.value_heap)
            return
        self._flush_block()
        first_key = enc.key_at(0)
        last_key = enc.key_at(n - 1)
        if self._last_key is not None and first_key <= self._last_key:
            raise ValueError("blocks must be added in key order")
        buf = enc.raw if isinstance(enc.raw, bytes) else bytes(enc.raw)
        hashes = (crc64_rows(enc.key_matrix(), enc.key_len)
                  if self.sidecar_hashes else None)
        self.add_block_encoded_raw(buf, n, enc.key_width,
                                   enc.raw_heap_len, first_key,
                                   last_key, hashes)

    def add_block_encoded_raw(self, buf: bytes, n: int, key_width: int,
                              raw_heap_len: int, first_key: bytes,
                              last_key: bytes, key_hashes) -> None:
        """Append pre-encoded block bytes with the metadata the index
        needs already in hand — the native subset kernel's exit
        (pegasus_cblock_subset emits the bloom hashes and fence keys
        in its gather pass, so nothing here re-parses the block on the
        GIL)."""
        if self.codec == CODEC_NONE:
            raise ValueError("writer codec is 'none'; encoded blocks "
                             "must decode first")
        if n == 0:
            return
        if not codec_accepts(self.codec, block_version(buf)):
            # callers (lsm's subset fast path) pre-check compatibility;
            # reaching here means a version this file's named codec
            # cannot legally contain — refuse rather than write a file
            # that other builds would misparse
            raise ValueError(
                f"block format v{block_version(buf)} cannot be stored "
                f"in a {self.codec!r} file")
        self._flush_block()
        if self._last_key is not None and first_key <= self._last_key:
            raise ValueError("blocks must be added in key order")
        if self.sidecar_hashes:
            if key_hashes is None:
                raise ValueError("sidecar build needs key hashes")
            self._sidecar_note(None, None, hashes=key_hashes)
        offset = self._offset
        self._write(buf)
        self._blocks.append(BlockMeta(
            offset=offset, size=len(buf), count=n,
            key_width=key_width, first_key=first_key,
            last_key=last_key,
            crc=_block_crc32(buf) if self._block_crc else None))
        self._codec_raw_bytes += raw_block_size(n, key_width,
                                                raw_heap_len)
        self._codec_stored_bytes += len(buf)
        self._count += n
        self._last_key = last_key

    def finish(self) -> None:
        self._flush_block()
        self._join_io()
        index = {
            "blocks": [
                {"off": b.offset, "size": b.size, "count": b.count,
                 "kw": b.key_width, "first": b.first_key.hex(),
                 "last": b.last_key.hex(),
                 **({"crc": b.crc} if b.crc is not None else {})}
                for b in self._blocks
            ],
            "meta": self._meta,
            "total_count": self._count,
        }
        if self.codec != CODEC_NONE:
            # format versioning exactly like the PR 5 block CRC: the
            # codec is named once per file; readers without the codec
            # refuse at open (never misparse), and codec=none files
            # stay bit-for-bit the legacy layout (no key at all)
            index["codec"] = self.codec
            index["codec_stats"] = {
                "raw_bytes": self._codec_raw_bytes,
                "stored_bytes": self._codec_stored_bytes,
            }
        self._build_sidecars(index)
        blob = json.dumps(index).encode()
        index_offset = self._f.tell()
        self._f.write(blob)
        self._f.write(FOOTER.pack(index_offset, len(blob), crc32(blob), MAGIC))
        self._f.flush()
        fsync_file(self._f)
        self._f.close()
        os.replace(self.path + ".tmp", self.path)
        # the rename itself must be durable BEFORE the caller truncates the
        # WAL, or a power failure can lose the SST while the WAL is already
        # empty — fsync the containing directory
        fsync_dir(os.path.dirname(self.path))

    def _build_sidecars(self, index: dict) -> None:
        """Build + persist the run's sidecar structures from the
        accumulated per-block hash columns — the ONE place every
        writer-finish site (flush / merge-compact / bulk-compact /
        ingest) derives them, so a new sidecar cannot drift across
        paths. Sections sit between the data blocks and the index; the
        index names offsets/geometry, so sidecar-less files (and
        sidecar-less READERS of the bloom) stay compatible. The phash
        entry carries a format VERSION: readers refuse versions they
        do not know at open (never misparse), exactly like the block
        codec key."""
        if not self._key_hashes:
            return
        if self.bloom_enabled:
            bf = BloomFilter.build(np.concatenate(self._key_hashes),
                                   self._bloom_bits_per_key)
            bloom_off = self._f.tell()
            blob = bf.to_bytes()
            self._f.write(blob)
            index["bloom"] = {"off": bloom_off, "size": len(blob),
                              "m": bf.m, "k": bf.k}
        if self.phash_enabled:
            # construction can fail (adversarial keys, hash
            # collisions, oversized geometry, the forced fail point):
            # a perf event — the run serves via bloom + bisect
            ph = PHashIndex.build(
                np.concatenate(self._key_hashes)
                if len(self._key_hashes) > 1 else self._key_hashes[0],
                [b.count for b in self._blocks])
            if ph is None:
                PHASH_BUILD_FAIL.increment()
            else:
                # pad the blob start to a 4-byte boundary: the mmap
                # read path hands the native probe raw u32/u16
                # pointers into the file, and the mmap base is
                # page-aligned, so an aligned file offset IS an
                # aligned address (misaligned loads are UB)
                pad = (-self._f.tell()) % 4
                if pad:
                    self._f.write(b"\x00" * pad)
                ph_off = self._f.tell()
                blob = ph.to_bytes()
                self._f.write(blob)
                index["phash"] = {"off": ph_off, "size": len(blob),
                                  **ph.meta()}

    def abandon(self) -> None:
        try:
            self._join_io()
        except BaseException:  # noqa: BLE001 - abandoning anyway
            pass
        self._f.close()
        try:
            os.remove(self.path + ".tmp")
        except OSError:
            pass


class SSTable:
    """Reader with an in-memory index and a byte-capped block cache."""

    def __init__(self, path: str,
                 cache_bytes: Optional[int] = None) -> None:
        # the decoded-block cache is BYTE-capped (LRU, like the node
        # row cache): a raw-file Block is zero-copy numpy views over
        # the mmap and charges only bookkeeping, but a block decoded
        # from a COMPRESSED file allocates (key matrix, rebuilt
        # columns, an inflated heap) what the old fixed 256-block count
        # cap could not see. A block is charged its Block.resident.
        # `cache_bytes` None -> the mutable [pegasus.storage]
        # block_cache_bytes flag.
        import io as _io
        import mmap as _mmap

        self.path = path
        self._f = open_data_file(path, "rb")
        # plaintext files are mmapped: read_block decodes ZERO-COPY numpy
        # views straight over the page cache (no read() copy, no seek
        # syscalls). Encrypted files (CipherFile) keep the read() path.
        # The map is never explicitly closed — cached Blocks hold views
        # into it, and Linux keeps the mapping alive past close()/unlink
        # until the last view dies.
        self._mv: Optional[memoryview] = None
        if isinstance(self._f, _io.BufferedReader):
            try:
                self._mv = memoryview(_mmap.mmap(
                    self._f.fileno(), 0, access=_mmap.ACCESS_READ))
            except (ValueError, OSError):
                self._mv = None  # empty file or no-mmap fs
        self._f.seek(0, os.SEEK_END)
        file_size = self._f.tell()
        if file_size < len(MAGIC) + FOOTER.size:
            raise StorageCorruptionError(path, "not an sstable (too small)")
        self._f.seek(file_size - FOOTER.size)
        index_offset, index_size, index_crc, magic = FOOTER.unpack(
            self._f.read(FOOTER.size))
        if magic not in (MAGIC, MAGIC_V1):
            raise StorageCorruptionError(path, "bad footer magic")
        self._has_hash_lo = magic == MAGIC
        self._f.seek(index_offset)
        blob = self._f.read(index_size)
        if crc32(blob) != index_crc:
            raise StorageCorruptionError(path, "index crc mismatch")
        try:
            index = json.loads(blob)
        except ValueError as e:
            # crc passed but the JSON doesn't parse: a write bug, not a
            # disk flip — still corruption at the serving surface
            raise StorageCorruptionError(path, f"index unparsable: {e}")
        self.blocks: List[BlockMeta] = [
            BlockMeta(offset=e["off"], size=e["size"], count=e["count"],
                      key_width=e["kw"], first_key=bytes.fromhex(e["first"]),
                      last_key=bytes.fromhex(e["last"]),
                      crc=e.get("crc"))
            for e in index["blocks"]
        ]
        self.meta: dict = index.get("meta", {})
        self.total_count: int = index.get("total_count", 0)
        # per-file codec negotiation: legacy files carry no key and
        # serve the raw layout unmodified; a codec this build does not
        # know is REFUSED at open (a misparse would serve garbage)
        codec = index.get("codec")
        if codec is not None and codec not in KNOWN_CODECS:
            raise StorageCorruptionError(
                path, f"unsupported block codec {codec!r} "
                      f"(known: {', '.join(KNOWN_CODECS)})")
        self.codec: Optional[str] = codec
        self.codec_stats: Optional[dict] = index.get("codec_stats")
        # pre-filter files simply miss the "bloom" entry and degrade to
        # the unfiltered path (may_contain == always True)
        self.bloom: Optional[BloomFilter] = None
        bl = index.get("bloom")
        if bl:
            if self._mv is not None:
                raw = self._mv[bl["off"]:bl["off"] + bl["size"]]
            else:
                self._f.seek(bl["off"])
                raw = self._f.read(bl["size"])
            self.bloom = BloomFilter.from_bytes(raw, bl["m"], bl["k"])
        # perfect-hash (block, slot) index: pre-index files miss the
        # entry and keep serving via bloom + bisect; an index VERSION
        # this build does not know is refused at open (a misparse
        # would locate the wrong rows), mirroring the codec rule
        self.phash: Optional[PHashIndex] = None
        ph = index.get("phash")
        if ph:
            if ph.get("version") not in KNOWN_PHASH_VERSIONS:
                raise StorageCorruptionError(
                    path, f"unsupported phash index version "
                          f"{ph.get('version')!r} (known: "
                          f"{', '.join(map(str, KNOWN_PHASH_VERSIONS))})")
            if self._mv is not None:
                raw = self._mv[ph["off"]:ph["off"] + ph["size"]]
            else:
                self._f.seek(ph["off"])
                raw = self._f.read(ph["size"])
            # torn/mismatched blob: from_bytes returns None and the
            # file degrades to the bisect path (like a torn bloom)
            self.phash = PHashIndex.from_bytes(raw, ph)
        from collections import OrderedDict as _OD

        # idx -> (Block, charged_bytes): the block's `resident` as the
        # cache last read it, tracked alongside so eviction never
        # recomputes sizes. Insert/evict accounting runs under a lock:
        # serving and compaction threads share run caches, and an
        # interleaved += / -= on _cache_bytes would drift the budget
        # for the file's whole lifetime (a hit stays lock-free unless
        # its block has grown)
        self._cache: "_OD[int, Tuple[Block, int]]" = _OD()
        self._cache_bytes = 0
        self._cache_lock = threading.Lock()
        weakref.finalize(self, _drop_charges, self._cache)
        self._cache_budget = cache_bytes  # None -> flag at use
        self._off2idx: Optional[dict] = None  # block_index lookup
        self._last_keys: Optional[List[bytes]] = None  # iter_blocks bisect
        # fence columns as plain attributes: the block list is immutable
        # for the file's lifetime, and the point-read planner compares
        # fences for every (key, table) candidate — property dispatch
        # was measurable there
        self.first_key: Optional[bytes] = (
            self.blocks[0].first_key if self.blocks else None)
        self.last_key: Optional[bytes] = (
            self.blocks[-1].last_key if self.blocks else None)

    def close(self) -> None:
        self._f.close()

    def clear_block_cache(self) -> None:
        """Drop every decoded block (and its byte accounting) — tests
        and cache-pressure tooling; the serving path never needs it."""
        with self._cache_lock:
            _drop_charges(self._cache)
            self._cache_bytes = 0

    def may_contain(self, key: bytes, key_hash: Optional[int] = None
                    ) -> bool:
        """False means definitively absent (bloom-filtered); tables
        without a filter (or with probing switched off) answer True.
        `key_hash` lets callers that already hashed the key (the
        batched probe path, or a multi-table solo get) skip the crc."""
        bf = self.bloom
        if bf is None or not bloom_probe_enabled():
            return True
        hit = (bf.may_contain_hash(key_hash) if key_hash is not None
               else bf.may_contain(key))
        if not hit:
            _BLOOM_USEFUL.increment()
            pc = _perf_current()
            if pc is not None:
                pc.bloom_pruned += 1
        return hit

    def _read_raw_block(self, idx: int):
        """(raw bytes of block `idx`, its BlockMeta), crc-verified —
        the shared cold-read step of decode / encoded-probe paths."""
        bm = self.blocks[idx]
        if self._mv is not None:
            raw = self._mv[bm.offset:bm.offset + bm.size]
        else:
            self._f.seek(bm.offset)
            raw = self._f.read(bm.size)
        # verify-on-read BEHIND the block cache: a decoded block is
        # checked exactly once per residency, so cached hits (the hot
        # path) pay nothing. Legacy blocks (crc None) serve unverified.
        if bm.crc is not None and _block_crc32(raw) != bm.crc:
            raise StorageCorruptionError(
                self.path,
                f"block {idx} crc mismatch (offset {bm.offset}, "
                f"{bm.size} bytes)")
        return raw, bm

    def read_block_encoded(self, idx: int) -> Optional[EncodedBlock]:
        """The ENCODED form of block `idx` (predicate columns parsed,
        key matrix and value heap untouched) — the direct-compute entry
        point for compaction drop masks and scan probes. None for
        uncompressed files. No cache: callers stream sequentially or
        probe once per (block, flavor) miss, and parsing is a handful
        of section views."""
        if self.codec is None:
            return None
        raw, _bm = self._read_raw_block(idx)
        return EncodedBlock.parse(raw)

    def block_index(self, bm: BlockMeta) -> int:
        """BlockMeta -> its position (offset-keyed; block offsets are
        unique and immutable for the file's lifetime)."""
        o2i = self._off2idx
        if o2i is None:
            o2i = self._off2idx = {
                b.offset: i for i, b in enumerate(self.blocks)}
        return o2i[bm.offset]

    def read_block(self, idx: int) -> Block:
        pc = _perf_current()  # the op's PerfContext (None = untracked)
        hit = self._cache.get(idx)
        if hit is not None:
            # true LRU: a hit refreshes recency (the old FIFO eviction
            # popped insertion order, so resident-forever hot blocks
            # were evicted by any cold streak)
            try:
                self._cache.move_to_end(idx)
            except KeyError:
                pass  # raced a concurrent eviction (serving vs
                # compaction threads share run caches); the decoded
                # block in hand stays valid
            blk, charged = hit
            if blk.resident != charged:
                # a lazy part was built since the cache last looked
                self._charge(idx, blk, fresh=False)
            _BLOCK_CACHE_HIT.increment()
            if pc is not None:
                pc.block_cache_hit += 1
            return blk
        _BLOCK_CACHE_MISS.increment()
        if pc is not None:
            pc.blocks_decoded += 1
            pc.bytes_read += self.blocks[idx].size
        raw, bm = self._read_raw_block(idx)
        if self.codec is not None:
            enc = EncodedBlock.parse(raw)
            blk = enc.decode()
            _COMPRESSED_DECODE.increment()
            # storage join point: a traced request that paid a cold
            # compressed-block decode records it on its span
            _trace_annotate("block_decode")
            decoded = raw_block_size(enc.n, enc.key_width,
                                     enc.raw_heap_len)
        else:
            n, width, heap_size = _BLOCK_HDR.unpack_from(raw, 0)
            pos = _BLOCK_HDR.size
            keys = np.frombuffer(raw, dtype=np.uint8, count=n * width,
                                 offset=pos).reshape(n, width)
            pos += n * width
            key_len = np.frombuffer(raw, dtype=np.int32, count=n,
                                    offset=pos)
            pos += 4 * n
            ets = np.frombuffer(raw, dtype=np.uint32, count=n, offset=pos)
            pos += 4 * n
            if self._has_hash_lo:
                hash_lo = np.frombuffer(raw, dtype=np.uint32, count=n,
                                        offset=pos)
                pos += 4 * n
            else:
                hash_lo = None  # v1 file: predicate path computes on device
            flags = np.frombuffer(raw, dtype=np.uint8, count=n, offset=pos)
            pos += n
            offs = np.frombuffer(raw, dtype=np.uint32, count=n + 1,
                                 offset=pos)
            pos += 4 * (n + 1)
            heap = np.frombuffer(raw, dtype=np.uint8, count=heap_size,
                                 offset=pos)
            # zero-copy views over the page cache, or over a real
            # read() copy on encrypted stores: Block.resident tells them
            # apart, as it does for a compressed file's RAW heap
            blk = Block(keys, key_len, ets, hash_lo, flags, offs, heap)
            decoded = bm.size
        if pc is not None:
            # materialized bytes after the codec: the raw layout's size
            # of a compressed block, the on-disk (zero-copy view) size
            # of a raw one — against bytes_read this is the decode ratio
            pc.bytes_decoded += decoded
        self._charge(idx, blk, fresh=True)
        return blk

    def _charge(self, idx: int, blk: Block, fresh: bool) -> None:
        """Enter `blk`'s charge (`fresh`: a block just decoded) or
        bring it up to what the block has grown to since, and evict
        from the cold end while the table is over its budget."""
        budget = (self._cache_budget if self._cache_budget is not None
                  else block_cache_budget())
        nbytes = blk.resident
        evicted = 0
        with self._cache_lock:
            prev = self._cache.get(idx)
            if not fresh and (prev is None or prev[0] is not blk):
                return  # evicted or replaced since the hit
            # prev on a fresh insert: two threads raced the same cold
            # block (serving + compaction share run caches): the
            # overwrite must release the first insert's charge or the
            # budget drifts up by one block per race, forever
            grown = nbytes - (prev[1] if prev is not None else 0)
            self._cache[idx] = (blk, nbytes)
            self._cache_bytes += grown
            while self._cache_bytes > budget and len(self._cache) > 1:
                _k, (_b, nb) = self._cache.popitem(last=False)
                self._cache_bytes -= nb
                evicted += nb
        if evicted:
            _BLOCK_EVICT_BYTES.increment(evicted)
        _resident_add(grown - evicted)

    def verify_block(self, idx: int) -> bool:
        """Scrub entry point: re-read block `idx`'s raw bytes and check
        them against the index CRC — no decode, no block-cache
        pollution (a scrub walking a cold table must not evict the
        serving working set). Returns False for legacy blocks (nothing
        to verify); raises StorageCorruptionError on a mismatch."""
        bm = self.blocks[idx]
        if bm.crc is None:
            return False
        if self._mv is not None:
            raw = self._mv[bm.offset:bm.offset + bm.size]
        else:
            self._f.seek(bm.offset)
            raw = self._f.read(bm.size)
        if len(raw) != bm.size or _block_crc32(raw) != bm.crc:
            raise StorageCorruptionError(
                self.path,
                f"scrub: block {idx} crc mismatch (offset {bm.offset}, "
                f"{bm.size} bytes)")
        return True

    def verify_index_consistency(self) -> None:
        """Scrub's structural pass: block fences must be internally
        ordered and monotonic across the file; (when a filter exists)
        every block's first key must answer 'maybe' from the bloom
        filter; and (when a perfect-hash index exists) every block's
        first key must locate to exactly (that block, slot 0) — a
        sidecar that denies or mislocates a present key would turn
        into silent NotFound under probe pruning, which is data loss
        without a single flipped data byte. A corrupt/stale phash is
        therefore caught by the same quarantine/re-learn loop the
        block CRCs feed."""
        prev_last: Optional[bytes] = None
        for i, bm in enumerate(self.blocks):
            if bm.first_key > bm.last_key:
                raise StorageCorruptionError(
                    self.path, f"scrub: block {i} fence inverted")
            if prev_last is not None and bm.first_key <= prev_last:
                raise StorageCorruptionError(
                    self.path, f"scrub: block {i} overlaps block {i - 1}")
            prev_last = bm.last_key
            if self.bloom is not None and \
                    not self.bloom.may_contain(bm.first_key):
                raise StorageCorruptionError(
                    self.path,
                    f"scrub: bloom filter denies resident key "
                    f"(block {i} first key)")
            if self.phash is not None:
                loc = self.phash.lookup_hash(crc64(bm.first_key))
                if loc < 0 or self.phash.unpack(loc) != (i, 0):
                    raise StorageCorruptionError(
                        self.path,
                        f"scrub: phash index denies or mislocates "
                        f"resident key (block {i} first key)")

    def index_memory(self) -> dict:
        """Resident sidecar bytes: {"bloom": ..., "phash": ...} — the
        per-structure split behind the node's index-memory signal."""
        return {
            "bloom": (self.bloom.bits.nbytes
                      if self.bloom is not None else 0),
            "phash": (self.phash.mem_bytes()
                      if self.phash is not None else 0),
        }

    def get(self, key: bytes, key_hash: Optional[int] = None
            ) -> Optional[Tuple[Optional[bytes], int]]:
        """Returns (value|None-for-tombstone, expire_ts), or None if absent.

        `key_hash` (crc64 of the full key, the same hash every sidecar
        shares) lets callers that already hashed skip the crc. Indexed
        files answer via the perfect-hash index: a miss costs one slot
        gather and ZERO block touches; a hit reads its (block, slot)
        row directly — no fence bisect, no in-block bisect — and one
        row compare rejects the rare fingerprint collision."""
        ph = self.phash
        if ph is not None and phash_probe_enabled():
            pc = _perf_current()
            h = key_hash if key_hash is not None else crc64(key)
            loc = ph.lookup_hash(h)
            if loc < 0:
                PHASH_USEFUL.increment()
                if pc is not None:
                    pc.phash_pruned += 1
                return None
            bi, slot = ph.unpack(loc)
            if bi < len(self.blocks) and slot < self.blocks[bi].count:
                blk = self.read_block(bi)
                if blk.key_at(slot) == key:
                    PHASH_HIT.increment()
                    if pc is not None:
                        pc.phash_located += 1
                    if blk.is_tombstone(slot):
                        return (None, 0)
                    return (blk.value_at(slot),
                            int(blk.expire_ts[slot]))
                PHASH_USEFUL.increment()
                if pc is not None:
                    pc.phash_pruned += 1
                return None  # fp collision: definitively absent
            # out-of-range loc (corrupt index): serve via the bisect
            # below; the scrub structural pass flags the file
        idx = self._block_for_key(key)
        if idx is None:
            return None
        blk = self.read_block(idx)
        kl = blk._key_list
        if kl is None and blk._gets >= 4:
            kl = blk.key_list()  # hot block: slice-free bisects from now on
        if kl is not None:
            lo = bisect.bisect_left(kl, key)
            found = lo < blk.count and kl[lo] == key
        else:
            # cold block: O(log N) row probes, no full materialization
            blk._gets += 1
            lo, hi = 0, blk.count
            while lo < hi:
                mid = (lo + hi) // 2
                if blk.key_at(mid) < key:
                    lo = mid + 1
                else:
                    hi = mid
            found = lo < blk.count and blk.key_at(lo) == key
        if found:
            if blk.is_tombstone(lo):
                return (None, 0)
            return (blk.value_at(lo), int(blk.expire_ts[lo]))
        return None

    def _block_for_key(self, key: bytes) -> Optional[int]:
        lo, hi = 0, len(self.blocks)
        while lo < hi:
            mid = (lo + hi) // 2
            if self.blocks[mid].last_key < key:
                lo = mid + 1
            else:
                hi = mid
        if lo == len(self.blocks):
            return None
        return lo if self.blocks[lo].first_key <= key else None

    def iterate(self, start: bytes = b"", stop: Optional[bytes] = None,
                reverse: bool = False
                ) -> Iterator[Tuple[bytes, Optional[bytes], int]]:
        """Yield (key, value|None-for-tombstone, expire_ts) in range."""
        if not self.blocks:
            return
        if reverse:
            block_range = range(len(self.blocks) - 1, -1, -1)
        else:
            block_range = range(len(self.blocks))
        for bi in block_range:
            bm = self.blocks[bi]
            if stop is not None and bm.first_key >= stop:
                if reverse:
                    continue
                break
            if start and bm.last_key < start:
                if reverse:
                    break
                continue
            blk = self.read_block(bi)
            idxs = range(blk.count - 1, -1, -1) if reverse else range(blk.count)
            for i in idxs:
                k = blk.key_at(i)
                if start and k < start:
                    continue
                if stop is not None and k >= stop:
                    continue
                v = None if blk.is_tombstone(i) else blk.value_at(i)
                yield k, v, int(blk.expire_ts[i])

    def iter_blocks(self, start: bytes = b"", stop: Optional[bytes] = None
                    ) -> Iterator[Tuple[BlockMeta, Block]]:
        """Yield whole blocks intersecting [start, stop) — the device fast
        path: callers feed Block columns directly to the predicate kernels.
        The first candidate is found by bisect over the cached last-key
        column (scans start mid-table constantly; a linear walk from
        block 0 was the planner's hottest loop)."""
        lk = self._last_keys
        if lk is None:
            lk = self._last_keys = [b.last_key for b in self.blocks]
        bi = bisect.bisect_left(lk, start) if start else 0
        for bi in range(bi, len(self.blocks)):
            bm = self.blocks[bi]
            if stop is not None and bm.first_key >= stop:
                break
            yield bm, self.read_block(bi)
