"""Per-block SST compression with direct compute on the encoded form.

The LSM-OPD design point (PAPERS.md): compression must not tax the
vectorized read path, so the encoded layout keeps every PREDICATE
column directly addressable — the batched scan/filter kernels evaluate
TTL masks, partition-hash ownership, and hashkey/sortkey pattern
filters against the encoded representation, and the expensive
materialization (padded key matrix + value heap inflate) is deferred
to row assembly of surviving records.

Codec ``dcz`` (dictionary + columnar + zlib):

    header      fixed 48-byte struct (section geometry + mode bytes)
    expire_ts   uint32[n]   RAW — the per-second TTL mask reads it in
                            place (omitted when every row is TTL-free)
    hash_lo     uint32[n]   RAW — stale-split / ownership checks and
                            scan hash validation need no key decode
    dict_offs   uint32[D+1] hashkey dictionary offsets
    key_len     n x {1,2,4} narrowed ints
    value_len   n x {1,2,4} narrowed ints (offsets rebuild by cumsum)
    hk_idx      n x {2,4}   per-row dictionary slot (sorted keys make
                            equal hashkeys adjacent, so D << n;
                            sentinel = malformed row stored raw)
    flags       uint8[n]    omitted when all zero (L1 blocks carry no
                            tombstones)
    dict bytes  D unique hashkeys, concatenated
    sortkey heap            per-row sortkey bytes, concatenated (the
                            pow2-padded key matrix is NOT stored — the
                            padding and the repeated hashkeys are the
                            bulk of the key-side waste)
    value heap  zstd(level 1) (zlib level 1 when libzstd is absent)
                            when an entropy + sample-compress probe
                            proves the heap compressible, RAW
                            otherwise (see _maybe_deflate: even fast
                            compressors waste work on data they cannot
                            shrink, and the incompressible case must
                            not pay decompress on every cold read; the
                            heap_mode byte records which compressor
                            wrote the heap, so zlib- and zstd-heap
                            blocks serve side by side)

Decoding reproduces the raw block's columns byte-for-byte (zero
padding, dtypes, offsets), so every downstream consumer — predicate
kernels, native page assembly, point probes — sees exactly the block
it would have seen from an uncompressed file. The per-block CRC is
computed over the ON-DISK (encoded) bytes, which keeps the PR 5
scrubber's raw re-read path working unchanged.

Codec ``dcz2`` (the PR 8 follow-on): same family, two column upgrades
on the until-now-raw uint32 predicate columns, stamped per BLOCK via
the header's format byte so one dcz2 FILE may verbatim-carry legacy v1
blocks (compaction copies untouched blocks without transcoding):

    expire_ts   FOR/delta: u32 base (min nonzero) + {u8,u16} per-row
                delta_plus1 (0 keeps meaning "no TTL"); falls back to
                raw u32 when the spread overflows u16, omitted when
                all-zero — exactly the old ets_mode=0 case
    hash_lo     dictionary-indexed: rows sharing a hashkey share its
                crc64 lane, so the column stores one u32 PER DICT SLOT
                plus a row-ordered overflow array for rows whose hash
                is not slot-derivable (malformed keys, empty hashkeys
                — an empty hashkey hashes the per-row SORTKEY region)

Format versioning follows the PR 7 rule: new files stamp codec "dcz2"
in the index (builds without it refuse at open, never misparse);
legacy "dcz" files keep serving; "none" stays bit-for-bit; and a "dcz"
WRITER never emits a v2 block (down-transcoding instead), so a file's
named codec always bounds what is inside it.
"""

from __future__ import annotations

import struct
import zlib
from typing import List, Optional, Tuple

import numpy as np

CODEC_NONE = "none"
CODEC_DCZ = "dcz"
CODEC_DCZ2 = "dcz2"
KNOWN_CODECS = (CODEC_DCZ, CODEC_DCZ2)

# block format versions the dcz-family codecs may contain: a file's
# index-named codec BOUNDS the block versions inside it, so an old
# build that knows only "dcz" can never meet a v2 block it would
# misparse (it refuses "dcz2" files at open)
_CODEC_VERSIONS = {CODEC_DCZ: (1,), CODEC_DCZ2: (1, 2)}


def codec_accepts(codec: str, version: int) -> bool:
    """May a file stamped `codec` contain a block of `version`? The
    verbatim-copy / encoded-subset fast paths gate on this: an
    incompatible block transcodes through decode->re-encode instead."""
    return version in _CODEC_VERSIONS.get(codec, ())


def block_version(buf) -> int:
    """Format version of one encoded block's bytes (header fmt byte;
    pre-dcz2 writers zeroed it, so 0 reads as version 1)."""
    return 2 if buf[46] == 2 else 1


# n, key_width, raw_heap, comp_heap, sk_bytes, dict_n, dict_bytes,
# klen_w, vlen_w, idx_w, flags_mode, ets_mode, heap_mode, fmt, pad
# (fmt was a zeroed pad byte before dcz2 — 0 therefore means v1)
_CBLK_HDR = struct.Struct("<IIQQQIIBBBBBBBx")

_HEAP_RAW = 0
_HEAP_ZLIB = 1
_HEAP_ZSTD = 2
_ZLIB_LEVEL = 1  # compressor speed is on the compaction critical path
_ZSTD_LEVEL = 1


class _Zstd:
    """ctypes binding to the system libzstd (the stdlib has no zstd
    before 3.14 and the container must not gain pip deps). Level-1
    zstd compresses ~6x faster than zlib-1 at a similar ratio — on the
    compaction critical path that difference is the whole game — so
    encode prefers it and falls back to zlib only when the shared
    library is missing. Decode supports both heap modes regardless."""

    _lib = None
    _tried = False

    @classmethod
    def lib(cls):
        if not cls._tried:
            cls._tried = True
            import ctypes

            for name in ("libzstd.so.1", "libzstd.so"):
                try:
                    lib = ctypes.CDLL(name)
                except OSError:
                    continue
                try:
                    lib.ZSTD_compressBound.restype = ctypes.c_size_t
                    lib.ZSTD_compressBound.argtypes = [ctypes.c_size_t]
                    lib.ZSTD_compress.restype = ctypes.c_size_t
                    lib.ZSTD_compress.argtypes = [
                        ctypes.c_void_p, ctypes.c_size_t,
                        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
                    lib.ZSTD_decompress.restype = ctypes.c_size_t
                    lib.ZSTD_decompress.argtypes = [
                        ctypes.c_void_p, ctypes.c_size_t,
                        ctypes.c_void_p, ctypes.c_size_t]
                    lib.ZSTD_isError.restype = ctypes.c_uint
                    lib.ZSTD_isError.argtypes = [ctypes.c_size_t]
                except AttributeError:
                    continue
                cls._lib = lib
                break
        return cls._lib

    @classmethod
    def compress(cls, data: bytes, level: int = _ZSTD_LEVEL):
        lib = cls.lib()
        if lib is None:
            return None
        import ctypes

        bound = lib.ZSTD_compressBound(len(data))
        out = ctypes.create_string_buffer(bound)
        n = lib.ZSTD_compress(out, bound, data, len(data), level)
        if lib.ZSTD_isError(n):
            return None
        return out.raw[:n]

    @classmethod
    def decompress(cls, comp, raw_len: int) -> bytes:
        lib = cls.lib()
        if lib is None:
            raise RuntimeError(
                "block heap is zstd-compressed but libzstd is not "
                "resolvable on this host")
        import ctypes

        comp = bytes(comp)
        out = ctypes.create_string_buffer(raw_len if raw_len else 1)
        n = lib.ZSTD_decompress(out, raw_len, comp, len(comp))
        if lib.ZSTD_isError(n) or n != raw_len:
            raise ValueError("zstd heap decompression failed")
        return out.raw[:raw_len]

# compressor throughput COLLAPSES on the data it cannot shrink
# (measured on this box with zlib-1: 13 MB/s on random bytes, 20 MB/s
# at ratio 0.835 on printable-random — vs 350 MB/s at ratio 0.3 on
# structured payloads and a ~300 MB/s disk it is trying to outrun;
# zstd-1 degrades far less but an incompressible heap stored
# compressed still taxes every cold read with a pointless decompress),
# so the full pass runs only when two cheap probes prove the heap
# genuinely compressible: a byte-histogram entropy estimate on a 16 KB
# sample (near-8-bit heaps store raw, ~40 µs), then a sample compress
# that must clear a 30% gain — the marginal regime between 5% and 30%
# is a net loss on the compaction critical path, where a small byte
# saving loses to just writing them at disk speed.
_PROBE_SAMPLE = 1 << 14
_PROBE_MAX_ENTROPY_BITS = 7.5
_PROBE_MAX_RATIO = 0.70
_KEEP_MAX_RATIO = 0.95


def _compress_heap(data: bytes) -> Tuple[int, bytes]:
    comp = _Zstd.compress(data)
    if comp is not None:
        return _HEAP_ZSTD, comp
    return _HEAP_ZLIB, zlib.compress(data, _ZLIB_LEVEL)


def _maybe_deflate(heap_bytes: bytes) -> Tuple[int, bytes]:
    """(heap_mode, stored bytes) — compression gated by
    compressibility."""
    n = len(heap_bytes)
    if n > _PROBE_SAMPLE:
        a = np.frombuffer(heap_bytes, dtype=np.uint8,
                          count=_PROBE_SAMPLE)
        cnt = np.bincount(a, minlength=256).astype(np.float64)
        p = cnt[cnt > 0] / a.size
        if float(-(p * np.log2(p)).sum()) >= _PROBE_MAX_ENTROPY_BITS:
            return _HEAP_RAW, heap_bytes
        sample = heap_bytes[:_PROBE_SAMPLE]
        if len(_compress_heap(sample)[1]) \
                > len(sample) * _PROBE_MAX_RATIO:
            return _HEAP_RAW, heap_bytes
    elif n == 0:
        return _HEAP_RAW, heap_bytes
    mode, comp = _compress_heap(heap_bytes)
    if len(comp) < n * _KEEP_MAX_RATIO:
        return mode, comp
    return _HEAP_RAW, heap_bytes


def _width_for(maxv: int) -> int:
    if maxv < (1 << 8):
        return 1
    if maxv < (1 << 16):
        return 2
    return 4


_NARROW = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def _ragged_gather(flat: np.ndarray, starts: np.ndarray,
                   lens: np.ndarray) -> np.ndarray:
    """Concatenate flat[starts[i] : starts[i]+lens[i]] for all i in one
    vectorized pass (the per-row loop this replaces is the encode hot
    loop)."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.uint8)
    cum = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=cum[1:])
    pos = (np.repeat(starts - cum[:-1], lens)
           + np.arange(total, dtype=np.int64))
    return flat[pos]


def ragged_scatter(dst: np.ndarray, dst_starts: np.ndarray,
                    src: np.ndarray, src_starts: np.ndarray,
                    lens: np.ndarray) -> None:
    """dst[dst_starts[i]:+lens[i]] = src[src_starts[i]:+lens[i]]."""
    total = int(lens.sum())
    if total == 0:
        return
    cum = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=cum[1:])
    intra = np.arange(total, dtype=np.int64) - np.repeat(cum[:-1], lens)
    dst[np.repeat(dst_starts, lens) + intra] = \
        src[np.repeat(src_starts, lens) + intra]


def _ets_for_encode(ets: np.ndarray):
    """(ets_mode, [section bytes]) for the v2 FOR/delta expire_ts
    column: mode 0 = all-zero (omitted), 1/2 = u32 base + per-row
    delta_plus1 narrowed to u8/u16 (0 stays 0 — "no TTL"), 4 = raw
    u32 fallback when the nonzero spread overflows u16."""
    if not ets.any():
        return 0, []
    nz = ets[ets != 0]
    base = int(nz.min())
    spread = int(nz.max()) - base + 1
    if spread <= 0xFF:
        w = 1
    elif spread <= 0xFFFF:
        w = 2
    else:
        return 4, [ets.tobytes()]
    d = np.where(ets == 0, 0,
                 ets.astype(np.int64) - base + 1).astype(_NARROW[w])
    return w, [struct.pack("<I", base), d.tobytes()]


def _ets_for_decode(mode: int, raw, pos: int, n: int):
    """Inverse of _ets_for_encode: (expire_ts uint32[n], bytes read)."""
    if mode == 0:
        return np.zeros(n, dtype=np.uint32), 0
    if mode == 4:
        return np.frombuffer(raw, dtype=np.uint32, count=n,
                             offset=pos), 4 * n
    (base,) = struct.unpack_from("<I", raw, pos)
    d = np.frombuffer(raw, dtype=_NARROW[mode], count=n,
                      offset=pos + 4).astype(np.int64)
    ets = np.where(d == 0, 0, base + d - 1).astype(np.uint32)
    return ets, 4 + mode * n


def encode_block(keys: np.ndarray, key_len: np.ndarray, ets: np.ndarray,
                 hash_lo: np.ndarray, flags: np.ndarray,
                 value_offs: np.ndarray, heap,
                 version: int = 1) -> bytes:
    """Raw columnar block -> dcz bytes. `keys` is the zero-padded
    uint8[n, W] matrix exactly as the raw format would store it.
    `version` 1 writes the original dcz layout bit-for-bit; 2 writes
    the dcz2 layout (FOR expire_ts + dictionary-indexed hash_lo)."""
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    n, width = keys.shape
    key_len = np.asarray(key_len, dtype=np.int32)
    ets = np.asarray(ets, dtype=np.uint32)
    hash_lo = np.asarray(hash_lo, dtype=np.uint32)
    flags = np.asarray(flags, dtype=np.uint8)
    value_offs = np.asarray(value_offs, dtype=np.uint32)
    if int(value_offs[0]) != 0:
        raise ValueError("value_offs must start at 0")
    if isinstance(heap, np.ndarray):
        heap_bytes = np.ascontiguousarray(heap, dtype=np.uint8).tobytes()
    else:
        heap_bytes = bytes(heap)

    kl64 = key_len.astype(np.int64)
    hkl = np.where(
        kl64 >= 2,
        (keys[:, 0].astype(np.int64) << 8) | keys[:, 1].astype(np.int64),
        np.int64(-1))
    normal = (kl64 >= 2) & (hkl >= 0) & (hkl <= kl64 - 2)

    # group adjacent rows sharing one hashkey (keys are sorted, and the
    # 2-byte length header sorts same-length hashkeys together, so equal
    # hashkeys are always adjacent): a row continues its predecessor's
    # group iff both are well-formed, the headers agree, and the first
    # differing byte lies past the hashkey region
    if n > 1:
        diff = keys[1:] != keys[:-1]
        any_diff = diff.any(axis=1)
        first_diff = np.where(any_diff, diff.argmax(axis=1),
                              np.int64(width))
        same_hk = ((hkl[1:] == hkl[:-1])
                   & (first_diff >= 2 + hkl[1:])
                   & normal[1:] & normal[:-1])
    else:
        same_hk = np.zeros(0, dtype=bool)
    new_group = np.ones(n, dtype=bool)
    new_group[1:] = ~same_hk
    gid = np.cumsum(new_group) - 1                  # group id per row
    leaders = np.flatnonzero(new_group)             # leader row per group
    leader_normal = normal[leaders]
    # dictionary slots number the normal-leader groups in order; a
    # normal row always sits in a group whose leader is normal (a
    # malformed predecessor can never chain into same_hk)
    dict_of_group = np.cumsum(leader_normal) - 1
    dict_rows = leaders[leader_normal]
    dict_n = int(dict_rows.size)

    idx_w = 2 if dict_n < 0xFFFF else 4
    sentinel = (1 << (8 * idx_w)) - 1
    hk_idx = np.where(normal, dict_of_group[gid], np.int64(sentinel))

    flat = keys.reshape(-1)
    dict_lens = hkl[dict_rows]
    dict_heap = _ragged_gather(flat, dict_rows * width + 2, dict_lens)
    dict_offs = np.zeros(dict_n + 1, dtype=np.uint32)
    if dict_n:
        dict_offs[1:] = np.cumsum(dict_lens)

    sk_start = np.where(normal, 2 + hkl, np.int64(0))
    sk_len = np.where(normal, kl64 - 2 - hkl, kl64)
    sk_heap = _ragged_gather(flat, np.arange(n, dtype=np.int64) * width
                             + sk_start, sk_len)

    vlens = np.diff(value_offs.astype(np.int64))
    klen_w = _width_for(int(kl64.max()) if n else 0)
    vlen_w = _width_for(int(vlens.max()) if n else 0)
    flags_mode = 1 if flags.any() else 0

    heap_mode, heap_out = _maybe_deflate(heap_bytes)

    if version == 2:
        ets_mode, ets_parts = _ets_for_encode(ets)
        # hash_lo is crc64 of the HASHKEY region, constant across a
        # dictionary group — store one u32 per slot. Rows whose hash
        # is not slot-derivable (malformed keys, and empty hashkeys,
        # whose hash covers the per-row SORTKEY region) append to a
        # row-ordered overflow array the decoder consumes in turn.
        slot_ok = normal & (hkl > 0)
        slot_hash = hash_lo[dict_rows]
        overflow = hash_lo[~slot_ok]
        parts = [_CBLK_HDR.pack(
            n, width, len(heap_bytes), len(heap_out),
            int(sk_len.sum()), dict_n, int(dict_offs[-1]), klen_w,
            vlen_w, idx_w, flags_mode, ets_mode, heap_mode, 2)]
        parts.extend(ets_parts)
        parts.append(dict_offs.tobytes())
        parts.append(key_len.astype(_NARROW[klen_w]).tobytes())
        parts.append(vlens.astype(_NARROW[vlen_w]).tobytes())
        parts.append(hk_idx.astype(_NARROW[idx_w]).tobytes())
        if flags_mode:
            parts.append(flags.tobytes())
        parts.append(slot_hash.tobytes())
        parts.append(overflow.tobytes())
        parts.append(dict_heap.tobytes())
        parts.append(sk_heap.tobytes())
        parts.append(heap_out)
        return b"".join(parts)

    ets_mode = 4 if ets.any() else 0
    parts: List[bytes] = [_CBLK_HDR.pack(
        n, width, len(heap_bytes), len(heap_out), int(sk_len.sum()),
        dict_n, int(dict_offs[-1]), klen_w, vlen_w, idx_w, flags_mode,
        ets_mode, heap_mode, 0)]
    if ets_mode:
        parts.append(ets.tobytes())
    parts.append(hash_lo.tobytes())
    parts.append(dict_offs.tobytes())
    parts.append(key_len.astype(_NARROW[klen_w]).tobytes())
    parts.append(vlens.astype(_NARROW[vlen_w]).tobytes())
    parts.append(hk_idx.astype(_NARROW[idx_w]).tobytes())
    if flags_mode:
        parts.append(flags.tobytes())
    parts.append(dict_heap.tobytes())
    parts.append(sk_heap.tobytes())
    parts.append(heap_out)
    return b"".join(parts)


def raw_block_size(n: int, width: int, heap_len: int) -> int:
    """On-disk size the RAW format would use for the same block — the
    'logical bytes' side of the compression-ratio accounting."""
    # _BLOCK_HDR(16) + keys + key_len + ets + hash_lo + flags + offs
    return 16 + n * width + 4 * n + 4 * n + 4 * n + n + 4 * (n + 1) \
        + heap_len


class EncodedBlock:
    """Parsed (NOT decoded) dcz block: every predicate column is a
    zero-copy view over the on-disk bytes; the key matrix and value
    heap materialize only on demand."""

    __slots__ = ("raw", "n", "key_width", "key_len", "expire_ts",
                 "hash_lo", "flags", "hk_idx", "dict_offs", "dict_heap",
                 "sk_heap", "sk_offs", "hk_len", "value_offs",
                 "_heap_comp", "heap_mode", "raw_heap_len",
                 "has_malformed", "_sentinel", "version", "_keys")

    @property
    def count(self) -> int:
        return self.n

    @property
    def keys(self) -> np.ndarray:
        """The key matrix, rebuilt on first use and kept: what a filter
        program with rules reads of a block whose bytes the compaction
        then copies or subsets as they are (the value heap stays
        deflated)."""
        try:
            return self._keys
        except AttributeError:
            self._keys = self.key_matrix()
            return self._keys

    @staticmethod
    def parse(raw) -> "EncodedBlock":
        self = EncodedBlock()
        self.raw = raw
        buf = np.frombuffer(raw, dtype=np.uint8)
        (n, width, raw_heap, comp_heap, sk_bytes, dict_n, dict_bytes,
         klen_w, vlen_w, idx_w, flags_mode, ets_mode, heap_mode,
         fmt) = _CBLK_HDR.unpack_from(raw, 0)
        self.version = 2 if fmt == 2 else 1
        self.n, self.key_width = n, width
        self.raw_heap_len = raw_heap
        self.heap_mode = heap_mode
        self._sentinel = (1 << (8 * idx_w)) - 1
        pos = _CBLK_HDR.size
        if self.version == 2:
            self.expire_ts, adv = _ets_for_decode(ets_mode, raw, pos, n)
            pos += adv
        elif ets_mode:
            self.expire_ts = np.frombuffer(raw, dtype=np.uint32,
                                           count=n, offset=pos)
            pos += 4 * n
        else:
            self.expire_ts = np.zeros(n, dtype=np.uint32)
        if self.version == 1:
            self.hash_lo = np.frombuffer(raw, dtype=np.uint32, count=n,
                                         offset=pos)
            pos += 4 * n
        self.dict_offs = np.frombuffer(raw, dtype=np.uint32,
                                       count=dict_n + 1, offset=pos)
        pos += 4 * (dict_n + 1)
        self.key_len = np.frombuffer(
            raw, dtype=_NARROW[klen_w], count=n,
            offset=pos).astype(np.int32)
        pos += klen_w * n
        vlens = np.frombuffer(raw, dtype=_NARROW[vlen_w], count=n,
                              offset=pos)
        pos += vlen_w * n
        offs = np.zeros(n + 1, dtype=np.uint32)
        if n:
            offs[1:] = np.cumsum(vlens, dtype=np.int64).astype(np.uint32)
        self.value_offs = offs
        self.hk_idx = np.frombuffer(raw, dtype=_NARROW[idx_w], count=n,
                                    offset=pos).astype(np.int64)
        pos += idx_w * n
        if flags_mode:
            self.flags = np.frombuffer(raw, dtype=np.uint8, count=n,
                                       offset=pos)
            pos += n
        else:
            self.flags = np.zeros(n, dtype=np.uint8)

        normal = self.hk_idx != self._sentinel
        self.has_malformed = bool((~normal).any())
        do64 = self.dict_offs.astype(np.int64)
        hk_len = np.zeros(n, dtype=np.int64)
        ni = self.hk_idx[normal]
        hk_len[normal] = do64[ni + 1] - do64[ni]
        self.hk_len = hk_len

        if self.version == 2:
            # dictionary-indexed hash column: one u32 per slot, plus a
            # row-ordered overflow for rows whose hash is not
            # slot-derivable (sentinel / empty hashkey — the hash then
            # covers the per-row sortkey region, unique per row)
            slot_ok = normal & (hk_len > 0)
            n_over = n - int(slot_ok.sum())
            slot_hash = np.frombuffer(raw, dtype=np.uint32,
                                      count=dict_n, offset=pos)
            pos += 4 * dict_n
            overflow = np.frombuffer(raw, dtype=np.uint32,
                                     count=n_over, offset=pos)
            pos += 4 * n_over
            hash_lo = np.empty(n, dtype=np.uint32)
            hash_lo[slot_ok] = slot_hash[self.hk_idx[slot_ok]]
            hash_lo[~slot_ok] = overflow
            self.hash_lo = hash_lo

        self.dict_heap = np.frombuffer(raw, dtype=np.uint8,
                                       count=dict_bytes, offset=pos)
        pos += dict_bytes
        self.sk_heap = np.frombuffer(raw, dtype=np.uint8,
                                     count=sk_bytes, offset=pos)
        pos += sk_bytes
        self._heap_comp = buf[pos:pos + comp_heap]

        kl64 = self.key_len.astype(np.int64)
        sk_len = np.where(normal, kl64 - 2 - hk_len, kl64)
        so = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(sk_len, out=so[1:])
        self.sk_offs = so
        return self

    # ---- direct compute ------------------------------------------------

    def key_at(self, i: int) -> bytes:
        """One key materialized from the dictionary + sortkey heap —
        the bisect/fence primitive, no block decode."""
        sk = self.sk_heap[self.sk_offs[i]:self.sk_offs[i + 1]].tobytes()
        if int(self.hk_idx[i]) == self._sentinel:
            return sk
        d = int(self.hk_idx[i])
        hk = self.dict_heap[
            self.dict_offs[d]:self.dict_offs[d + 1]].tobytes()
        return struct.pack(">H", len(hk)) + hk + sk

    def dict_entries(self) -> List[bytes]:
        """The block's unique hashkeys (pattern filters evaluate once
        per entry instead of once per row)."""
        do = self.dict_offs
        return [self.dict_heap[do[d]:do[d + 1]].tobytes()
                for d in range(len(do) - 1)]

    # ---- materialization ----------------------------------------------

    def key_matrix(self) -> np.ndarray:
        """Rebuild the zero-padded uint8[n, W] key matrix (native
        kernel when available) WITHOUT touching the value heap — bloom
        builds and key-only paths stay inflate-free."""
        from pegasus_tpu import native

        n, width = self.n, self.key_width
        out = np.zeros((n, width), dtype=np.uint8)
        if n == 0:
            return out
        fn = native.cblock_decode_keys_fn()
        idx32 = np.ascontiguousarray(
            np.where(self.hk_idx == self._sentinel,
                     np.int64(0xFFFFFFFF), self.hk_idx)
            .astype(np.uint32))
        if fn is not None:
            fn(np.ascontiguousarray(self.dict_heap),
               np.ascontiguousarray(self.dict_offs), idx32,
               np.ascontiguousarray(self.sk_heap),
               np.ascontiguousarray(self.sk_offs),
               np.ascontiguousarray(self.key_len), n, width, out)
            return out
        # numpy fallback: two ragged scatters + vectorized headers
        flat = out.reshape(-1)
        rows = np.arange(n, dtype=np.int64)
        normal = self.hk_idx != self._sentinel
        hk_len = self.hk_len
        nrm = np.flatnonzero(normal)
        if nrm.size:
            hl = hk_len[nrm]
            out[nrm, 0] = (hl >> 8).astype(np.uint8)
            out[nrm, 1] = (hl & 0xFF).astype(np.uint8)
            ragged_scatter(flat, nrm * width + 2, self.dict_heap,
                            self.dict_offs.astype(np.int64)[
                                self.hk_idx[nrm]], hl)
        sk_start = np.where(normal, 2 + hk_len, np.int64(0))
        sk_len = self.sk_offs[1:] - self.sk_offs[:-1]
        ragged_scatter(flat, rows * width + sk_start, self.sk_heap,
                        self.sk_offs[:-1], sk_len)
        return out

    def decode(self):
        """Full materialization to the standard columnar Block — the
        value heap stays a lazy thunk until a survivor's bytes are
        actually read."""
        from pegasus_tpu.storage.sstable import Block

        return Block(self.key_matrix(), self.key_len, self.expire_ts,
                     self.hash_lo, self.flags, self.value_offs,
                     self._heap_comp if self.heap_mode == _HEAP_RAW
                     else LazyHeap(self._heap_comp, self.heap_mode,
                                   self.raw_heap_len))


class LazyHeap:
    """A deflated value heap, inflated by the call. It holds the stored
    bytes (`stored`: a view over the block's on-disk bytes) and not the
    EncodedBlock, so a decoded Block that has not served a value yet
    pins, and is charged, no more than those."""

    __slots__ = ("stored", "mode", "raw_len")

    def __init__(self, stored: np.ndarray, mode: int, raw_len: int) -> None:
        self.stored, self.mode, self.raw_len = stored, mode, raw_len

    def __call__(self) -> np.ndarray:
        if self.mode == _HEAP_ZLIB:
            out = zlib.decompress(self.stored)
        else:
            out = _Zstd.decompress(self.stored, self.raw_len)
        return np.frombuffer(out, dtype=np.uint8)


# ---- wire-payload compression (shared with cross-cluster duplication) ----
#
# The same zstd-1/zlib-1 machinery the block value heap uses, exposed for
# RPC payload blobs: duplication ships batched mutation envelopes across
# the WAN and must not pay per-envelope codec plumbing of its own. The
# compressibility probe gates exactly like the heap path — an
# incompressible envelope ships raw and never taxes the follower with a
# pointless decompress.

PAYLOAD_RAW = _HEAP_RAW
PAYLOAD_ZLIB = _HEAP_ZLIB
PAYLOAD_ZSTD = _HEAP_ZSTD


def deflate_payload(data: bytes) -> Tuple[int, bytes]:
    """(mode, stored bytes) for a wire payload blob."""
    return _maybe_deflate(data)


def inflate_payload(mode: int, stored, raw_len: int) -> bytes:
    """Inverse of deflate_payload; both compressors decode forever."""
    if mode == _HEAP_RAW:
        return bytes(stored)
    if mode == _HEAP_ZLIB:
        out = zlib.decompress(bytes(stored))
    elif mode == _HEAP_ZSTD:
        out = _Zstd.decompress(stored, raw_len)
    else:
        raise ValueError(f"unknown payload compression mode {mode}")
    if len(out) != raw_len:
        raise ValueError("payload length mismatch after inflate")
    return out
