"""Vectorized record predicates — the scan/multi_get hot path on device.

Parity with the reference's per-record scalar loop:
- validate_filter (src/server/pegasus_server_impl.cpp:2350): empty pattern
  matches everything; a region shorter than the pattern never matches;
  FT_MATCH_ANYWHERE/PREFIX/POSTFIX substring semantics.
- validate_key_value_for_scan (:2382): precedence is
  expired → hash_invalid → filtered → normal.
- check_if_ts_expired (src/base/pegasus_value_schema.h:113):
  expired iff 0 < expire_ts <= now.

Filter types are *static* arguments: each of the four types compiles to its
own XLA program (4 variants max per shape bucket), so FT_NO_FILTER costs
nothing and each matching type compiles only its own start selector.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pegasus_tpu.ops.device_crc import key_hash_device
from pegasus_tpu.ops.record_block import RecordBlock, next_bucket

# rrdb filter_type values (idl/rrdb.thrift:27-33)
FT_NO_FILTER = 0
FT_MATCH_ANYWHERE = 1
FT_MATCH_PREFIX = 2
FT_MATCH_POSTFIX = 3


def host_match_filter(data: bytes, filter_type: int,
                      pattern: bytes) -> bool:
    """Scalar twin of match_filter for host-side paths (overlay rows,
    tests). Empty pattern matches everything, like the device kernel."""
    if filter_type == FT_NO_FILTER or not pattern:
        return True
    if filter_type == FT_MATCH_ANYWHERE:
        return pattern in data
    if filter_type == FT_MATCH_PREFIX:
        return data.startswith(pattern)
    if filter_type == FT_MATCH_POSTFIX:
        return data.endswith(pattern)
    raise ValueError(f"unknown filter type {filter_type}")

_PATTERN_MIN_WIDTH = 32


class FilterSpec(NamedTuple):
    """A filter pattern padded for device dispatch. `filter_type` stays a
    Python int (static); pattern bytes + length are device operands.
    `raw` keeps the original pattern bytes host-side so cache keys never
    need a device->host fetch of `pattern`."""

    filter_type: int
    pattern: jax.Array      # uint8[P] padded
    pattern_len: jax.Array  # int32 scalar
    raw: bytes = b""

    @staticmethod
    def make(filter_type: int, pattern: bytes = b"") -> "FilterSpec":
        return _make_cached(int(filter_type), bytes(pattern),
                            jax.config.jax_default_device)

    @staticmethod
    def none() -> "FilterSpec":
        return _make_cached(FT_NO_FILTER, b"",
                            jax.config.jax_default_device)

    @property
    def key(self) -> tuple:
        """Hashable host-side identity (for mask cache keys)."""
        return (self.filter_type, self.raw)


@functools.lru_cache(maxsize=256)
def _make_cached(filter_type: int, pattern: bytes, _device) -> FilterSpec:
    """FilterSpec fields are immutable (jax arrays), so identical
    filters share one device copy — each cache hit saves two
    host->device transfers per scan batch.
    Keyed by the ambient default device so a process that dispatches
    under more than one backend (`jax.default_device` is thread-local)
    never leaks one backend's arrays into the other's dispatches."""
    width = next_bucket(len(pattern))
    buf = np.zeros(width, dtype=np.uint8)
    if pattern:
        buf[:len(pattern)] = np.frombuffer(pattern, dtype=np.uint8)
    return FilterSpec(filter_type, jnp.asarray(buf),
                      jnp.asarray(len(pattern), jnp.int32), pattern)


def match_filter(keys: jax.Array, region_start: jax.Array,
                 region_len: jax.Array, pattern: jax.Array,
                 pattern_len: jax.Array, filter_type: int) -> jax.Array:
    """bool[B]: does each record's byte region match the pattern?

    keys uint8[B, K]; region_start/region_len int32[B] (region within the
    padded key row); pattern uint8[P]; pattern_len int32; filter_type static.

    No gather (TPUs run one a scalar at a time): `window_ok[b, t]`, "the
    pattern matches from byte t of row b", is P static shifted compares
    of the zero-padded rows, AND-accumulated — O(B*K) memory per step
    instead of materializing B*K*P windows. The three types differ only
    in which starts they admit, picked by an iota compare: PREFIX the
    region's first byte, POSTFIX the start that ends at the region's
    end, ANYWHERE every start from the one to the other. A region that
    runs past the row (a header the writer never produces) reads zeros
    there.
    """
    b, k = keys.shape
    if filter_type == FT_NO_FILTER:
        return jnp.ones((b,), dtype=bool)

    p = pattern.shape[0]
    last_start = region_start + region_len - pattern_len
    lo = last_start if filter_type == FT_MATCH_POSTFIX else region_start
    hi = region_start if filter_type == FT_MATCH_PREFIX else last_start
    padded = jnp.pad(keys, ((0, 0), (0, p)))
    window_ok = jnp.ones((b, k), dtype=bool)
    for j in range(p):  # static unroll over the pattern buffer; XLA fuses
        cmp = (padded[:, j:j + k] == pattern[j]) | (j >= pattern_len)
        window_ok = window_ok & cmp
    t = jnp.arange(k, dtype=jnp.int32)[None, :]
    t_ok = (t >= lo[:, None]) & (t <= hi[:, None])
    fits = region_len >= pattern_len
    return (jnp.any(window_ok & t_ok, axis=1) & fits) | (pattern_len == 0)


def ttl_expired(expire_ts: jax.Array, now: jax.Array) -> jax.Array:
    """bool[B]: expired iff 0 < expire_ts <= now (value_schema.h:113)."""
    now = jnp.asarray(now, jnp.uint32)
    return (expire_ts > 0) & (expire_ts <= now)


class ScanMasks(NamedTuple):
    """Per-record outcome masks, mutually exclusive, reference precedence
    (pegasus_server_impl.cpp:2382): expired → hash_invalid → filtered."""

    keep: jax.Array
    expired: jax.Array
    hash_invalid: jax.Array
    filtered: jax.Array


@functools.partial(jax.jit, static_argnames=("hash_filter_type",
                                             "sort_filter_type",
                                             "validate_hash",
                                             "use_hash_lo"))
def _scan_block_predicate(keys, key_len, hashkey_len, expire_ts, valid,
                          now, hash_pattern, hash_pattern_len,
                          sort_pattern, sort_pattern_len,
                          pidx, partition_version,
                          hash_filter_type: int, sort_filter_type: int,
                          validate_hash: bool, hash_lo=None,
                          use_hash_lo: bool = False) -> ScanMasks:
    expired = ttl_expired(expire_ts, now) & valid

    if validate_hash:
        if use_hash_lo:
            lo = hash_lo  # precomputed at SST write time
        else:
            _, lo = key_hash_device(keys, key_len, hashkey_len)
        pv = jnp.asarray(partition_version, jnp.uint32)
        hash_ok = (lo & pv) == jnp.asarray(pidx, jnp.uint32)
    else:
        hash_ok = jnp.ones_like(valid)
    hash_invalid = ~hash_ok & valid & ~expired

    hk_ok = match_filter(keys, jnp.full_like(key_len, 2), hashkey_len,
                         hash_pattern, hash_pattern_len, hash_filter_type)
    sort_start = 2 + hashkey_len
    sort_len = key_len - sort_start
    sk_ok = match_filter(keys, sort_start, sort_len,
                         sort_pattern, sort_pattern_len, sort_filter_type)
    filtered = ~(hk_ok & sk_ok) & valid & ~expired & ~hash_invalid

    keep = valid & ~expired & ~hash_invalid & ~filtered
    return ScanMasks(keep, expired, hash_invalid, filtered)


@functools.partial(jax.jit, static_argnames=("hash_filter_type",
                                             "sort_filter_type",
                                             "validate_hash",
                                             "use_hash_lo", "pack"))
@jax.named_scope("pegasus_scan_mask")
def _static_block_predicate(keys, key_len, hashkey_len, valid,
                            hash_pattern, hash_pattern_len,
                            sort_pattern, sort_pattern_len,
                            pidx, partition_version,
                            hash_filter_type: int, sort_filter_type: int,
                            validate_hash: bool, hash_lo=None,
                            use_hash_lo: bool = False,
                            pack: bool = False) -> jax.Array:
    """The `now`-independent part of the scan predicate.

    For an IMMUTABLE columnar block, filter matching and partition-hash
    validation never change; only TTL expiry depends on the current
    second — and `expire_ts` is already host-resident, so the host can
    apply expiry with one vectorized AND at assembly time. Splitting the
    predicate this way means each (block, filter, partition_version)
    needs exactly ONE device evaluation for the block's whole lifetime:
    steady-state serving performs zero device round-trips.
    """
    if validate_hash:
        if use_hash_lo:
            lo = hash_lo  # precomputed at SST write time
        else:
            _, lo = key_hash_device(keys, key_len, hashkey_len)
        pv = jnp.asarray(partition_version, jnp.uint32)
        hash_ok = (lo & pv) == jnp.asarray(pidx, jnp.uint32)
    else:
        hash_ok = jnp.ones_like(valid)
    hk_ok = match_filter(keys, jnp.full_like(key_len, 2), hashkey_len,
                         hash_pattern, hash_pattern_len, hash_filter_type)
    sort_start = 2 + hashkey_len
    sort_len = key_len - sort_start
    sk_ok = match_filter(keys, sort_start, sort_len,
                         sort_pattern, sort_pattern_len, sort_filter_type)
    keep = valid & hash_ok & hk_ok & sk_ok
    # pack=True: bit-pack the mask ON DEVICE — 8x fewer mask bytes to
    # fetch per program
    return jnp.packbits(keep) if pack else keep


def static_block_predicate(block: RecordBlock,
                           hash_filter: Optional[FilterSpec] = None,
                           sort_filter: Optional[FilterSpec] = None,
                           validate_hash: bool = False,
                           pidx=0,
                           partition_version: int = -1,
                           pack: bool = False) -> jax.Array:
    """bool[B]: records passing every `now`-independent predicate.

    keep(now) == static_keep & ~expired(now), applied host-side from the
    block's expire_ts column. Same reject-all split-safety gate as
    scan_block_predicate (pegasus_server_impl.cpp:2392-2401)."""
    hash_filter = hash_filter or FilterSpec.none()
    sort_filter = sort_filter or FilterSpec.none()
    if validate_hash and (partition_version < 0
                          or pidx > partition_version):
        if pack:
            return jnp.zeros((block.capacity // 8,), dtype=jnp.uint8)
        return jnp.zeros((block.capacity,), dtype=bool)
    use_hash_lo = validate_hash and block.hash_lo is not None
    return _static_block_predicate(
        jnp.asarray(block.keys), jnp.asarray(block.key_len),
        jnp.asarray(block.hashkey_len), jnp.asarray(block.valid),
        hash_filter.pattern, hash_filter.pattern_len,
        sort_filter.pattern, sort_filter.pattern_len,
        jnp.asarray(pidx, jnp.uint32),
        jnp.asarray(partition_version & 0xFFFFFFFF, jnp.uint32),
        hash_filter.filter_type, sort_filter.filter_type, validate_hash,
        hash_lo=(jnp.asarray(block.hash_lo) if use_hash_lo
                 else jnp.zeros((1,), jnp.uint32)),
        use_hash_lo=use_hash_lo, pack=pack)


def _stack_operands(blocks, validate_hash: bool) -> tuple:
    """A stack's block operands for the stacked programs: one tuple a
    column, of every block's array. `hash_lo` goes for all blocks or
    for none: a stack mixing them computes the hash on device, so a
    (width, cap) has at most two operand structures."""
    use_hash_lo = validate_hash and all(b.hash_lo is not None
                                        for b in blocks)
    return (tuple(b.keys for b in blocks),
            tuple(b.key_len for b in blocks),
            tuple(b.hashkey_len for b in blocks),
            tuple(b.valid for b in blocks),
            tuple(b.hash_lo for b in blocks) if use_hash_lo else None)


def _concat_stack(keys, key_len, hashkey_len, valid, hash_lo, pidx):
    """Inside a trace: the stack's columns concatenated and its
    per-block `pidx` vector expanded to a per-record column, for the
    single-stack program bodies to take as they are. The expansion is
    a broadcast: `jnp.repeat` lowers to a gather, which the TPU runs a
    scalar at a time."""
    cap = keys[0].shape[0]
    cat = jnp.concatenate
    return (cat(keys), cat(key_len), cat(hashkey_len), cat(valid),
            cat(hash_lo) if hash_lo is not None
            else jnp.zeros((1,), jnp.uint32),
            jnp.broadcast_to(pidx[:, None], (len(keys), cap)).reshape(-1))


@functools.partial(jax.jit, static_argnames=("hash_filter_type",
                                             "sort_filter_type",
                                             "validate_hash", "pack"))
def _stacked_static_block_predicate(keys, key_len, hashkey_len, valid,
                                    hash_lo, pidx,
                                    hash_pattern, hash_pattern_len,
                                    sort_pattern, sort_pattern_len,
                                    partition_version,
                                    hash_filter_type: int,
                                    sort_filter_type: int,
                                    validate_hash: bool,
                                    pack: bool) -> jax.Array:
    keys, key_len, hashkey_len, valid, lo, pidx = _concat_stack(
        keys, key_len, hashkey_len, valid, hash_lo, pidx)
    return _static_block_predicate(
        keys, key_len, hashkey_len, valid,
        hash_pattern, hash_pattern_len, sort_pattern, sort_pattern_len,
        pidx, partition_version, hash_filter_type, sort_filter_type,
        validate_hash, hash_lo=lo, use_hash_lo=hash_lo is not None,
        pack=pack)


def stacked_static_block_predicate(blocks, pidx,
                                   hash_filter: Optional[FilterSpec] = None,
                                   sort_filter: Optional[FilterSpec] = None,
                                   validate_hash: bool = False,
                                   partition_version: int = -1,
                                   pack: bool = False) -> jax.Array:
    """static_block_predicate over a stack of same-shaped blocks in ONE
    jitted call: the blocks' columns are concatenated inside the
    program, not by eager dispatches before it. `pidx`: uint32[S], the
    owning partition of each block. bool[S*cap] (or packed), block i's
    mask at [i*cap, (i+1)*cap). A per-block pidx has no reject-all gate:
    a block whose pidx exceeds the partition version matches no record
    by the hash test itself."""
    hash_filter = hash_filter or FilterSpec.none()
    sort_filter = sort_filter or FilterSpec.none()
    return _stacked_static_block_predicate(
        *_stack_operands(blocks, validate_hash),
        np.asarray(pidx, np.uint32),
        hash_filter.pattern, hash_filter.pattern_len,
        sort_filter.pattern, sort_filter.pattern_len,
        np.uint32(partition_version & 0xFFFFFFFF),
        hash_filter.filter_type, sort_filter.filter_type, validate_hash,
        pack)


def host_alive_mask(expire_ts: np.ndarray, now: int) -> np.ndarray:
    """bool[B] numpy twin of ~ttl_expired: rows NOT expired at `now`."""
    ets = np.asarray(expire_ts)
    return ~((ets > 0) & (ets <= np.uint32(now)))


# direct compute on compressed blocks: probes answered from the encoded
# representation, with zero key-matrix rebuild and zero device dispatch
from pegasus_tpu.utils.metrics import METRICS as _METRICS  # noqa: E402

_ENCODED_PROBE = _METRICS.entity("storage", "node").relaxed_counter(
    "encoded_probe_count")


def _region_filter_host(heap: np.ndarray, offs: np.ndarray,
                        filter_type: int, pattern: bytes) -> np.ndarray:
    """bool[n] pattern match over ragged byte regions
    heap[offs[i]:offs[i+1]] — native kernel when available, scalar
    host_match_filter loop otherwise. Device-kernel semantics: empty
    pattern matches everything; region shorter than pattern never
    matches."""
    from pegasus_tpu import native

    n = len(offs) - 1
    if filter_type == FT_NO_FILTER or not pattern:
        return np.ones(n, dtype=bool)
    fn = native.region_filter_fn()
    if fn is not None:
        out = np.empty(n, dtype=np.uint8)
        fn(np.ascontiguousarray(heap),
           np.ascontiguousarray(offs, dtype=np.int64), n, pattern,
           filter_type, out)
        return out.astype(bool)
    hv = np.asarray(heap)
    return np.fromiter(
        (host_match_filter(hv[offs[i]:offs[i + 1]].tobytes(),
                           filter_type, pattern) for i in range(n)),
        dtype=bool, count=n)


def encoded_static_keep(enc, validate_hash: bool, pidx: int,
                        partition_version: int,
                        filter_key) -> Optional[np.ndarray]:
    """bool[n] static keep mask of an EncodedBlock
    (storage/block_codec.py), bit-identical to
    `static_block_predicate` over the decoded block — evaluated
    entirely on the HOST against the encoded representation:

    - partition-hash validation reads the raw `hash_lo` column;
    - the hashkey filter evaluates once per DICTIONARY entry (D unique
      hashkeys, not n rows) and gathers per-row through the index
      column;
    - the sortkey filter runs over the packed sortkey heap (no padded
      key matrix, no zero-byte scanning).

    Returns None when the block cannot take this path (malformed rows
    present — the device kernel's hashkey_len semantics differ there).
    TTL stays the caller's per-second host mask, exactly as on the
    device path (static masks are `now`-independent).
    """
    if enc.has_malformed:
        return None
    n = enc.n
    hft, hfp, sft, sfp = filter_key
    if validate_hash and (partition_version < 0
                          or pidx > partition_version):
        # split-safety reject-all gate, mirroring static_block_predicate
        _ENCODED_PROBE.increment()
        return np.zeros(n, dtype=bool)
    keep = np.asarray(enc.key_len) >= 2
    if validate_hash:
        pv = np.uint32(partition_version & 0xFFFFFFFF)
        keep = keep & ((np.asarray(enc.hash_lo) & pv)
                       == np.uint32(pidx))
    if hft != FT_NO_FILTER and hfp:
        do = np.asarray(enc.dict_offs, dtype=np.int64)
        per_dict = _region_filter_host(enc.dict_heap, do, hft, hfp)
        keep = keep & per_dict[enc.hk_idx]
    if sft != FT_NO_FILTER and sfp:
        keep = keep & _region_filter_host(enc.sk_heap, enc.sk_offs,
                                          sft, sfp)
    _ENCODED_PROBE.increment()
    return keep


def pad_probe_keys(probe_keys, width: int):
    """(uint8[P, width] padded rows, int64[P] lengths) for a batch of
    exact-match probe keys. Keys longer than `width` cannot exist in a
    block of that key width; their rows are zeroed and flagged by
    length so point_probe_rows reports them absent."""
    p = len(probe_keys)
    lens = np.fromiter((len(k) for k in probe_keys), dtype=np.int64,
                       count=p)
    buf = bytearray(p * width)
    for i, k in enumerate(probe_keys):
        if len(k) <= width:
            off = i * width
            buf[off:off + len(k)] = k
    return (np.frombuffer(bytes(buf), dtype=np.uint8).reshape(p, width),
            lens)


def point_probe_rows(keys_matrix: np.ndarray, key_len: np.ndarray,
                     probe_keys, block_void=None) -> np.ndarray:
    """Vectorized exact-key probe into ONE sorted columnar block.

    keys_matrix: uint8[N, W] zero-padded sorted rows (SST block order);
    key_len: int[N]; probe_keys: list[bytes]; block_void: optional
    precomputed memcmp-ordered void view of keys_matrix (cached per
    block by page.probe_nat). Returns int64[P] row indices (-1 =
    absent). One np.searchsorted over the void view locates every probe
    at once — the batched replacement for per-key Python bisects on the
    point-get hot path; no key materialization, so cold blocks probe as
    fast as hot ones.

    Zero padding makes two keys differing only in TRAILING zero bytes
    pad to identical rows; such twins are adjacent and sorted by true
    length, so the rare collision resolves with a short forward scan.
    """
    n, w = keys_matrix.shape
    p = len(probe_keys)
    if p == 0 or n == 0:
        return np.full(p, -1, dtype=np.int64)
    vt = np.dtype((np.void, w))
    if block_void is None:
        block_void = np.ascontiguousarray(keys_matrix).view(vt).ravel()
    if p <= 4:
        # scalar fast path: the common flush shape scatters 1-2 keys
        # per block, where the batch verify's array setup costs more
        # than the probes
        rows = np.full(p, -1, dtype=np.int64)
        for i, k in enumerate(probe_keys):
            lk = len(k)
            if lk > w:
                continue
            padded = k.ljust(w, b"\x00")
            pos = int(np.searchsorted(
                block_void, np.frombuffer(padded, dtype=vt))[0])
            while pos < n and block_void[pos].tobytes() == padded:
                if int(key_len[pos]) == lk:
                    rows[i] = pos
                    break
                pos += 1  # trailing-zero twin: true match is ahead
        return rows
    pm, lens = pad_probe_keys(probe_keys, w)
    probe_v = pm.view(vt).ravel()
    pos = np.searchsorted(block_void, probe_v)
    rows = np.full(p, -1, dtype=np.int64)
    in_range = (pos < n) & (lens <= w)
    cand = np.flatnonzero(in_range)
    if cand.size:
        cpos = pos[cand]
        same = (keys_matrix[cpos] == pm[cand]).all(axis=1)
        exact = same & (np.asarray(key_len)[cpos] == lens[cand])
        rows[cand[exact]] = cpos[exact]
        # padded-equal but length-mismatched: trailing-zero twins ahead
        for i in cand[same & ~exact]:
            j = int(pos[i]) + 1
            want = int(lens[i])
            while j < n and block_void[j] == probe_v[i]:
                if int(key_len[j]) == want:
                    rows[i] = j
                    break
                j += 1
    return rows


def phash_verify_rows(keys_matrix: np.ndarray, key_len: np.ndarray,
                      rows: np.ndarray, probe_keys) -> np.ndarray:
    """bool[P]: does block row rows[i] hold EXACTLY probe_keys[i]?

    The perfect-hash probe's fingerprint-collision rejector: the index
    (storage/phash.py) maps a batched flush straight to (block, slot)
    rows, and this one vectorized compare per touched block confirms
    each located row before it serves — a collision (~0.08% of absent
    keys) must read as "absent", never as another row's value. Scalar
    fast path below the same threshold as point_probe_rows (the 1-4
    key flush shape)."""
    p = len(probe_keys)
    if p == 0:
        return np.zeros(0, dtype=bool)
    n, w = keys_matrix.shape
    kl = np.asarray(key_len)
    if p <= 4:
        out = np.zeros(p, dtype=bool)
        for i, k in enumerate(probe_keys):
            r = int(rows[i])
            lk = len(k)
            out[i] = (lk <= w and int(kl[r]) == lk
                      and keys_matrix[r, :lk].tobytes() == k)
        return out
    pm, lens = pad_probe_keys(probe_keys, w)
    fits = lens <= w
    rows = np.asarray(rows, dtype=np.int64)
    same = (keys_matrix[rows] == pm).all(axis=1)
    return same & fits & (kl[rows] == lens)


def bloom_key_hashes(keys) -> np.ndarray:
    """uint64[B] full-key crc64 for a batch of probe keys — the hash
    input EVERY sidecar structure shares (bloom filters and the
    perfect-hash index probe the same column), evaluated once per read
    flush and consumed by every table/run the flush's candidates
    touch.

    Placement: compute-trivial per byte (the "probe" workload class in
    ops/placement.py — a table lookup per byte), so this always runs on
    the host: small batches take the scalar C crc64 (one call per key
    beats the batch call's array setup), larger flushes take ONE
    `crc64_rows` pass over the padded key matrix.
    """
    n = len(keys)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    from pegasus_tpu.base.crc import crc64, crc64_rows

    if n < 16:
        return np.fromiter((crc64(k) for k in keys), dtype=np.uint64,
                           count=n)
    width = max(1, max(len(k) for k in keys))
    mat, lens = pad_probe_keys(keys, width)
    return crc64_rows(mat, lens)


def bloom_probe_rows(bloom, hashes: np.ndarray) -> np.ndarray:
    """bool[B]: may each hashed probe key be present in `bloom`
    (storage.bloom.BloomFilter)? False is definitive — the caller skips
    that run/table without decoding a block. One vectorized pass
    answers the whole flush; a filterless table answers all-True.

    This is the batch-evaluation form the coalesced read flush feeds
    (LSM-OPD's direct-on-format idea: membership for N keys is k
    vectorized gathers over the bit array, not N scalar walks).
    """
    if bloom is None:
        return np.ones(len(hashes), dtype=bool)
    return bloom.may_contain_hashes(hashes)


def host_key_hash_lo(hash_keys, sort_keys=None) -> np.ndarray:
    """uint32[B] low lane of pegasus_key_hash for a key batch, evaluated
    with ONE vectorized crc64 pass (base.crc.crc64_batch) instead of a
    per-key scalar crc loop — the batched probe-eval form of
    key_hash_parts used by the point-read coordinator's split-staleness
    gate. Empty hash keys hash by their sort key (pegasus_key_schema
    .h:150); placement note: this is compute-trivial per byte (the
    "probe" workload class in ops/placement.py), so it always runs on
    the host."""
    from pegasus_tpu.base.crc import crc64_batch

    regions = list(hash_keys)
    if sort_keys is not None:
        regions = [hk if hk else sk
                   for hk, sk in zip(hash_keys, sort_keys)]
    b = len(regions)
    if b == 0:
        return np.zeros(0, dtype=np.uint32)
    width = max(1, max(len(r) for r in regions))
    mat, lens = pad_probe_keys(regions, width)
    return (crc64_batch(mat, lens, start=0)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


@functools.partial(jax.jit, static_argnames=("hash_filter_type",
                                             "sort_filter_type",
                                             "validate_hash",
                                             "use_hash_lo"))
def _multi_static_block_predicate(keys, key_len, hashkey_len, valid,
                                  hash_patterns, hash_plens,
                                  sort_patterns, sort_plens,
                                  pidx, partition_version,
                                  hash_filter_type: int,
                                  sort_filter_type: int,
                                  validate_hash: bool, hash_lo=None,
                                  use_hash_lo: bool = False) -> jax.Array:
    """K filter flavors × one stacked block in ONE program, bit-packed.

    Batching the FLAVOR axis (SURVEY §2.6 dispatch model) multiplies
    compute-per-byte K-fold over the already-resident key matrix for
    one dispatch's fixed cost, and `packbits` shrinks the returned
    masks 8x. hash validation is flavor-independent, so it is
    evaluated once and broadcast.

    hash_patterns/sort_patterns: uint8[K, P]; *_plens: int32[K].
    Returns uint8[K, B//8] packed masks (B is a multiple of 8 — block
    capacities are power-of-two bucketed).
    """
    if validate_hash:
        if use_hash_lo:
            lo = hash_lo
        else:
            _, lo = key_hash_device(keys, key_len, hashkey_len)
        pv = jnp.asarray(partition_version, jnp.uint32)
        hash_ok = (lo & pv) == jnp.asarray(pidx, jnp.uint32)
    else:
        hash_ok = jnp.ones_like(valid)
    base = valid & hash_ok
    sort_start = 2 + hashkey_len
    sort_len = key_len - sort_start
    hk_start = jnp.full_like(key_len, 2)

    def one_flavor(hp, hl, sp, sl):
        hk_ok = match_filter(keys, hk_start, hashkey_len, hp, hl,
                             hash_filter_type)
        sk_ok = match_filter(keys, sort_start, sort_len, sp, sl,
                             sort_filter_type)
        return base & hk_ok & sk_ok

    ok = jax.vmap(one_flavor)(hash_patterns, hash_plens,
                              sort_patterns, sort_plens)     # [K, B]
    return jnp.packbits(ok, axis=1)


def multi_static_block_predicate_submit(block: RecordBlock, filters,
                                        validate_hash: bool, pidx,
                                        partition_version: int):
    """Dispatch K same-type filter flavors over one (stacked) block
    WITHOUT waiting; returns the device uint8[K, B//8] packed-mask
    array (callers overlap many submissions, then unpack with
    `unpack_masks`).

    `filters`: [(hash_FilterSpec, sort_FilterSpec)] — every entry must
    share (hash_filter_type, sort_filter_type) and pattern pad widths
    (callers group by exactly that). The split-safety reject-all gate
    matches static_block_predicate.
    """
    if validate_hash and (partition_version < 0
                          or pidx > partition_version):
        return jnp.zeros((len(filters), block.capacity // 8),
                         dtype=jnp.uint8)
    hf0, sf0 = filters[0]
    use_hash_lo = validate_hash and block.hash_lo is not None
    return _multi_static_block_predicate(
        jnp.asarray(block.keys), jnp.asarray(block.key_len),
        jnp.asarray(block.hashkey_len), jnp.asarray(block.valid),
        *_flavor_operands(filters),
        jnp.asarray(pidx, jnp.uint32),
        jnp.asarray(partition_version & 0xFFFFFFFF, jnp.uint32),
        hf0.filter_type, sf0.filter_type, validate_hash,
        hash_lo=(jnp.asarray(block.hash_lo) if use_hash_lo
                 else jnp.zeros((1,), jnp.uint32)),
        use_hash_lo=use_hash_lo)


def _flavor_operands(filters) -> tuple:
    """The flavor axis: (hash patterns [K, P], their lengths [K], sort
    patterns, their lengths)."""
    return (jnp.stack([hf.pattern for hf, _sf in filters]),
            jnp.stack([hf.pattern_len for hf, _sf in filters]),
            jnp.stack([sf.pattern for _hf, sf in filters]),
            jnp.stack([sf.pattern_len for _hf, sf in filters]))


@functools.partial(jax.jit, static_argnames=("hash_filter_type",
                                             "sort_filter_type",
                                             "validate_hash"))
def _stacked_multi_static_block_predicate(keys, key_len, hashkey_len,
                                          valid, hash_lo, pidx,
                                          hash_patterns, hash_plens,
                                          sort_patterns, sort_plens,
                                          partition_version,
                                          hash_filter_type: int,
                                          sort_filter_type: int,
                                          validate_hash: bool) -> jax.Array:
    keys, key_len, hashkey_len, valid, lo, pidx = _concat_stack(
        keys, key_len, hashkey_len, valid, hash_lo, pidx)
    return _multi_static_block_predicate(
        keys, key_len, hashkey_len, valid,
        hash_patterns, hash_plens, sort_patterns, sort_plens,
        pidx, partition_version, hash_filter_type, sort_filter_type,
        validate_hash, hash_lo=lo, use_hash_lo=hash_lo is not None)


def stacked_multi_static_block_predicate_submit(blocks, filters,
                                                validate_hash: bool, pidx,
                                                partition_version: int):
    """multi_static_block_predicate_submit over a stack of same-shaped
    blocks in ONE jitted call, the stack concatenated inside the
    program (stacked_static_block_predicate's rules: `pidx` uint32[S],
    no reject-all gate). Returns the device uint8[K, S*cap//8]."""
    hf0, sf0 = filters[0]
    return _stacked_multi_static_block_predicate(
        *_stack_operands(blocks, validate_hash),
        np.asarray(pidx, np.uint32), *_flavor_operands(filters),
        np.uint32(partition_version & 0xFFFFFFFF),
        hf0.filter_type, sf0.filter_type, validate_hash)


def unpack_masks(packed, count: int) -> np.ndarray:
    """uint8[..., B//8] packed device/host masks -> bool[..., count]."""
    arr = np.asarray(packed)
    return np.unpackbits(arr, axis=-1, count=count).astype(bool)


def multi_static_block_predicate(block: RecordBlock, filters,
                                 validate_hash: bool, pidx,
                                 partition_version: int) -> np.ndarray:
    """Synchronous form of multi_static_block_predicate_submit:
    bool[K, B] host masks."""
    packed = multi_static_block_predicate_submit(
        block, filters, validate_hash, pidx, partition_version)
    return unpack_masks(packed, block.capacity)


def scan_block_predicate(block: RecordBlock, now,
                         hash_filter: Optional[FilterSpec] = None,
                         sort_filter: Optional[FilterSpec] = None,
                         validate_hash: bool = False,
                         pidx=0,
                         partition_version: int = -1) -> ScanMasks:
    """Evaluate the full scan validation for a record block on device.

    Mirrors validate_key_value_for_scan for a whole block at once. When
    `validate_hash` and partition_version < 0 or pidx > partition_version,
    every non-expired record is hash-invalid (the reference checks expiry
    first, then rejects with kHashInvalid; pegasus_server_impl.cpp:2392-2401).
    """
    hash_filter = hash_filter or FilterSpec.none()
    sort_filter = sort_filter or FilterSpec.none()
    # `pidx` may be a PER-RECORD array: stacked cross-partition batches
    # (SURVEY §2.6 — partitions as the batch dimension of one dispatch)
    # pass each record its owning partition index; scalar callers keep
    # the reject-all split-safety gate below
    pidx_is_array = not isinstance(pidx, int)
    if (validate_hash and not pidx_is_array
            and (partition_version < 0 or pidx > partition_version)):
        valid = jnp.asarray(block.valid)
        expired = ttl_expired(jnp.asarray(block.expire_ts),
                              jnp.asarray(now, jnp.uint32)) & valid
        zeros = jnp.zeros((block.capacity,), dtype=bool)
        return ScanMasks(zeros, expired, valid & ~expired, zeros)
    use_hash_lo = validate_hash and block.hash_lo is not None
    return _scan_block_predicate(
        jnp.asarray(block.keys), jnp.asarray(block.key_len),
        jnp.asarray(block.hashkey_len), jnp.asarray(block.expire_ts),
        jnp.asarray(block.valid), jnp.asarray(now, jnp.uint32),
        hash_filter.pattern, hash_filter.pattern_len,
        sort_filter.pattern, sort_filter.pattern_len,
        jnp.asarray(pidx, jnp.uint32)
        if not pidx_is_array else jnp.asarray(pidx),
        jnp.asarray(partition_version & 0xFFFFFFFF, jnp.uint32),
        hash_filter.filter_type, sort_filter.filter_type, validate_hash,
        hash_lo=(jnp.asarray(block.hash_lo) if use_hash_lo
                 else jnp.zeros((1,), jnp.uint32)),
        use_hash_lo=use_hash_lo)
