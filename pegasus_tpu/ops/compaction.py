"""Device compaction filter: TTL + default-TTL rewrite + stale-split drop.

Parity: KeyWithTTLCompactionFilter::Filter
(src/server/key_ttl_compaction_filter.h:55-121):
1. default_ttl != 0 and record has no TTL -> rewrite expire_ts to
   now + default_ttl (value_changed).
2. user-specified compaction operations may delete / update TTL (the rule
   kernels live in ops/compaction_rules.py).
3. drop iff expired(now) after the rewrite, OR the key is stale post-split
   data: validate_hash and partition_version >= 0 and
   pidx <= partition_version and crc64-hash doesn't map here
   (check_if_stale_split_data, :114-121 — note: partition_version < 0 means
   KEEP here, the opposite of the scan path's reject).

Evaluated for a whole columnar batch in one XLA program, vs the reference's
per-record scalar Filter() callback during RocksDB compaction. A pass with
no user rules over compressed blocks reads no key bytes: the engine hands
a pipeline window's expire_ts and hash_lo columns to ttl_filter_window on
the device where placement_verdict("ttl") says "device", and evaluates
each block with encoded_drop_mask on the host where it says "host-XLA".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pegasus_tpu.ops.device_crc import key_hash_device
from pegasus_tpu.ops.predicates import ttl_expired
from pegasus_tpu.ops.record_block import next_bucket
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.metrics import METRICS

# every compaction filter program handed to a device (always on), and
# the part of them dispatched under a traced root or inside a profiler
# session: what a traced slice's device busy time is made of. The read
# path's static mask programs (scan_coordinator.stacked_block_submit)
# count beside them under `mask_*`: a scan batch whose plan a publish
# tore dispatches them in the same slice.
_FILTER_ENT = METRICS.entity("engine", "filter_programs")
_PROGRAM_COUNTERS = {
    kind: tuple(_FILTER_ENT.counter(f"{kind}_{what}") for what in (
        "programs", "rows", "bytes", "programs_traced", "bytes_traced"))
    for kind in ("filter", "mask")}
# a serving wave of mask programs in its three steps, host time in
# whole microseconds, always on (scan_coordinator._WaveClock)
_MASK_STEP_US = tuple(_FILTER_ENT.counter(f"mask_{step}_us")
                      for step in ("stack", "launch", "fetch"))
# of `mask_programs`, those whose stack of blocks was concatenated
# inside the program (always on)
_MASK_STACKED = _FILTER_ENT.counter("mask_stacked_programs")


def note_mask_steps(stack_ns: int, launch_ns: int, fetch_ns: int) -> None:
    """One wave's three steps, host nanoseconds each."""
    for counter, ns in zip(_MASK_STEP_US, (stack_ns, launch_ns, fetch_ns)):
        counter.increment(ns // 1000)


def note_filter_program(rows: int, nbytes: int,
                        kind: str = "filter", stacked: bool = False) -> None:
    """One dispatched program: its padded rows, and the bytes of the
    columns it was handed plus the masks / expire_ts it gives back.
    `stacked`: a mask program that concatenated its blocks itself."""
    programs, c_rows, c_bytes, traced, bytes_traced = _PROGRAM_COUNTERS[kind]
    programs.increment()
    if stacked:
        _MASK_STACKED.increment()
    c_rows.increment(rows)
    c_bytes.increment(nbytes)
    if tracing.frame_span() is not None or tracing.profiling():
        traced.increment()
        bytes_traced.increment(nbytes)


@functools.partial(jax.jit, static_argnames=("validate_hash",))
def compaction_filter_block(keys, key_len, hashkey_len, expire_ts, valid,
                            now, default_ttl, pidx, partition_version,
                            validate_hash: bool):
    """Returns (drop: bool[B], new_expire_ts: uint32[B]).

    `partition_version` must be >= 0 when validate_hash is set (callers gate
    the pv<0 / pidx>pv cases to keep, mirroring check_if_stale_split_data).
    """
    with jax.named_scope("pegasus_compact_ttl_filter"):
        now = jnp.asarray(now, jnp.uint32)
        default_ttl = jnp.asarray(default_ttl, jnp.uint32)

        new_ets = jnp.where((default_ttl != 0) & (expire_ts == 0),
                            now + default_ttl, expire_ts)
        expired = ttl_expired(new_ets, now)

        if validate_hash:
            _, lo = key_hash_device(keys, key_len, hashkey_len)
            pv = jnp.asarray(partition_version, jnp.uint32)
            stale = (lo & pv) != jnp.asarray(pidx, jnp.uint32)
        else:
            stale = jnp.zeros_like(valid)

        drop = (expired | stale) & valid
        return drop, new_ets


# ---- bulk block-level compaction (the GB/s path) -----------------------
#
# The merge-based compactor streams per-record Python; the bulk path
# below evaluates WHOLE device-resident columnar blocks — stacked across
# blocks (and partitions) into a handful of programs — and rewrites
# surviving rows with vectorized numpy gathers. One fused program per
# ruleset covers the reference's full Filter() ordering
# (key_ttl_compaction_filter.h:55-121): default-TTL rewrite -> user
# rules -> expiry + stale-split drop.

from collections import OrderedDict

_EVAL_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_EVAL_CACHE_CAP = 32


def _ops_key(operations) -> tuple:
    """Content identity of a parsed ruleset: recompiling the same JSON
    (config-sync re-delivers app-envs periodically) must reuse the same
    jitted program instead of leaking one compiled executable per
    delivery."""
    if not operations:
        return ()
    out = []
    for op in operations:
        rules = []
        for r in op.rules:
            if r.kind == "ttl_range":
                rules.append((r.kind, r.start_ttl, r.stop_ttl))
            else:
                rules.append((r.kind, r.filter.filter_type, r.filter.raw))
        out.append((op.op, getattr(op, "utot", None),
                    getattr(op, "value", None), tuple(rules)))
    return tuple(out)


def make_compaction_eval(operations=None):
    """Jitted (drop, new_ets) program for one (optional) parsed ruleset.

    `operations` is the tuple from compile_rules(...).operations (static
    ruleset structure -> its own XLA program, cached by CONTENT and
    bounded)."""
    key = _ops_key(operations)
    cached = _EVAL_CACHE.get(key)
    if cached is not None:
        _EVAL_CACHE.move_to_end(key)
        return cached

    @functools.partial(jax.jit, static_argnames=("validate_hash",
                                                 "use_hash_lo",
                                                 "want_ets", "pack"))
    def eval_block(keys, key_len, hashkey_len, expire_ts, valid, hash_lo,
                   now, default_ttl, pidx, partition_version,
                   validate_hash: bool, use_hash_lo: bool,
                   want_ets: bool = True, pack: bool = False):
        from pegasus_tpu.ops.compaction_rules import apply_rules_ops

        with jax.named_scope("pegasus_compact_bulk_filter"):
            now = jnp.asarray(now, jnp.uint32)
            default_ttl = jnp.asarray(default_ttl, jnp.uint32)
            ets1 = jnp.where((default_ttl != 0) & (expire_ts == 0),
                             now + default_ttl, expire_ts)
            if operations:
                rule_drop, ets2 = apply_rules_ops(
                    operations, keys, key_len, hashkey_len, ets1, valid, now)
            else:
                rule_drop = jnp.zeros_like(valid)
                ets2 = ets1
            expired = ttl_expired(ets2, now)
            if validate_hash:
                if use_hash_lo:
                    lo = hash_lo  # precomputed at SST write time
                else:
                    _, lo = key_hash_device(keys, key_len, hashkey_len)
                pv = jnp.asarray(partition_version, jnp.uint32)
                stale = (lo & pv) != jnp.asarray(pidx, jnp.uint32)
            else:
                stale = jnp.zeros_like(valid)
            drop = ((expired | stale) & valid) | rule_drop
            # pack: bit-pack the drop mask on device (8x fewer bytes to
            # fetch); want_ets=False skips
            # returning the rewritten-TTL column entirely when no rule or
            # default-TTL can change it (the caller never reads it)
            if pack:
                drop = jnp.packbits(drop)
            return (drop, ets2) if want_ets else (drop,)

    _EVAL_CACHE[key] = eval_block
    while len(_EVAL_CACHE) > _EVAL_CACHE_CAP:
        _EVAL_CACHE.popitem(last=False)
    return eval_block


def encoded_drop_mask(enc, now: int, default_ttl: int, pidx: int,
                      partition_version: int, validate_hash: bool,
                      want_ets: bool = True):
    """(drop bool[n], new_ets|None) for one ENCODED block — the
    direct-compute twin of the jitted eval_block for rulesets that
    touch no key bytes (no user rules): the TTL + default-TTL rewrite
    reads the raw `expire_ts` column and the stale-split check reads
    the raw `hash_lo` column, so a compressed block's drop mask costs
    zero key decode, zero value-heap inflate, and zero device
    dispatch. Semantics match eval_block exactly (valid is all-True
    for SST-origin blocks, as compaction_eval_submit stamps it)."""
    ets = np.asarray(enc.expire_ts)
    if default_ttl:
        new_ets = np.where(ets == 0,
                           np.uint32((now + default_ttl) & 0xFFFFFFFF),
                           ets)
    else:
        new_ets = ets
    now32 = np.uint32(now & 0xFFFFFFFF)
    drop = (new_ets > 0) & (new_ets <= now32)
    if validate_hash:
        pv = np.uint32(max(partition_version, 0) & 0xFFFFFFFF)
        drop = drop | ((np.asarray(enc.hash_lo) & pv)
                       != np.uint32(pidx & 0xFFFFFFFF))
    return drop, (new_ets if want_ets else None)


@functools.partial(jax.jit, static_argnames=("validate_hash", "want_ets"))
def ttl_filter_window(expire_ts, hash_lo, rows, now, default_ttl, pidx,
                      partition_version, validate_hash: bool,
                      want_ets: bool):
    """encoded_drop_mask over a pipeline window's blocks at once, on
    the device: `expire_ts` / `hash_lo` are the blocks' columns
    stacked and padded to a power-of-two bucket (`hash_lo` a
    placeholder of one element unless `validate_hash`), `rows` the
    real rows at their head. No key byte is read. Returns the drop
    mask bit-packed, and the rewritten expire_ts where `want_ets`."""
    with jax.named_scope("pegasus_compact_ttl_filter"):
        now = jnp.asarray(now, jnp.uint32)
        default_ttl = jnp.asarray(default_ttl, jnp.uint32)
        new_ets = jnp.where((default_ttl != 0) & (expire_ts == 0),
                            now + default_ttl, expire_ts)
        drop = ttl_expired(new_ets, now)
        if validate_hash:
            pv = jnp.asarray(partition_version, jnp.uint32)
            drop = drop | ((hash_lo & pv) != jnp.asarray(pidx, jnp.uint32))
        drop = drop & (jnp.arange(expire_ts.shape[0]) < rows)
        packed = jnp.packbits(drop)
        return (packed, new_ets) if want_ets else (packed,)


def ttl_filter_submit(blocks, now: int, default_ttl: int, pidx: int,
                      partition_version: int, validate_hash: bool,
                      want_ets: bool):
    """Dispatch ttl_filter_window over `blocks` ([(tag, block)], any
    block with `count`, `expire_ts` and `hash_lo`) without waiting.
    Returns the token ttl_filter_drain takes."""
    spans, pos = [], 0
    for tag, blk in blocks:
        spans.append((tag, pos, blk.count))
        pos += blk.count
    cap = _row_bucket(pos)
    ets = np.zeros(cap, dtype=np.uint32)
    hash_lo = np.zeros(cap if validate_hash else 1, dtype=np.uint32)
    for (_tag, lo, n), (_t, blk) in zip(spans, blocks):
        ets[lo:lo + n] = blk.expire_ts
        if validate_hash:
            hash_lo[lo:lo + n] = blk.hash_lo
    out = ttl_filter_window(
        ets, hash_lo, np.int32(pos), np.uint32(now & 0xFFFFFFFF),
        np.uint32(default_ttl & 0xFFFFFFFF), np.uint32(pidx & 0xFFFFFFFF),
        np.uint32(max(partition_version, 0) & 0xFFFFFFFF),
        validate_hash=validate_hash, want_ets=want_ets)
    # the two columns and five scalars up; the packed mask and the
    # rewritten expire_ts back
    note_filter_program(cap, 4 * (cap + hash_lo.size + 5) + cap // 8
                        + (4 * cap if want_ets else 0))
    return spans, cap, out


def ttl_filter_drain(token):
    """Wait for one ttl_filter_submit and yield (tag, drop, new_ets or
    None) a block, as encoded_drop_mask gives them."""
    spans, cap, out = token
    fetched = jax.device_get(out)
    drop_all = np.unpackbits(fetched[0], count=cap).astype(bool)
    ets_all = fetched[1] if len(fetched) > 1 else None
    for tag, lo, n in spans:
        yield (tag, drop_all[lo:lo + n],
               None if ets_all is None else ets_all[lo:lo + n])


def mesh_compact_step(keys, key_len, hashkey_len, expire_ts, present,
                      hash_lo, pidx, allowed, now, default_ttl,
                      partition_version, *, operations=None,
                      validate_hash: bool = False,
                      want_ets: bool = True):
    """Whole-table [P, B] twin of eval_block over the RESIDENT image
    (parallel/mesh_resident.py): one SPMD dispatch computes every
    compacting partition's drop masks instead of per-window host/XLA
    programs — the LUDA shape.

    Filter ordering is byte-for-byte eval_block's (default-TTL rewrite
    -> user rules -> expiry + stale-split), flattened [P, B] -> [P*B]
    with a per-row pidx vector exactly like mesh_resident._mesh_step so
    the paths cannot drift. `present` plays eval_block's `valid`: the
    host submit path stamps valid=True for every real SST row
    (tombstones included — the write stage's flags check drops them
    either way), and the stack's present mask is exactly that. The
    stale-split term is additionally gated per-slot by `allowed`
    (pidx <= partition_version — check_if_stale_split_data's KEEP for
    mid-split children above the version), so one dispatch serves a
    table whose partitions straddle a split. Returns
    (packed_drop uint8[P, B/8], ets2 uint32[P, B] if want_ets)."""
    from pegasus_tpu.ops.compaction_rules import apply_rules_ops

    p, b = expire_ts.shape
    k = keys.shape[-1]
    now = jnp.asarray(now, jnp.uint32)
    default_ttl = jnp.asarray(default_ttl, jnp.uint32)
    ets = expire_ts.reshape(p * b)
    present_f = present.reshape(p * b)
    ets1 = jnp.where((default_ttl != 0) & (ets == 0),
                     now + default_ttl, ets)
    if operations:
        rule_drop, ets2 = apply_rules_ops(
            operations, keys.reshape(p * b, k), key_len.reshape(p * b),
            hashkey_len.reshape(p * b), ets1, present_f, now)
    else:
        rule_drop = jnp.zeros_like(present_f)
        ets2 = ets1
    expired = ttl_expired(ets2, now)
    if validate_hash:
        pv = jnp.asarray(partition_version, jnp.uint32)
        stale = ((hash_lo.reshape(p * b) & pv) != jnp.repeat(pidx, b)) \
            & jnp.repeat(allowed, b)
    else:
        stale = jnp.zeros_like(present_f)
    drop = ((expired | stale) & present_f) | rule_drop
    packed = jnp.packbits(drop.reshape(p, b), axis=1)
    if want_ets:
        return packed, ets2.reshape(p, b)
    return (packed,)


COMPACT_CHUNK_ROWS = 1 << 18  # 256k records per stacked program


def _row_bucket(n: int) -> int:
    """Power-of-two row capacity for a stacked program (bounds distinct
    XLA compilations). Unlike record_block.next_bucket this is a ROW
    count, not a key width — no 64k ceiling (chunking already bounds it
    at COMPACT_CHUNK_ROWS plus one block)."""
    w = 4096
    while w < n:
        w <<= 1
    return w


# compaction must move every key byte host->device and the masks back,
# so eval placement is decided by the shared link probe
from pegasus_tpu.ops.placement import choose_eval_device  # noqa: F401 (re-export)


def rules_workload(operations) -> str:
    """Placement class for a parsed ruleset (ops/placement.py).

    The accelerator's upload cost (~32 key bytes/record) buys ALL
    rules' compute at once, while the host pays per pattern — the
    break-even is taken as two substring (MATCH_ANYWHERE) patterns or
    a handful of cheaper prefix/postfix ones (not measured on a local
    chip). Rulesets below that stay compute-trivial ("ttl" class)."""
    if not operations:
        return "ttl"
    anywhere = 0
    patterns = 0
    for op in operations:
        for r in op.rules:
            if r.kind == "ttl_range":
                continue
            patterns += 1
            ft = getattr(r.filter, "filter_type", None)
            if ft == 1:  # FT_MATCH_ANYWHERE
                anywhere += 1
    return "rules" if (anywhere >= 2 or patterns >= 4) else "ttl"


def compaction_eval_submit(blocks, now, default_ttl, partition_version,
                           validate_hash: bool, operations=None,
                           eval_device=None, want_ets: bool = True):
    """Phase 1: dispatch compaction-filter programs WITHOUT waiting.

    `blocks`: [(tag, host_block, pidx)] — host_block is a columnar SST
    Block (storage/sstable.py), `pidx` the owning partition (one wave
    can span a whole table). Blocks are concatenated host-side into
    ~COMPACT_CHUNK_ROWS-record programs per key width (ONE transfer set
    per chunk, not per block). Returns an opaque list for
    compaction_eval_drain. Drop masks come back bit-packed; the
    rewritten-TTL column transfers only when `want_ets` (a pass with no
    default-TTL and no update_ttl rule never reads it).

    `eval_device`: jax device to run on ("auto" via choose_eval_device
    when None is resolved by the caller)."""
    import contextlib

    import jax as _jax

    eval_block = make_compaction_eval(operations)
    ctx = (contextlib.nullcontext() if eval_device is None
           else _jax.default_device(eval_device))

    buckets: dict = {}
    for tag, blk, pidx in blocks:
        buckets.setdefault(int(blk.keys.shape[1]), []).append(
            (tag, blk, pidx))

    submitted = []
    with ctx:
        for _w, group in buckets.items():
            off = 0
            while off < len(group):
                chunk = []
                rows = 0
                while off < len(group):
                    n_blk = group[off][1].count
                    if chunk and rows + n_blk > COMPACT_CHUNK_ROWS:
                        break  # close the chunk at the row target
                    chunk.append(group[off])
                    rows += n_blk
                    off += 1
                cap = _row_bucket(rows)
                keys = np.zeros((cap, _w), dtype=np.uint8)
                key_len = np.zeros(cap, dtype=np.int32)
                ets = np.zeros(cap, dtype=np.uint32)
                valid = np.zeros(cap, dtype=bool)
                pidx_col = np.zeros(cap, dtype=np.uint32)
                use_lo = validate_hash and all(
                    b.hash_lo is not None for _t, b, _p in chunk)
                hash_lo = (np.zeros(cap, dtype=np.uint32) if use_lo
                           else np.zeros(1, dtype=np.uint32))
                pos = 0
                spans = []
                for tag, blk, pidx in chunk:
                    n = blk.count
                    keys[pos:pos + n, :blk.keys.shape[1]] = blk.keys
                    key_len[pos:pos + n] = blk.key_len
                    ets[pos:pos + n] = blk.expire_ts
                    valid[pos:pos + n] = True
                    pidx_col[pos:pos + n] = pidx
                    if use_lo:
                        hash_lo[pos:pos + n] = blk.hash_lo
                    spans.append((tag, pos, n))
                    pos += n
                # hashkey_len from the big-endian u16 key prefix
                hkl = ((key_len > 0)
                       * ((keys[:, 0].astype(np.int32) << 8)
                          | keys[:, 1].astype(np.int32)))
                out = eval_block(
                    keys, key_len, hkl, ets, valid, hash_lo,
                    np.uint32(now), np.uint32(default_ttl), pidx_col,
                    np.uint32(max(partition_version, 0) & 0xFFFFFFFF),
                    validate_hash, use_lo, want_ets=want_ets, pack=True)
                drop = out[0]
                new_ets = out[1] if want_ets else None
                # key matrix + key_len, hashkey_len, expire_ts, pidx (4
                # B each) + valid, and hash_lo where it is used; the
                # packed mask and the rewritten expire_ts back
                note_filter_program(
                    cap, cap * (_w + 17 + (4 if use_lo else 0))
                    + cap // 8 + (4 * cap if want_ets else 0))
                submitted.append((spans, cap, drop, new_ets))
    return submitted


def compaction_eval_drain(submitted, want_ets: bool = True):
    """Phase 2: fetch EVERY submitted result in one transfer round (a
    synchronous fetch has a fixed cost regardless of size) and yield
    (tag, drop[:n], new_ets[:n]|None) per block."""
    import jax as _jax

    arrays = [d for _s, _c, d, _e in submitted]
    if want_ets:
        arrays += [e for _s, _c, _d, e in submitted]
    try:
        fetched = _jax.device_get(arrays)
    except Exception:  # noqa: BLE001 - fall back to per-array fetch
        fetched = [np.asarray(a) for a in arrays]
    n_chunks = len(submitted)
    for i, (spans, cap, _d, _e) in enumerate(submitted):
        drop_all = np.unpackbits(fetched[i], count=cap).astype(bool)
        ets_all = fetched[n_chunks + i] if want_ets else None
        for tag, pos, n in spans:
            yield (tag, drop_all[pos:pos + n],
                   ets_all[pos:pos + n] if want_ets else None)


def compaction_eval_stacked(blocks, now, default_ttl, partition_version,
                            validate_hash: bool, operations=None,
                            eval_device=None, want_ets: bool = True):
    """Submit + drain in one call (the non-pipelined form; the engine's
    windowed compactor overlaps a window's drain/rewrite with the next
    window's submit)."""
    yield from compaction_eval_drain(
        compaction_eval_submit(blocks, now, default_ttl,
                               partition_version, validate_hash,
                               operations=operations,
                               eval_device=eval_device,
                               want_ets=want_ets),
        want_ets=want_ets)
