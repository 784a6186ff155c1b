"""Resident mesh serving: a table's record blocks live STACKED on the
device mesh and one SPMD program answers every partition's scan wave.

The per-partition serving path (scan_coordinator.stacked_block_eval,
partition_server._pushdown_aggregate_page) evaluates predicates in
per-chunk device programs — one dispatch per (key_width, capacity)
flavor per wave, per partition for aggregates. On a mesh the same work
is ONE program: each partition's blocks are a [B] row-slab of a
[P, B, K] resident image sharded PartitionSpec("dp", "sp"), refreshed
incrementally at flush/compaction publish, and a single jitted dispatch
returns

- the static keep mask for every partition (bit-packed on device — the
  device->host link is the scarce resource),
- per-partition [live, pre-value-filter, expired] counts (psum shapes:
  count and sum aggregates never touch rows), and
- per-partition value sums as four uint16 lanes in uint32 accumulators
  (jax x64 is disabled; lane-linearity recombines to sum mod 2^64
  exactly for up to 65536 resident rows per partition).

top_k / sample stay psum-free: the device mask all-gathers to the host
edge and the existing AggState folds the surviving rows in block order,
so results are byte-identical to the host arm by construction.

Placement: ops/placement grows a third "mesh" verdict —
mesh_wave_pays() weighs one mesh round against the host's per-chunk
dispatches — and the PR 15 drift auditor judges the prediction under
the "mesh" class like any other.

Dispatch safety: every dispatch runs under a DispatchWatchdog (bounded
deadline on execution — programs are compiled before the deadline
starts — and a consecutive-failure trip). A trip disables mesh serving
and says so; the per-partition kernels carry on unchanged. A wedged
device can therefore delay one wave, never hang one.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pegasus_tpu.utils.flags import FLAGS, define_flag
from pegasus_tpu.utils.metrics import METRICS

_LOG = logging.getLogger("pegasus.mesh")

define_flag("pegasus.mesh", "serving_enabled", True,
            "route whole-table scan waves and pushdown aggregates to the "
            "resident device mesh when the placement model says it pays",
            mutable=True)
define_flag("pegasus.mesh", "dispatch_deadline_s", 30.0,
            "watchdog bound on one mesh dispatch's execution (compile "
            "happens before the clock starts); an overrun counts one "
            "consecutive dispatch failure", mutable=True)

_NODE = METRICS.entity("storage", "node")
_MESH_DISPATCH = _NODE.counter("mesh_dispatch_count")
_MESH_FALLBACK = _NODE.counter("mesh_fallback_count")
_DISPATCH_WEDGED = _NODE.gauge("mesh_dispatch_wedged")
# compaction-filter offload (the LUDA shape): whole-table drop-mask
# dispatches vs attempts that had to fall back to the host filter
# stage, plus the publish-refresh split — survivor-gather reuse vs
# full slab rebuild — that proves a mesh-filtered compaction never
# pays the predicate work twice
_COMPACT_MESH_DISPATCH = _NODE.counter("compact_mesh_dispatch_count")
_COMPACT_MESH_FALLBACK = _NODE.counter("compact_mesh_fallback_count")
_REFRESH_REUSE = _NODE.counter("mesh_refresh_reuse_count")
_REFRESH_REBUILD = _NODE.counter("mesh_refresh_rebuild_count")

_MASK64 = (1 << 64) - 1

# sum lanes are uint16 values accumulated in uint32: exact while
# rows_per_partition * 65535 < 2^32, i.e. up to 65536 resident rows
MAX_RESIDENT_ROWS = 65536

STACK_CHUNK = 16  # host chunk size (scan_coordinator) — cost-model input


def _servable_filters():
    from pegasus_tpu.ops.predicates import (
        FT_MATCH_ANYWHERE, FT_MATCH_POSTFIX, FT_MATCH_PREFIX, FT_NO_FILTER)
    return frozenset((FT_NO_FILTER, FT_MATCH_ANYWHERE, FT_MATCH_PREFIX,
                      FT_MATCH_POSTFIX))


def _tag_ckey(tag) -> Optional[Tuple[str, int]]:
    """Extract the (run_path, block_offset) cache key every wave caller
    embeds in its tag — bare, or as the tag's last element."""
    if isinstance(tag, tuple):
        if (len(tag) == 2 and isinstance(tag[0], str)
                and isinstance(tag[1], int)):
            return tag
        last = tag[-1] if tag else None
        if (isinstance(last, tuple) and len(last) == 2
                and isinstance(last[0], str) and isinstance(last[1], int)):
            return last
    return None


def _pattern_operands(pattern: bytes):
    """Raw numpy (buf[width], len) pattern operands — width bucketed so
    pattern length changes don't retrace the program. Deliberately NOT
    FilterSpec.make: that cache commits arrays to the ambient default
    device, which may not belong to the mesh."""
    from pegasus_tpu.ops.record_block import next_bucket

    width = next_bucket(max(1, len(pattern)))
    buf = np.zeros(width, dtype=np.uint8)
    if pattern:
        buf[:len(pattern)] = np.frombuffer(pattern, dtype=np.uint8)
    return buf, np.int32(len(pattern))


# -- the one program -------------------------------------------------------

def _mesh_step(keys, key_len, hashkey_len, expire_ts, valid, present, lanes,
               hash_lo,
               hash_pattern, hash_pattern_len, sort_pattern, sort_pattern_len,
               pidx, partition_version, allowed, now, extra, *,
               hash_filter_type: int, sort_filter_type: int,
               validate_hash: bool, with_sum: bool):
    """Whole-table predicate + aggregate step over the [P, B, K] image.

    Reuses _static_block_predicate by flattening [P, B] -> [P*B] with a
    per-row pidx vector (exactly the partition_mesh._scan_step contract)
    so the mesh and single-device paths cannot drift. `allowed` is the
    host-computed reject-all ownership gate per slot; `extra` carries the
    value-filter mask (all-ones when absent); `present` flags real rows
    inside the padded slab. `hash_lo` is the slab-staged per-record key
    hash (computed ONCE at refresh): validation is a compare against the
    resident column, never a per-wave re-hash of every key byte.
    """
    import jax.numpy as jnp

    from pegasus_tpu.ops.predicates import _static_block_predicate, ttl_expired

    p, b, k = keys.shape
    static = _static_block_predicate(
        keys.reshape(p * b, k), key_len.reshape(p * b),
        hashkey_len.reshape(p * b), valid.reshape(p * b),
        hash_pattern, hash_pattern_len, sort_pattern, sort_pattern_len,
        jnp.repeat(pidx, b), partition_version,
        hash_filter_type=hash_filter_type,
        sort_filter_type=sort_filter_type, validate_hash=validate_hash,
        hash_lo=hash_lo.reshape(p * b), use_hash_lo=True)
    static = static.reshape(p, b) & allowed[:, None]
    alive = ~ttl_expired(expire_ts, now)
    considered = static & alive       # survivors before the value filter
    live = considered & extra
    packed = jnp.packbits(static, axis=1)
    counts = jnp.stack([
        live.sum(axis=1, dtype=jnp.int32),
        considered.sum(axis=1, dtype=jnp.int32),
        (present & ~alive).sum(axis=1, dtype=jnp.int32),
    ], axis=1)
    if with_sum:
        lane_sums = (lanes * live[:, :, None].astype(jnp.uint32)
                     ).sum(axis=1, dtype=jnp.uint32)
    else:
        lane_sums = jnp.zeros((p, 4), jnp.uint32)
    return packed, counts, lane_sums


@functools.lru_cache(maxsize=64)
def _mesh_program(mesh, hash_filter_type: int, sort_filter_type: int,
                  validate_hash: bool, with_sum: bool):
    """One compiled whole-table program per (mesh, statics) — a flush
    generation re-dispatches with new operands, it does not re-trace."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    return jax.jit(
        functools.partial(_mesh_step, hash_filter_type=hash_filter_type,
                          sort_filter_type=sort_filter_type,
                          validate_hash=validate_hash, with_sum=with_sum),
        out_shardings=(rep, rep, rep))


# the compaction-filter twin: one compiled program per (mesh, ruleset
# CONTENT, statics). Rulesets are config-sync-delivered objects, so the
# cache keys on ops/compaction._ops_key — re-delivering the same JSON
# reuses the executable instead of leaking one per delivery. A manual
# OrderedDict because parsed Operation tuples are not hashable.
_COMPACT_PROGRAMS: "OrderedDict[tuple, object]" = OrderedDict()
_COMPACT_PROGRAM_CAP = 16


def _mesh_compact_program(mesh, operations, validate_hash: bool,
                          want_ets: bool):
    from pegasus_tpu.ops.compaction import _ops_key, mesh_compact_step

    key = (mesh, _ops_key(operations), bool(validate_hash),
           bool(want_ets))
    prog = _COMPACT_PROGRAMS.get(key)
    if prog is not None:
        _COMPACT_PROGRAMS.move_to_end(key)
        return prog
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    prog = jax.jit(
        functools.partial(mesh_compact_step, operations=operations,
                          validate_hash=bool(validate_hash),
                          want_ets=bool(want_ets)),
        out_shardings=(rep, rep) if want_ets else (rep,))
    _COMPACT_PROGRAMS[key] = prog
    while len(_COMPACT_PROGRAMS) > _COMPACT_PROGRAM_CAP:
        _COMPACT_PROGRAMS.popitem(last=False)
    return prog


# -- watchdog --------------------------------------------------------------

class DispatchWatchdog:
    """Bounded-deadline guard around every mesh dispatch.

    Each dispatch runs on its own daemon thread; the caller waits at most
    the deadline. An overrun or raising dispatch counts one CONSECUTIVE
    failure (any success resets the streak); `trip_after` in a row trips:
    the wedged gauge goes up and the owner disables mesh serving. The
    wedged thread is abandoned (daemon) — it can never queue new waves
    behind itself. What a failed dispatch raised stays readable in
    `last_error` (None for an overrun) and is logged.
    """

    def __init__(self, owner=None, deadline_s: Optional[float] = None,
                 trip_after: int = 2):
        self.owner = owner
        self.deadline_s = deadline_s  # None: pegasus.mesh dispatch flag
        self.trip_after = trip_after
        self.failures = 0       # consecutive
        self.trips = 0
        self.dispatches = 0
        self.last_error: Optional[BaseException] = None
        self._lock = threading.Lock()

    def _deadline(self) -> float:
        if self.deadline_s is not None:
            return float(self.deadline_s)
        return float(FLAGS.get("pegasus.mesh", "dispatch_deadline_s"))

    def run(self, fn):
        """fn() under the dispatch deadline; the result, or None on
        timeout/error (one consecutive failure noted)."""
        box: Dict[str, Any] = {}
        done = threading.Event()

        def _worker():
            try:
                box["out"] = fn()
            except BaseException as exc:  # a dying dispatch is a failure
                box["err"] = exc
            finally:
                done.set()

        threading.Thread(target=_worker, daemon=True,
                         name="mesh-dispatch").start()
        deadline = self._deadline()
        if not done.wait(deadline):
            self.last_error = None
            _LOG.warning("mesh dispatch overran its %.3gs deadline; the "
                         "per-partition kernels serve this one", deadline)
            self._note_failure()
            return None
        if "err" in box:
            self.last_error = box["err"]
            _LOG.error("mesh dispatch raised; the per-partition kernels "
                       "serve this one", exc_info=box["err"])
            self._note_failure()
            return None
        with self._lock:
            self.failures = 0
            self.dispatches += 1
        return box.get("out")

    def _note_failure(self) -> None:
        _MESH_FALLBACK.increment()
        with self._lock:
            self.failures += 1
            tripped = self.failures >= self.trip_after
            if tripped:
                self.failures = 0
        if tripped:
            self.trip()

    def trip(self) -> None:
        self.trips += 1
        _DISPATCH_WEDGED.set(1.0)
        if self.owner is not None:
            self.owner._on_trip()

    def recover(self) -> None:
        with self._lock:
            self.failures = 0
        _DISPATCH_WEDGED.set(0.0)


# -- resident state --------------------------------------------------------

class _Slab:
    """One partition's host-side columnar image: every L1 block of its
    store concatenated, in sorted-run block order (the order the host
    aggregate arm folds in — byte-identity depends on it)."""

    __slots__ = ("server", "lsm_id", "generation", "n_rows", "width",
                 "keys", "key_len", "hashkey_len", "expire_ts", "valid",
                 "hash_lo", "flags", "segments", "lanes", "hdr")

    def __init__(self, server, lsm_id: int, generation: int):
        self.server = server
        self.lsm_id = lsm_id
        self.generation = generation
        self.n_rows: Optional[int] = None  # None: oversized / unservable
        self.width = 32
        self.keys = None
        self.key_len = None
        self.hashkey_len = None
        self.expire_ts = None
        self.valid = None
        self.hash_lo = None
        self.flags = None  # uint8[n] tombstone flags — host-only
        #                    column so the survivor-gather refresh can
        #                    replay the write stage's flags==0 check
        #                    without re-reading any block
        self.segments: List[tuple] = []  # (ckey, blk, start, n)
        self.lanes = None                # uint32[n, 4] — built on demand
        self.hdr = 0

    def ensure_lanes(self) -> None:
        if self.lanes is not None or not self.n_rows:
            self.lanes = self.lanes if self.lanes is not None else \
                np.zeros((self.n_rows or 0, 4), np.uint32)
            return
        from pegasus_tpu.ops.pushdown import values_as_u64

        lanes = np.zeros((self.n_rows, 4), np.uint32)
        for _ckey, blk, start, n in self.segments:
            vals = values_as_u64(blk.value_heap, blk.value_offs, self.hdr,
                                 np.arange(n))
            for j in range(4):
                lanes[start:start + n, j] = (
                    (vals >> np.uint64(16 * j)) & np.uint64(0xFFFF)
                ).astype(np.uint32)
        self.lanes = lanes


def _build_slab(server) -> _Slab:
    from pegasus_tpu.base.value_schema import header_length
    from pegasus_tpu.ops.record_block import block_from_columns

    lsm = server.engine.lsm
    slab = _Slab(server, id(lsm), lsm.generation)
    slab.hdr = header_length(server.data_version)
    entries = []  # (ckey, blk, n)
    total = 0
    width = 32
    for run in list(lsm.l1_runs):
        for idx, bm in enumerate(run.blocks):
            blk = run.read_block(idx)
            n = int(len(blk.expire_ts))
            entries.append(((run.path, bm.offset), blk, n))
            total += n
            width = max(width, int(blk.keys.shape[1]))
    if total > MAX_RESIDENT_ROWS:
        return slab  # n_rows stays None: partition too large to reside
    slab.n_rows = total
    slab.width = width
    slab.keys = np.zeros((total, width), np.uint8)
    slab.key_len = np.zeros(total, np.int32)
    slab.hashkey_len = np.zeros(total, np.int32)
    slab.expire_ts = np.zeros(total, np.uint32)
    slab.valid = np.zeros(total, bool)
    slab.hash_lo = np.zeros(total, np.uint32)
    slab.flags = np.zeros(total, np.uint8)
    start = 0
    for ckey, blk, n in entries:
        nb = block_from_columns(blk.keys, blk.key_len, blk.expire_ts)
        slab.keys[start:start + n, :nb.keys.shape[1]] = nb.keys[:n]
        slab.key_len[start:start + n] = nb.key_len[:n]
        slab.hashkey_len[start:start + n] = nb.hashkey_len[:n]
        slab.expire_ts[start:start + n] = nb.expire_ts[:n]
        slab.valid[start:start + n] = nb.valid[:n]
        # the per-record key hash is immutable alongside the keys, so it
        # resides WITH them: one batched crc64 pass per slab build (or
        # the SST's own column when carried) and every later wave
        # validates by compare instead of re-hashing the key bytes
        if blk.hash_lo is not None:
            slab.hash_lo[start:start + n] = np.asarray(
                blk.hash_lo, np.uint32)[:n]
        else:
            slab.hash_lo[start:start + n] = _slab_hash_lo(nb, n)
        if blk.flags is not None:
            slab.flags[start:start + n] = np.asarray(
                blk.flags, np.uint8)[:n]
        slab.segments.append((ckey, blk, start, n))
        start += n
    return slab


class _LazyBlock:
    """Segment proxy for a survivor-refreshed slab: the slab's columns
    were gathered host-side, so the underlying block bytes are only
    needed if a later aggregate fold / value-mask touches this segment
    — then the run is read once, on demand, exactly like _build_slab
    would have."""

    __slots__ = ("_run", "_idx", "_blk")

    def __init__(self, run, idx: int):
        self._run = run
        self._idx = idx
        self._blk = None

    def __getattr__(self, name):
        blk = object.__getattribute__(self, "_blk")
        if blk is None:
            run = object.__getattribute__(self, "_run")
            idx = object.__getattribute__(self, "_idx")
            blk = run.read_block(idx)
            object.__setattr__(self, "_blk", blk)
        return getattr(blk, name)


def _survivor_slab(server, slab0: Optional[_Slab],
                   pending: Optional[tuple]) -> Optional[_Slab]:
    """Refresh one partition's slab from the drop masks its own
    mesh-filtered compaction computed: gather the surviving rows out of
    the OLD slab columns instead of re-reading (and re-hashing) every
    published block. Returns the new slab, or None when anything about
    the publish doesn't match the stashed masks — interleaved flush,
    geometry drift, merge-path compaction — in which case the caller
    does the full rebuild (always safe).

    Verification is structural, not trusting: the new L1 runs' block
    metas must align 1:1 — count AND first key — with the nonzero
    survivor sets the masks predict (bulk_compact_rewrite emits one
    output block per surviving input block, in order), so a publish
    produced by anything other than exactly these masks rebuilds."""
    if pending is None or slab0 is None:
        return None
    p_slab, masks, _want_ets = pending
    lsm = server.engine.lsm
    if (p_slab is not slab0 or slab0.n_rows is None
            or slab0.flags is None
            or slab0.lsm_id != id(lsm)
            or lsm.generation != slab0.generation + 1
            or len(lsm.memtable) or lsm.l0):
        return None
    # survivors per old segment: THE survivor definition, shared with
    # bulk_compact_rewrite's transform
    from pegasus_tpu.storage.lsm import survivor_mask

    surv = []  # (src_rows, ets_rows)
    for ckey, _blk, start, n in slab0.segments:
        m = masks.get(ckey)
        if m is None:
            return None
        drop, ets_new = m
        keep = survivor_mask(drop, slab0.flags[start:start + n])
        kept = np.flatnonzero(keep)
        if kept.size == 0:
            continue
        src = start + kept
        ets_rows = (np.asarray(ets_new)[kept] if ets_new is not None
                    else slab0.expire_ts[src])
        surv.append((src, ets_rows))
    new_entries = [(run, idx, bm) for run in list(lsm.l1_runs)
                   for idx, bm in enumerate(run.blocks)]
    if len(surv) != len(new_entries):
        return None
    slab = _Slab(server, id(lsm), lsm.generation)
    slab.hdr = slab0.hdr
    total = sum(int(src.size) for src, _e in surv)
    slab.n_rows = total
    slab.width = slab0.width
    all_src = (np.concatenate([src for src, _e in surv])
               if surv else np.zeros(0, np.int64))
    slab.keys = slab0.keys[all_src]
    slab.key_len = slab0.key_len[all_src]
    slab.hashkey_len = slab0.hashkey_len[all_src]
    slab.valid = slab0.valid[all_src]
    slab.hash_lo = slab0.hash_lo[all_src]
    slab.flags = slab0.flags[all_src]
    slab.expire_ts = (np.concatenate([e for _s, e in surv])
                      if surv else np.zeros(0, np.uint32)
                      ).astype(np.uint32, copy=False)
    if slab0.lanes is not None:
        # value payloads survive a TTL-header patch untouched (the
        # u64 lanes read past the header), so gathered lanes stay exact
        slab.lanes = slab0.lanes[all_src]
    start = 0
    for (src, _ets), (run, idx, bm) in zip(surv, new_entries):
        n = int(src.size)
        if int(bm.count) != n:
            return None
        first = src[0]
        if bytes(slab0.keys[first, :int(slab0.key_len[first])]) \
                != bm.first_key:
            return None
        slab.segments.append(((run.path, bm.offset),
                              _LazyBlock(run, idx), start, n))
        start += n
    return slab


def _slab_hash_lo(nb, n: int) -> np.ndarray:
    """uint32[n] pegasus key-hash low lane from a padded key matrix, one
    vectorized crc64 pass. The hashed region always starts at byte 2:
    the hashkey, or (empty hashkey) the sort key, which then also begins
    at offset 2 — predicates.host_key_hash_lo's rule on columnar rows."""
    from pegasus_tpu.base.crc import crc64_batch

    if n == 0:
        return np.zeros(0, np.uint32)
    mat = np.ascontiguousarray(nb.keys[:n, 2:])
    hkl = nb.hashkey_len[:n]
    lens = np.where(hkl > 0, hkl, np.maximum(nb.key_len[:n] - 2, 0))
    return (crc64_batch(mat, lens.astype(np.int32), start=0)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


class _Stack:
    """The device-resident [P, B, K] image of one table + its segment
    index. Immutable once built; a refresh swaps in a new one."""

    __slots__ = ("pmesh", "P", "B", "K", "keys", "key_len", "hashkey_len",
                 "expire_ts", "valid", "present", "hash_lo", "pidx",
                 "pidx_np", "slots", "index", "ones_extra", "rows_total",
                 "batch_bytes", "_lanes", "_extra_cache")

    def lanes_dev(self):
        if self._lanes is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            arr = np.zeros((self.P, self.B, 4), np.uint32)
            for slot, (_pidx, slab) in enumerate(self.slots):
                slab.ensure_lanes()
                arr[slot, :slab.n_rows] = slab.lanes
            self._lanes = jax.device_put(
                arr, NamedSharding(self.pmesh.mesh, P("dp", "sp", None)))
        return self._lanes

    def extra_dev(self, vf):
        """The value-filter mask as a [P, B] operand; reuses the server's
        cached per-block masks so the pruned accounting matches the host
        arm bit for bit."""
        if vf is None:
            return self.ones_extra
        hit = self._extra_cache.get(vf)
        if hit is not None:
            return hit
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = np.zeros((self.P, self.B), bool)
        for slot, (_pidx, slab) in enumerate(self.slots):
            for ckey, blk, start, n in slab.segments:
                arr[slot, start:start + n] = np.asarray(
                    slab.server._value_mask(ckey, blk, vf))[:n]
        dev = jax.device_put(
            arr, NamedSharding(self.pmesh.mesh, P("dp", "sp")))
        if len(self._extra_cache) >= 8:
            self._extra_cache.clear()
        self._extra_cache[vf] = dev
        return dev


def _build_stack(pmesh, slabs: List[Tuple[int, _Slab]]) -> _Stack:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = pmesh.dp
    n_slots = len(slabs)
    p_pad = max(dp, ((n_slots + dp - 1) // dp) * dp)
    max_rows = max(1, max(s.n_rows for _, s in slabs))
    b = 8
    while b < max_rows:
        b <<= 1
    k = max(32, max(s.width for _, s in slabs))

    keys = np.zeros((p_pad, b, k), np.uint8)
    key_len = np.zeros((p_pad, b), np.int32)
    hashkey_len = np.zeros((p_pad, b), np.int32)
    expire_ts = np.zeros((p_pad, b), np.uint32)
    valid = np.zeros((p_pad, b), bool)
    present = np.zeros((p_pad, b), bool)
    hash_lo = np.zeros((p_pad, b), np.uint32)
    pidx = np.zeros(p_pad, np.uint32)

    st = _Stack()
    st.index = {}
    st.slots = []
    st.rows_total = 0
    for slot, (part_idx, slab) in enumerate(slabs):
        n = slab.n_rows
        keys[slot, :n, :slab.keys.shape[1]] = slab.keys
        key_len[slot, :n] = slab.key_len
        hashkey_len[slot, :n] = slab.hashkey_len
        expire_ts[slot, :n] = slab.expire_ts
        valid[slot, :n] = slab.valid
        present[slot, :n] = True
        hash_lo[slot, :n] = slab.hash_lo
        pidx[slot] = part_idx
        for ckey, _blk, start, seg_n in slab.segments:
            st.index[ckey] = (slot, start, seg_n)
        st.slots.append((part_idx, slab))
        st.rows_total += n

    mesh = pmesh.mesh
    key_sh = NamedSharding(mesh, P("dp", "sp", None))
    col_sh = NamedSharding(mesh, P("dp", "sp"))
    pid_sh = NamedSharding(mesh, P("dp"))
    st.pmesh = pmesh
    st.P, st.B, st.K = p_pad, b, k
    st.keys = jax.device_put(keys, key_sh)
    st.key_len = jax.device_put(key_len, col_sh)
    st.hashkey_len = jax.device_put(hashkey_len, col_sh)
    st.expire_ts = jax.device_put(expire_ts, col_sh)
    st.valid = jax.device_put(valid, col_sh)
    st.present = jax.device_put(present, col_sh)
    st.hash_lo = jax.device_put(hash_lo, col_sh)
    st.pidx = jax.device_put(pidx, pid_sh)
    st.pidx_np = pidx
    st.ones_extra = jax.device_put(np.ones((p_pad, b), bool), col_sh)
    # same accounting the host wave auditor uses: key bytes + the 9
    # bytes/record of length/expiry columns
    st.batch_bytes = sum(
        int(s.keys.size) + 9 * int(s.n_rows) for _, s in slabs)
    st._lanes = None
    st._extra_cache = {}
    return st


class _TableResident:
    """One table's attachment record: its servers, per-partition slabs,
    and the current stacked device image."""

    def __init__(self, app_id: int):
        self.app_id = app_id
        self.servers: Dict[int, Any] = {}
        self.dirty: set = set()
        self.slabs: Dict[int, _Slab] = {}
        self.stack: Optional[_Stack] = None
        # pidx -> (slab, {ckey: (drop, ets|None)}, want_ets): the drop
        # masks a mesh-filtered compaction served, stashed until its
        # publish lands so the refresh can survivor-gather instead of
        # re-reading every block (the compaction already paid the
        # predicate work once)
        self.pending: Dict[int, tuple] = {}

    def refresh(self, owner: "MeshServing", pmesh) -> bool:
        """Rebuild ONLY the slabs whose store changed (publish-marked
        dirty, generation bump, or engine swap), restack if anything
        did. A dirty partition whose own mesh-filtered compaction just
        published reuses the stashed survivor masks (gather, no block
        reads); everything else takes the full rebuild. Returns whether
        the device image changed."""
        changed = False
        for pidx in sorted(self.servers):
            server = self.servers[pidx]
            lsm = server.engine.lsm
            slab = self.slabs.get(pidx)
            if (slab is None or pidx in self.dirty
                    or slab.lsm_id != id(lsm)
                    or slab.generation != lsm.generation):
                new_slab = _survivor_slab(server, slab,
                                          self.pending.pop(pidx, None))
                if new_slab is not None:
                    self.slabs[pidx] = new_slab
                    owner.refresh_reuses += 1
                    _REFRESH_REUSE.increment()
                else:
                    self.slabs[pidx] = _build_slab(server)
                    owner.slab_builds += 1
                    if slab is not None:  # a REFRESH, not first attach
                        owner.refresh_rebuilds += 1
                        _REFRESH_REBUILD.increment()
                changed = True
        self.dirty.clear()
        for pidx in list(self.slabs):
            if pidx not in self.servers:
                del self.slabs[pidx]
                changed = True
        if changed or (self.stack is None and self.slabs):
            slabs = [(pidx, self.slabs[pidx])
                     for pidx in sorted(self.slabs)]
            if slabs and all(s.n_rows is not None for _, s in slabs):
                self.stack = _build_stack(pmesh, slabs)
                owner.stack_builds += 1
            else:
                self.stack = None  # some partition exceeds residency
            changed = True
        return changed


# -- the serving layer -----------------------------------------------------

class MeshServing:
    """Singleton mesh-serving registry: explicit per-server attach, one
    resident stack per table, one program dispatch per wave."""

    def __init__(self):
        self._lock = threading.RLock()
        self._tables: Dict[int, _TableResident] = {}
        self._index: Dict[tuple, tuple] = {}  # ckey -> (tres, slot, start, n)
        self._pmesh = None
        self._mesh_failed = False
        self.disabled = False
        self.watchdog = DispatchWatchdog(self)
        # (program, operand shapes) -> AOT executable: compiled BEFORE
        # the watchdog's clock starts, so the deadline bounds execution
        self._executables: Dict[tuple, Any] = {}
        self.compiles = 0
        self.compile_s = 0.0
        self.wave_dispatches = 0
        self.agg_dispatches = 0
        self.host_waves = 0
        self.slab_builds = 0
        self.stack_builds = 0
        self.compact_dispatches = 0
        self.compact_mask_serves = 0
        self.refresh_reuses = 0
        self.refresh_rebuilds = 0
        self._agg_cache: Dict[tuple, dict] = {}
        # (params, ckey) -> (drop, ets|None): per-BLOCK mask slices from
        # whole-table compaction dispatches. Keyed by run path + block
        # offset (immutable file content), so sibling partitions
        # compacting in the same epoch second reuse ONE dispatch even
        # across the restacks their interleaved publishes trigger.
        self._compact_cache: Dict[tuple, tuple] = {}

    # -- lifecycle ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return (not self.disabled and bool(self._tables)
                and bool(FLAGS.get("pegasus.mesh", "serving_enabled")))

    def attach(self, server) -> None:
        """Opt one partition server into mesh serving. Grouped per table
        (app_id); subscribes to the server's publish fan-out so flush and
        compaction installs mark exactly that partition dirty."""
        with self._lock:
            tres = self._tables.get(server.app_id)
            if tres is None:
                tres = self._tables[server.app_id] = _TableResident(
                    server.app_id)
            tres.servers[server.pidx] = server
            tres.dirty.add(server.pidx)
            listeners = getattr(server, "publish_listeners", None)
            if listeners is not None:
                app_id, pidx = server.app_id, server.pidx

                def _on_publish(_live_paths, _self=self, _a=app_id, _p=pidx):
                    _self.note_publish(_a, _p)

                listeners.append(_on_publish)

    def note_publish(self, app_id: int, pidx: int) -> None:
        with self._lock:
            tres = self._tables.get(app_id)
            if tres is not None and pidx in tres.servers:
                tres.dirty.add(pidx)
                self._agg_cache.clear()

    def reset(self) -> None:
        """Full detach — test/bench isolation hook. Stale publish hooks on
        previously attached servers no-op via the note_publish guard."""
        with self._lock:
            self._tables.clear()
            self._index.clear()
            self._agg_cache.clear()
            self._compact_cache.clear()
            self._pmesh = None
            self._mesh_failed = False
            self.disabled = False
            self.watchdog = DispatchWatchdog(self)
            self._executables.clear()
            self.compiles = 0
            self.compile_s = 0.0
            self.wave_dispatches = self.agg_dispatches = 0
            self.host_waves = 0
            self.slab_builds = self.stack_builds = 0
            self.compact_dispatches = self.compact_mask_serves = 0
            self.refresh_reuses = self.refresh_rebuilds = 0
        _DISPATCH_WEDGED.set(0.0)

    def note_host_wave(self) -> None:
        self.host_waves += 1

    def note_compact_failure(self) -> None:
        """try_compact_masks raised (call from the except block): the
        caller's host filter stage carries on, visibly."""
        _COMPACT_MESH_FALLBACK.increment()
        _LOG.exception("mesh compaction-mask dispatch raised; the host "
                       "filter stage serves this compaction")

    # -- mesh / refresh ----------------------------------------------------

    def _mesh_or_none(self):
        with self._lock:
            if self._pmesh is not None:
                return self._pmesh
            if self._mesh_failed:
                return None
            try:
                from pegasus_tpu.parallel.partition_mesh import make_mesh

                self._pmesh = make_mesh()
            except Exception:
                self._mesh_failed = True
                _LOG.exception("no device mesh could be built; mesh "
                               "serving stays off until reset()")
                return None
            return self._pmesh

    def _on_trip(self) -> None:
        """Watchdog verdict: dispatches to the mesh keep failing.
        Disable mesh serving and let the per-partition kernels carry
        (they never stopped working)."""
        with self._lock:
            self._agg_cache.clear()
            self.disabled = True
        _LOG.error("mesh dispatch watchdog tripped (%d trips): mesh "
                   "serving disabled until reset()", self.watchdog.trips)

    def _executable(self, prog, args):
        """The AOT-compiled form of `prog` for these operands. Compiling
        here, outside DispatchWatchdog.run, keeps a cold compile from
        being read as a wedged device; compile time is counted apart."""
        key = (prog,) + tuple(
            (getattr(a, "shape", ()), str(getattr(a, "dtype", "")))
            for a in args if a is not None)
        with self._lock:
            exe = self._executables.get(key)
        if exe is None:
            t0 = time.perf_counter()
            exe = prog.lower(*args).compile()
            with self._lock:
                if len(self._executables) >= 64:
                    self._executables.clear()
                self._executables[key] = exe
                self.compiles += 1
                self.compile_s += time.perf_counter() - t0
        return exe

    def ensure_current(self) -> bool:
        """Refresh every attached table's resident image (incremental:
        only publish-dirty / generation-bumped partitions restage)."""
        pmesh = self._mesh_or_none()
        if pmesh is None:
            return False
        with self._lock:
            changed = False
            for tres in self._tables.values():
                changed |= tres.refresh(self, pmesh)
            if changed:
                self._index = {}
                for tres in self._tables.values():
                    st = tres.stack
                    if st is not None:
                        for ckey, loc in st.index.items():
                            self._index[ckey] = (tres,) + loc
                self._agg_cache.clear()
            return True

    # -- dispatch ----------------------------------------------------------

    def _run_program(self, stack: _Stack, validate: bool, pv: int,
                     filter_key, now: int, extra, with_sum: bool):
        """One watchdogged whole-table dispatch. Returns
        (measured_s, (packed, counts, lane_sums)) numpy, or None."""
        hft, hfp, sft, sfp = filter_key
        hpat, hlen = _pattern_operands(hfp)
        spat, slen = _pattern_operands(sfp)
        if validate and pv < 0:
            allowed = np.zeros(stack.P, bool)
        elif validate:
            allowed = stack.pidx_np <= np.uint32(max(pv, 0))
        else:
            allowed = np.ones(stack.P, bool)
        lanes = stack.lanes_dev() if with_sum else None
        prog = _mesh_program(stack.pmesh.mesh, int(hft), int(sft),
                             bool(validate), bool(with_sum))
        pv_op = np.uint32(max(pv, 0) & 0xFFFFFFFF)
        now_op = np.uint32(now)
        args = (stack.keys, stack.key_len, stack.hashkey_len,
                stack.expire_ts, stack.valid, stack.present, lanes,
                stack.hash_lo, hpat, hlen, spat, slen, stack.pidx, pv_op,
                allowed, now_op, extra)
        exe = self._executable(prog, args)

        def _call():
            import jax

            return jax.device_get(exe(*args))

        t0 = time.perf_counter()
        out = self.watchdog.run(_call)
        if out is None:
            return None
        return time.perf_counter() - t0, out

    def _audit(self, perf_ctxs, partitions: int, predicted_s: float,
               measured_s: float) -> None:
        from pegasus_tpu.server.workload import DRIFT
        from pegasus_tpu.utils import perf_context as perf

        DRIFT.note("mesh", predicted_s, measured_s)
        ctxs = [pc for pc in perf_ctxs if pc is not None]
        ambient = perf.current()
        if ambient is not None and all(pc is not ambient for pc in ctxs):
            ctxs.append(ambient)
        for pc in ctxs:
            pc.placement = "mesh"
            pc.predicted_kernel_ms += predicted_s * 1000.0
            pc.measured_kernel_ms += measured_s * 1000.0
            pc.mesh_partitions += partitions
            pc.mesh_wave_ms += measured_s * 1000.0

    def try_wave(self, blocks, validate: bool, pv: int, filter_key=None,
                 perf_ctxs=()) -> Optional[list]:
        """Serve one stacked wave from the resident image: ONE dispatch
        for every (tag, block) regardless of flavor mix. Returns
        [(tag, static_keep bool[n])] in input order, or None to decline
        (the host chunk path then runs unchanged)."""
        if not self.enabled:
            return None
        from pegasus_tpu.ops.predicates import FT_NO_FILTER

        fkey = tuple(filter_key) if filter_key else (
            FT_NO_FILTER, b"", FT_NO_FILTER, b"")
        servable = _servable_filters()
        if fkey[0] not in servable or fkey[2] not in servable:
            self.host_waves += 1
            return None
        if not self.ensure_current():
            self.host_waves += 1
            return None
        with self._lock:
            resolved = []
            tres0 = None
            batch_bytes = 0
            flavor_counts: Dict[tuple, int] = {}
            for tag, dev, bpidx in blocks:
                ckey = _tag_ckey(tag)
                hit = self._index.get(ckey) if ckey is not None else None
                if hit is None:
                    self.host_waves += 1
                    return None
                tres, slot, start, n = hit
                if tres0 is None:
                    tres0 = tres
                elif tres is not tres0:  # one table per resident program
                    self.host_waves += 1
                    return None
                if int(tres.stack.pidx_np[slot]) != int(bpidx):
                    self.host_waves += 1
                    return None
                resolved.append((tag, slot, start, n))
                batch_bytes += (int(dev.keys.size)
                                + 9 * int(dev.expire_ts.size))
                flavor = (int(dev.keys.shape[-1]), int(dev.keys.shape[0]))
                flavor_counts[flavor] = flavor_counts.get(flavor, 0) + 1
            stack = tres0.stack

            from pegasus_tpu.ops import placement

            n_programs = sum((c + STACK_CHUNK - 1) // STACK_CHUNK
                             for c in flavor_counts.values())
            if not placement.mesh_wave_pays(n_programs, batch_bytes):
                self.host_waves += 1
                return None

            res = self._run_program(stack, validate, pv, fkey, now=0,
                                    extra=stack.ones_extra, with_sum=False)
            if res is None:  # watchdog declined — host kernels carry
                self.host_waves += 1
                return None
            measured_s, (packed, _counts, _lanes) = res

        static = np.unpackbits(np.asarray(packed), axis=1).astype(bool)
        predicted_s = placement.predict_kernel_seconds("mesh", batch_bytes)
        _MESH_DISPATCH.increment()
        self.wave_dispatches += 1
        partitions = len({slot for _t, slot, _s, _n in resolved})
        self._audit(perf_ctxs, partitions, predicted_s, measured_s)
        return [(tag, static[slot, start:start + n])
                for tag, slot, start, n in resolved]

    def try_aggregate(self, server, req, pd, validate: bool, filter_key,
                      now: int, perf_ctx=None) -> Optional[dict]:
        """Answer one partition's whole-range pushdown aggregate from the
        table-wide resident dispatch. The dispatch is cached per (image,
        predicate, now): the first partition of a table pays one program,
        its siblings read their slot of the same result. Returns a dict
        (agg_state, pruned, expired, rows_evaluated, partitions, wave
        timings) or None to decline."""
        if not self.enabled:
            return None
        try:
            iter_budget = int(FLAGS.get("pegasus.server",
                                        "rocksdb_max_iteration_count") or 0)
        except KeyError:
            iter_budget = 0
        with self._lock:
            tres = self._tables.get(server.app_id)
        if tres is None or tres.servers.get(server.pidx) is not server:
            return None
        if server.engine.lsm.sorted_runs() is None:
            return None  # memtable / L0 overlay: host merge path handles
        fkey = tuple(filter_key)
        servable = _servable_filters()
        if fkey[0] not in servable or fkey[2] not in servable:
            return None
        if not self.ensure_current():
            return None
        from pegasus_tpu.ops import placement
        from pegasus_tpu.ops.predicates import host_alive_mask
        from pegasus_tpu.ops.pushdown import AggState

        with self._lock:
            stack = tres.stack
            if stack is None:
                return None
            slab = tres.slabs.get(server.pidx)
            slot = None
            for s, (part_idx, sl) in enumerate(stack.slots):
                if part_idx == server.pidx and sl is slab:
                    slot = s
                    break
            if slot is None or slab is None or slab.n_rows is None:
                return None
            if 0 < iter_budget < slab.n_rows:
                return None  # the host arm would PAGE this range: the
                #               paging protocol (partial rides the scan
                #               context, ships on the final page) must
                #               stay observable, so the mesh declines
            if slab.generation != server.engine.lsm.generation:
                return None  # raced a publish mid-call: host arm serves
            pv = int(server.partition_version)
            vf = pd.value_filter
            with_sum = pd.aggregate == "sum"
            cache_key = (id(stack), bool(validate), pv, fkey, vf, int(now),
                         with_sum)
            hit = self._agg_cache.get(cache_key)
            wave_ms = predicted_ms = measured_ms = 0.0
            if hit is None:
                # one mesh round vs one host wave per attached partition
                if not placement.mesh_wave_pays(max(1, len(stack.slots)),
                                                stack.batch_bytes):
                    return None
                extra = stack.extra_dev(vf)
                res = self._run_program(stack, validate, pv, fkey, now,
                                        extra, with_sum)
                if res is None:
                    return None
                measured_s, (packed, counts, lane_sums) = res
                lanes = np.asarray(lane_sums, dtype=np.uint64)
                totals = [int(lanes[s, 0] + (lanes[s, 1] << np.uint64(16))
                              + (lanes[s, 2] << np.uint64(32))
                              + (lanes[s, 3] << np.uint64(48))) & _MASK64
                          for s in range(stack.P)]
                hit = {
                    "static": np.unpackbits(np.asarray(packed),
                                            axis=1).astype(bool),
                    "counts": np.asarray(counts),
                    "totals": totals,
                }
                if len(self._agg_cache) >= 16:
                    self._agg_cache.clear()
                self._agg_cache[cache_key] = hit
                predicted_s = placement.predict_kernel_seconds(
                    "mesh", stack.batch_bytes)
                _MESH_DISPATCH.increment()
                self.agg_dispatches += 1
                from pegasus_tpu.server.workload import DRIFT

                DRIFT.note("mesh", predicted_s, measured_s)
                wave_ms = measured_ms = measured_s * 1000.0
                predicted_ms = predicted_s * 1000.0
            counts = hit["counts"]
            live_n = int(counts[slot, 0])
            considered = int(counts[slot, 1])
            expired = int(counts[slot, 2])
            partitions = len(stack.slots)

        state = AggState(pd)
        if pd.aggregate == "count":
            state.count = live_n
        elif pd.aggregate == "sum":
            state.count = live_n
            state.total = hit["totals"][slot]
        else:  # top_k / sample: all-gathered mask, host-edge fold in the
            # exact block order the host arm uses
            static_row = hit["static"][slot]
            for ckey, blk, start, n in slab.segments:
                keep = static_row[start:start + n] \
                    & host_alive_mask(blk.expire_ts, now)[:n]
                if vf is not None:
                    keep = keep & np.asarray(
                        server._value_mask(ckey, blk, vf))[:n]
                sel = np.flatnonzero(keep)
                state.fold_columnar(sel, heap=blk.value_heap,
                                    value_offs=blk.value_offs,
                                    hdr=slab.hdr, key_at=blk.key_at)
        return {
            "agg_state": state,
            "folded": live_n,
            "pruned": considered - live_n,
            "expired": expired,
            "rows_evaluated": int(slab.n_rows),
            "partitions": partitions,
            "wave_ms": wave_ms,
            "predicted_ms": predicted_ms,
            "measured_ms": measured_ms,
        }

    # -- compaction filter offload (the LUDA shape) ------------------------

    def _compact_params(self, now, default_ttl, partition_version,
                        validate, operations, want_ets) -> tuple:
        from pegasus_tpu.ops.compaction import _ops_key

        return (int(now) & 0xFFFFFFFF, int(default_ttl) & 0xFFFFFFFF,
                int(max(partition_version, 0)) & 0xFFFFFFFF,
                bool(validate), _ops_key(operations), bool(want_ets))

    def _compact_masks_from_cache(self, params, entries):
        """{(run, idx): (drop, ets|None)} for every entry, or None if
        any block's mask isn't cached under these filter params."""
        out = {}
        for run, i, bm in entries:
            m = self._compact_cache.get((params, (run.path, bm.offset)))
            if m is None:
                return None
            out[(run, i)] = m
        return out

    def _stash_pending(self, tres, pidx: int, lsm, params,
                       want_ets: bool) -> None:
        """Record the served masks against the partition's CURRENT slab
        so the publish this compaction is about to do can refresh
        residency by survivor-gather instead of a full rebuild."""
        slab = tres.slabs.get(pidx)
        if (slab is None or slab.n_rows is None
                or slab.lsm_id != id(lsm)
                or slab.generation != lsm.generation):
            return
        masks = {}
        for ckey, _blk, _start, _n in slab.segments:
            m = self._compact_cache.get((params, ckey))
            if m is None:
                return
            masks[ckey] = m
        tres.pending[pidx] = (slab, masks, want_ets)

    def try_compact_masks(self, lsm, entries, now, default_ttl, pidx,
                          partition_version, validate, operations,
                          want_ets: bool, n_windows: int = 1
                          ) -> Optional[dict]:
        """Serve one bulk compaction's FILTER stage from the resident
        image: ONE whole-table SPMD dispatch computes the drop masks
        (and rewritten-TTL column) for ALL of the table's partitions,
        and each sibling partition compacting under the same filter
        params in the same epoch second reads its blocks' slices from
        the per-ckey cache — table-wide compaction pays one dispatch,
        not one per partition per window.

        `entries` is lsm.bulk_compact_entries(); returns
        {(run, idx): (drop bool[n], new_ets uint32[n]|None)} covering
        every entry, or None to decline — gate says host wins, blocks
        not resident, store raced a publish, or the watchdog tripped
        mid-dispatch (mesh serving is then off for the NEXT compaction;
        this one falls back to the host filter stage, byte-identical by
        construction)."""
        if not self.enabled or not entries:
            return None
        pidx = int(pidx)
        params = self._compact_params(now, default_ttl,
                                      partition_version, validate,
                                      operations, want_ets)
        with self._lock:
            tres = None
            for t in self._tables.values():
                srv = t.servers.get(pidx)
                if srv is not None and srv.engine.lsm is lsm:
                    tres = t
                    break
            if tres is None:
                return None
            got = self._compact_masks_from_cache(params, entries)
            if got is not None:  # a sibling's dispatch covered us
                self.compact_mask_serves += 1
                self._stash_pending(tres, pidx, lsm, params, want_ets)
                return got
        if not self.ensure_current():
            _COMPACT_MESH_FALLBACK.increment()
            return None
        from pegasus_tpu.ops import placement

        with self._lock:
            got = self._compact_masks_from_cache(params, entries)
            if got is not None:  # raced a sibling mid-refresh
                self.compact_mask_serves += 1
                self._stash_pending(tres, pidx, lsm, params, want_ets)
                return got
            stack = tres.stack
            slab = tres.slabs.get(pidx)
            if (stack is None or slab is None or slab.n_rows is None
                    or slab.lsm_id != id(lsm)
                    or slab.generation != lsm.generation):
                _COMPACT_MESH_FALLBACK.increment()
                return None
            for run, i, bm in entries:
                hit = stack.index.get((run.path, bm.offset))
                if hit is None or int(stack.pidx_np[hit[0]]) != pidx:
                    _COMPACT_MESH_FALLBACK.increment()
                    return None
            n_slots = max(1, len(stack.slots))
            mask_bytes = stack.P * (stack.B // 8)
            if want_ets:
                mask_bytes += 4 * stack.P * stack.B
            # one whole-table dispatch amortizes over every attached
            # partition's windows; a solo small compaction (one window,
            # one partition) honestly stays on the host filter stage
            if not placement.mesh_compact_pays(
                    max(1, int(n_windows)) * n_slots,
                    stack.batch_bytes, mask_bytes):
                return None
            prog = _mesh_compact_program(stack.pmesh.mesh, operations,
                                         bool(validate), bool(want_ets))
            if validate:
                allowed = stack.pidx_np <= np.uint32(params[2])
            else:
                allowed = np.ones(stack.P, bool)
            args = (stack.keys, stack.key_len, stack.hashkey_len,
                    stack.expire_ts, stack.present, stack.hash_lo,
                    stack.pidx, allowed, np.uint32(params[0]),
                    np.uint32(params[1]), np.uint32(params[2]))
            exe = self._executable(prog, args)

            def _call():
                import jax

                return jax.device_get(exe(*args))

            t0 = time.perf_counter()
            out = self.watchdog.run(_call)
            if out is None:  # overrun/error: this compaction goes host
                _COMPACT_MESH_FALLBACK.increment()
                return None
            measured_s = time.perf_counter() - t0
            drop_all = np.unpackbits(np.asarray(out[0]), axis=1,
                                     count=stack.B).astype(bool)
            ets_all = np.asarray(out[1]) if want_ets else None
            if len(self._compact_cache) > 65536:
                self._compact_cache.clear()
            for slot, (_part_idx, sl) in enumerate(stack.slots):
                for ckey, _blk, start, seg_n in sl.segments:
                    drop = np.ascontiguousarray(
                        drop_all[slot, start:start + seg_n])
                    ets = (np.ascontiguousarray(
                        ets_all[slot, start:start + seg_n])
                        if want_ets else None)
                    self._compact_cache[(params, ckey)] = (drop, ets)
            predicted_s = placement.predict_mesh_compact_seconds(
                stack.batch_bytes, mask_bytes)
            from pegasus_tpu.server.workload import DRIFT

            DRIFT.note("mesh_compact", predicted_s, measured_s)
            _COMPACT_MESH_DISPATCH.increment()
            self.compact_dispatches += 1
            self.compact_mask_serves += 1
            self._stash_pending(tres, pidx, lsm, params, want_ets)
            return self._compact_masks_from_cache(params, entries)

    # -- observability -----------------------------------------------------

    def status(self) -> Dict[str, Any]:
        with self._lock:
            waves = self.wave_dispatches + self.host_waves
            n_dev, platform = 0, None
            if self._pmesh is not None:
                devs = list(self._pmesh.mesh.devices.flat)
                n_dev = len(devs)
                platform = devs[0].platform if devs else None
            resident = sum(
                int(getattr(t.stack, col).nbytes)
                for t in self._tables.values() if t.stack is not None
                for col in ("keys", "key_len", "hashkey_len", "expire_ts",
                            "valid", "present", "hash_lo", "ones_extra"))
            return {
                "enabled": self.enabled,
                "disabled": self.disabled,
                "tables": len(self._tables),
                "devices": n_dev,
                "platform": platform,
                "resident_bytes": resident,
                "mesh_dispatch_count": int(_MESH_DISPATCH.value()),
                "mesh_fallback_count": int(_MESH_FALLBACK.value()),
                "dispatch_wedged": bool(_DISPATCH_WEDGED.value()),
                "wave_dispatches": self.wave_dispatches,
                "agg_dispatches": self.agg_dispatches,
                "host_waves": self.host_waves,
                "mesh_verdict_share": (round(self.wave_dispatches / waves, 3)
                                       if waves else 0.0),
                "slab_builds": self.slab_builds,
                "stack_builds": self.stack_builds,
                "compact_mesh_dispatch_count":
                    int(_COMPACT_MESH_DISPATCH.value()),
                "compact_mesh_fallback_count":
                    int(_COMPACT_MESH_FALLBACK.value()),
                "mesh_refresh_reuse_count": int(_REFRESH_REUSE.value()),
                "mesh_refresh_rebuild_count":
                    int(_REFRESH_REBUILD.value()),
                "compact_dispatches": self.compact_dispatches,
                "compact_mask_serves": self.compact_mask_serves,
                "refresh_reuses": self.refresh_reuses,
                "refresh_rebuilds": self.refresh_rebuilds,
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "watchdog": {
                    "deadline_s": self.watchdog._deadline(),
                    "consecutive_failures": self.watchdog.failures,
                    "trips": self.watchdog.trips,
                    "dispatches": self.watchdog.dispatches,
                    "last_error": (repr(self.watchdog.last_error)
                                   if self.watchdog.last_error else None),
                },
            }


MESH_SERVING = MeshServing()
