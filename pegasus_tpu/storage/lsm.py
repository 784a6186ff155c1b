"""LSMStore: memtable + L0 runs + ranged L1 runs, flush, merge, compaction.

Role parity: the RocksDB instance behind one replica
(src/server/pegasus_server_impl.cpp:1551 opens the DB; manual compaction
drives CompactRange, src/server/pegasus_manual_compact_service.h:48).

Shape: two levels. Flushes produce L0 SSTs (overlapping, newest wins).
L1 is a sequence of NON-OVERLAPPING, size-capped runs ordered by key —
compaction processes one output range at a time (merge memtable + L0
sub-range + that L1 run) and caps each output run, so a big table is
never rewritten as one monolithic file and each step's memory/latency
stays bounded (the leveled-compaction property manual CompactRange
relies on). Two paths produce that output: the block path (whole
columnar blocks; L0 tables that chain by key range flow through it
unchanged, one that overlaps is spliced into the blocks it meets) and
the per-record merge (`compact`), kept for what the first cannot read.
The filter seam drops tombstones, expired records
(device-evaluated TTL predicate), stale post-split keys, and applies
user-specified rules — the bottommost-level semantics of
src/server/key_ttl_compaction_filter.h:55,91.

Device pipelining: while the device evaluates one batch's filter, the
host builds the next (jax dispatch is async; materialization is delayed
one batch).

Durability: a manifest (temp+rename) names the live L1 runs; boot
removes obsolete compaction inputs/outputs from crash windows.

Scan merge order: memtable > newest L0 > ... > oldest L0 > L1 runs.
"""

from __future__ import annotations

import bisect
import heapq
import itertools
import os
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from pegasus_tpu.base.crc import crc64
from pegasus_tpu.storage.block_codec import (
    CODEC_NONE,
    EncodedBlock,
    ragged_scatter,
    codec_accepts,
)
from pegasus_tpu.storage.bloom import bloom_probe_enabled
from pegasus_tpu.storage.memtable import Memtable, TOMBSTONE
from pegasus_tpu.storage.sstable import (
    BLOCK_CAPACITY,
    Block,
    SSTable,
    SSTableWriter,
)
from pegasus_tpu.utils.tracing import mark

# (key, value|None, expire_ts) record triple
Record = Tuple[bytes, Optional[bytes], int]


# records per L1 output run before the compactor starts a new one:
# bounds every future range-compaction step (and its device batches)
L1_RUN_CAPACITY = 262_144

# blocks an L0 table needs before the block path lets its blocks flow
# unchanged (a chained table leaves at most one undersized block at its
# seam); a smaller table is spliced row-wise, so a train of small
# flushes packs into full blocks
CHAIN_MIN_BLOCKS = 4

# consecutive blocks one splice may decode and merge at a time (bounds
# what a load of the block path's read stage holds)
SPLICE_GROUP_BLOCKS = 8

# blocks a future of the block path's transform pool holds (a future
# round trip costs a condition-variable wait, which at one per block
# ate the whole overlap win): a rewrite of no more blocks than this
# has nothing to run in parallel
TRANSFORM_CHUNK_BLOCKS = 16

# process-unique store ids: cache owners (the node row cache) key
# entries by store identity + generation, and an int token can never
# alias a recycled object id after an engine swap
_STORE_UIDS = itertools.count(1)


def survivor_mask(drop: np.ndarray, flags) -> np.ndarray:
    """Rows a compaction keeps: the filter's drop mask plus the
    tombstone flags — THE survivor definition. bulk_compact_rewrite's
    transform applies it to build output blocks, and the mesh residency
    refresh (parallel/mesh_resident._survivor_slab) replays it to
    gather the post-compaction slab without re-reading those blocks;
    both sides calling one function is what keeps them in lockstep."""
    keep = ~np.asarray(drop, bool)
    if flags is not None:
        keep &= np.asarray(flags) == 0  # tombstones never stay
    return keep


def _seq_of(name: str) -> int:
    """The sequence number in an `l0-<seq>.sst` / `l1-<seq>.sst` name."""
    return int(os.path.basename(name).split("-")[1].split(".")[0])


class LSMStore:
    def __init__(self, data_dir: str, block_capacity: int = BLOCK_CAPACITY,
                 l0_compaction_trigger: int = 4,
                 l1_run_capacity: int = L1_RUN_CAPACITY) -> None:
        self.data_dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self._block_capacity = block_capacity
        self._l0_trigger = l0_compaction_trigger
        self._l1_run_capacity = l1_run_capacity
        self.memtable = Memtable()
        self.l0: List[SSTable] = []   # newest first
        self.l1_runs: List[SSTable] = []  # key-ordered, non-overlapping
        self._file_seq = 0
        # bumped whenever the visible run set changes (flush / ingest /
        # compaction publish): callers key derived caches (scan plans)
        # on it so they invalidate exactly when the block set does
        self.generation = 0
        self.store_uid = next(_STORE_UIDS)
        # last manual-compaction finish time (pegasus-epoch seconds),
        # persisted in the manifest INDEPENDENTLY of the run set so an
        # all-tombstone compaction (zero surviving runs) still records
        # completion — env-trigger staleness checks depend on it.
        # Recorded AT PUBLISH (with the manifest write), never at merge
        # start: a failed mid-run compaction must not make a
        # re-delivered env trigger look satisfied.
        self.compact_finish_time = 0
        # publish hook: called with the live L1 path set after every
        # compaction publish, so cache owners (PartitionServer) evict
        # entries keyed by runs that just left the manifest instead of
        # pinning dead fds/mmaps/HBM until GC
        self.on_publish: Optional[Callable[[set], None]] = None
        self._load_existing()

    # ---- files --------------------------------------------------------

    def _manifest_path(self) -> str:
        return os.path.join(self.data_dir, "MANIFEST.json")

    def _write_manifest(self, l1_names: List[str],
                        live_l0: Sequence[SSTable] = ()) -> None:
        """Atomically record the live L1 run set + the seq horizon. Any
        l1-* file not listed, and any l0-* file older than the horizon,
        is a crash leftover boot removes. `live_l0`: the L0 tables the
        publish leaves (flushed while a snapshot-mode compaction ran,
        so numbered below its later outputs): the horizon stays at the
        oldest of them."""
        import json as _json
        import tempfile as _tempfile

        horizon = min([self._file_seq] + [_seq_of(t.path)
                                           for t in live_l0])
        fd, tmp = _tempfile.mkstemp(dir=self.data_dir)
        with os.fdopen(fd, "w") as f:
            _json.dump({"seq": horizon, "l1": l1_names,
                        "mcft": self.compact_finish_time}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._manifest_path())

    def _load_existing(self) -> None:
        import json as _json

        manifest = None
        if os.path.exists(self._manifest_path()):
            with open(self._manifest_path()) as f:
                manifest = _json.load(f)
            # the seq horizon must survive even when every .sst is gone
            # (an all-tombstone compaction): fresh flushes below the
            # horizon would be deleted as consumed inputs at next boot
            self._file_seq = max(self._file_seq, manifest["seq"])
            self.compact_finish_time = manifest.get("mcft", 0)
        l0_files = []
        l1_files = []
        for name in os.listdir(self.data_dir):
            if name.endswith(".sst"):
                seq = _seq_of(name)
                self._file_seq = max(self._file_seq, seq + 1)
                if name.startswith("l0-"):
                    l0_files.append((seq, name))
                elif name.startswith("l1-"):
                    l1_files.append((seq, name))
            elif name.endswith(".sst.tmp"):
                # abandoned writer from a crash mid-build
                os.remove(os.path.join(self.data_dir, name))
        if manifest is None:
            # legacy layout (pre-manifest): newest l1 file wins, older
            # files are obsolete compaction inputs
            l1_live = []
            if l1_files:
                newest = max(l1_files)
                l1_live = [newest[1]]
                horizon = newest[0]
            else:
                horizon = -1
            stale_l1 = [n for _s, n in l1_files if n not in l1_live]
        else:
            l1_live = [n for n in manifest["l1"]
                       if os.path.exists(os.path.join(self.data_dir, n))]
            horizon = manifest["seq"]
            # unlisted l1 files: incomplete outputs from a crashed
            # compaction (or inputs whose removal did not finish)
            stale_l1 = [n for _s, n in l1_files if n not in l1_live]
        for name in stale_l1:
            os.remove(os.path.join(self.data_dir, name))
        # l0 files older than the horizon are consumed compaction inputs
        for seq, name in list(l0_files):
            if seq < horizon:
                os.remove(os.path.join(self.data_dir, name))
                l0_files.remove((seq, name))
        for seq, name in sorted(l0_files, reverse=True):
            self.l0.append(SSTable(os.path.join(self.data_dir, name)))
        runs = [SSTable(os.path.join(self.data_dir, name))
                for name in l1_live]
        runs.sort(key=lambda t: t.first_key or b"")
        self.l1_runs = runs

    def _next_path(self, level: str) -> str:
        path = os.path.join(self.data_dir, f"{level}-{self._file_seq}.sst")
        self._file_seq += 1
        return path

    def close(self) -> None:
        for t in self.l0:
            t.close()
        for t in self.l1_runs:
            t.close()

    # ---- writes -------------------------------------------------------

    def put(self, key: bytes, value: bytes, expire_ts: int = 0) -> None:
        self.memtable.put(key, value, expire_ts)

    def delete(self, key: bytes) -> None:
        self.memtable.delete(key)

    def flush(self, meta: Optional[dict] = None) -> Optional[SSTable]:
        """Memtable -> new L0 SST carrying `meta` (decree watermark etc.)."""
        if len(self.memtable) == 0:
            return None
        writer = SSTableWriter(self._next_path("l0"),
                               block_capacity=self._block_capacity, meta=meta)
        for key, value, ets in self.memtable.items_sorted():
            if value is TOMBSTONE:
                writer.add(key, b"", 0, tombstone=True)
            else:
                writer.add(key, value, ets)
        writer.finish()
        table = SSTable(writer.path)
        self.l0.insert(0, table)
        self.memtable = Memtable()
        self.generation += 1
        return table

    def ingest(self, build_sst, meta: Optional[dict] = None):
        """Adopt an externally-built run as the newest L0 SST. `build_sst`
        is a callback (dest_path, meta) -> None writing the file; keeping
        the naming + newest-first invariants inside the store."""
        dest = self._next_path("l0")
        build_sst(dest, meta)
        table = SSTable(dest)
        self.l0.insert(0, table)
        self.generation += 1
        return table

    def should_compact(self) -> bool:
        return len(self.l0) >= self._l0_trigger

    # ---- reads --------------------------------------------------------

    def get(self, key: bytes) -> Optional[Tuple[bytes, int]]:
        """Visible (value, expire_ts) or None. TTL filtering is the caller's
        job (reference checks expiry in the handlers, not the engine).

        L0 tables short-circuit on their first/last-key fences (an
        out-of-range table costs two compares, not a block lookup) and
        then on their sidecar structures — the key is hashed ONCE (the
        crc64 every sidecar shares) when any candidate table carries a
        bloom or a perfect-hash index, and the same hash feeds every
        structure this get consults. Indexed runs answer through
        SSTable.get's scalar phash probe (the batched kernel's hash,
        solo form): a miss costs one slot gather with zero block
        touches, a hit goes straight to its (block, slot) row — the
        non-batched client path never silently regresses to the
        bisect. Steady-state stores (empty L0, filterless runs) skip
        the hash entirely."""
        from pegasus_tpu.utils.perf_context import current as _perf_current

        pc = _perf_current()  # solo-path cost vector (None = untracked)
        hit = self.memtable.get(key)
        if hit is not None:
            if pc is not None:
                pc.overlay_hits += 1
            value, ets = hit
            return None if value is TOMBSTONE else (value, ets)
        from pegasus_tpu.storage.phash import phash_probe_enabled

        bloom_on = bloom_probe_enabled()
        phash_on = phash_probe_enabled()
        if pc is not None:
            # same meaning as the batched planner's field: the sidecar
            # candidacy matrix width this key was answered against
            pc.runs_considered += len(self.l0) + len(self.l1_runs)
        key_hash: Optional[int] = None  # computed at most once

        def lookup(table):
            """One table's sidecar-gated probe, matching the batched
            planner's structure selection exactly: an indexed table
            (phash probing on) answers through the perfect hash ALONE
            — consulting its bloom too would double the per-pair work
            — and each kill switch disables ONLY its own structure
            (a bloom_probe=False escape hatch must not keep pruning
            through a suspect filter just because phash hashing ran)."""
            nonlocal key_hash
            use_phash = phash_on and table.phash is not None
            use_bloom = bloom_on and not use_phash \
                and table.bloom is not None
            if (use_phash or use_bloom) and key_hash is None:
                key_hash = crc64(key)
            if use_bloom and not table.may_contain(key, key_hash):
                return None  # definitively absent from this table
            return table.get(key, key_hash=key_hash
                             if use_phash else None)

        for table in self.l0:
            fk = table.first_key
            if fk is None or key < fk or key > table.last_key:
                continue
            hit = lookup(table)
            if hit is not None:
                value, ets = hit
                return None if value is None else (value, ets)
        run = self._run_for(key)
        if run is not None:
            hit = lookup(run)
            if hit is not None:
                value, ets = hit
                return None if value is None else (value, ets)
        return None

    def _run_for(self, key: bytes) -> Optional[SSTable]:
        """The (single) L1 run whose range may hold `key` — runs are
        non-overlapping and key-ordered. Operates on ONE snapshot of
        the run list: a concurrent compaction publish swaps
        `self.l1_runs` wholesale (env-triggered manual compaction runs
        off the node lock), and re-reading the attribute mid-search
        could index a shorter list."""
        runs = self.l1_runs
        lo, hi = 0, len(runs)
        while lo < hi:
            mid = (lo + hi) // 2
            if (runs[mid].last_key or b"") < key:
                lo = mid + 1
            else:
                hi = mid
        if lo < len(runs) and ((runs[lo].first_key or b"") <= key):
            return runs[lo]
        return None

    def iterate(self, start: bytes = b"", stop: Optional[bytes] = None,
                reverse: bool = False) -> Iterator[Record]:
        """Merged visible records (tombstones resolved, TTL not applied)."""
        sources: List[Iterator[Record]] = [
            self.memtable.iterate(start, stop, reverse)]
        for table in self.l0:
            sources.append(table.iterate(start, stop, reverse))
        if self.l1_runs:
            # non-overlapping ordered runs chain into ONE merged source,
            # keeping the merge heap as small as the old single-L1 shape
            runs = (self.l1_runs if not reverse
                    else list(reversed(self.l1_runs)))
            sources.append(_chain_runs(runs, start, stop, reverse))
        return _merge(sources, reverse)

    def sorted_runs(self) -> Optional[List[SSTable]]:
        """The ordered L1 runs when the store is fully compacted and there
        is no overlay — the device fast path qualifier: scans stream each
        run's blocks columnar to the predicate kernels, in key order."""
        if len(self.memtable) == 0 and not self.l0 and self.l1_runs:
            return self.l1_runs
        return None

    # ---- compaction ---------------------------------------------------

    def compact(
        self,
        record_filter: Optional[Callable[..., np.ndarray]] = None,
        meta: Optional[dict] = None,
        patch_headers: bool = False,
        publish_lock=None,
    ) -> int:
        """Full compaction as a sequence of BOUNDED range steps, record
        by record. Returns the key + value bytes of the rows it merged
        (its input).

        Which stores take it: since the block path reads a snapshot
        with L0 tables (bulk_compact_snapshot), only what that path
        cannot read — a live memtable merged under the caller's lock
        (legacy mode), v1 files without the hash_lo column, and a
        rules callable that has no parsed `operations` (the engine's
        choice). It stays the reference the block path is held to
        (tests/test_block_compact_overlay.py).

        One merged pass over the overlay + L1 runs; output runs are
        size-capped (`l1_run_capacity`), so no monolithic rewrite and a
        predictable working set per step — the manual CompactRange shape.

        `publish_lock=None` (legacy mode): the caller holds the writer
        lock for the whole merge; memtable + live L0 + L1 merge and the
        overlay resets at publish. `publish_lock` set (snapshot mode —
        the narrow critical section): the caller froze the memtable
        with a flush, the merge runs over the IMMUTABLE L0/L1 snapshot
        with writes flowing, and the lock is taken only for the publish
        cut-over — post-snapshot writes (fresh memtable, newer L0
        flushes) survive untouched and keep shadowing the merged base.

        `record_filter(keys: List[bytes], expire_ts: List[int]) ->
        (drop_mask, new_expire)` (each at least len(keys) long; the
        first len(keys) are read) is the device TTL/compaction-rule seam
        (engine.StorageEngine wires it); evaluation is DOUBLE-BUFFERED:
        while the device filters batch N, the host gathers batch N+1
        (jax dispatch is asynchronous — only materialization blocks).
        Tombstones always drop (bottommost).

        Stage points (utils/tracing, layer `compact`): compact_merge
        (the merged iteration: block reads, decode, heap merge),
        compact_filter_submit, compact_filter_drain, compact_write,
        compact_publish.
        """
        runs_snap = list(self.l1_runs)
        if publish_lock is not None:
            l0_snap = list(self.l0)
            sources: List[Iterator[Record]] = [
                t.iterate() for t in l0_snap]
            if runs_snap:
                sources.append(_chain_runs(runs_snap, b"", None, False))
            merged = _merge(sources)
        else:
            l0_snap = None
            merged = self.iterate()
        new_runs: List[SSTable] = []
        writer: Optional[SSTableWriter] = None
        written_in_run = 0
        # write-stage overlap, same shape as the bulk path: block
        # writes stream on the writer's async-IO thread while the
        # merge/filter keeps producing, and filled runs finish on the
        # shared _FinishPool (joined before publish)
        finish_pool = _FinishPool()

        def open_writer() -> SSTableWriter:
            return SSTableWriter(self._next_path("l1"),
                                 block_capacity=self._block_capacity,
                                 meta=meta, async_io=True)

        def write_records(keys, vals, ets_orig, drop, new_ets) -> None:
            nonlocal writer, written_in_run
            from pegasus_tpu.base.value_schema import update_expire_ts

            for i, k in enumerate(keys):
                if drop is not None and drop[i]:
                    continue
                if writer is None:
                    writer = open_writer()
                ne = int(new_ets[i])
                v = vals[i]
                if patch_headers and ne != ets_orig[i]:
                    # a TTL rewrite must reach the encoded value header
                    # too, or readers of the raw header see the old TTL
                    # (the bulk path patches it the same way)
                    v = update_expire_ts(1, v, ne)
                writer.add(k, v, ne)
                written_in_run += 1
                if written_in_run >= self._l1_run_capacity:
                    finish_pool.submit(writer)
                    writer = None
                    written_in_run = 0

        # pipeline state: the batch whose filter is in flight on device
        pending: Optional[tuple] = None

        def submit(keys, vals, ets, nbytes):
            mark("compact_merge")
            # the merge path's input pacing: one governor charge per
            # filter batch (the bulk path pays per block) — background
            # bandwidth answers foreground pressure on BOTH compaction
            # shapes
            GOVERNOR.acquire(nbytes)
            if record_filter is None:
                return (keys, vals, ets, None, ets)
            drop, new_ets = record_filter(keys, ets)
            mark("compact_filter_submit")
            # jax returns asynchronously-evaluated arrays; conversion to
            # numpy in drain() is the synchronization point
            return (keys, vals, ets, drop, new_ets)

        def drain(entry) -> None:
            keys, vals, ets_orig, drop, new_ets = entry
            if drop is not None:
                # materialize = the device synchronization point
                drop = np.asarray(drop)
                new_ets = np.asarray(new_ets)
                mark("compact_filter_drain")
            write_records(keys, vals, ets_orig, drop, new_ets)
            mark("compact_write")

        from pegasus_tpu.storage.compact_governor import GOVERNOR

        batch_keys: List[bytes] = []
        batch_vals: List[bytes] = []
        batch_ets: List[int] = []
        batch_bytes = bytes_in = 0
        filter_batch = self.filter_batch_rows
        ok = False
        try:
            for key, value, ets in merged:
                if value is None:  # tombstone: bottommost level -> drop
                    continue
                batch_keys.append(key)
                batch_vals.append(value)
                batch_ets.append(ets)
                batch_bytes += len(key) + len(value)
                if len(batch_keys) >= filter_batch:
                    entry = submit(batch_keys, batch_vals, batch_ets,
                                   batch_bytes)
                    if pending is not None:
                        drain(pending)
                    pending = entry
                    batch_keys, batch_vals, batch_ets = [], [], []
                    bytes_in += batch_bytes
                    batch_bytes = 0
            if batch_keys:
                bytes_in += batch_bytes
                entry = submit(batch_keys, batch_vals, batch_ets,
                               batch_bytes)
                if pending is not None:
                    drain(pending)
                pending = entry
            if pending is not None:
                drain(pending)
            if writer is not None:
                finish_pool.submit(writer)
                writer = None
            new_runs = finish_pool.results()
            ok = True
        finally:
            finish_pool.shutdown(ok, open_writer=writer)
        mark("compact_write")

        self._publish_l1(new_runs, consumed_l0=l0_snap,
                         old_runs=runs_snap, publish_lock=publish_lock,
                         mcft=(meta or {}).get(
                             "manual_compact_finish_time", 0))
        mark("compact_publish")
        return bytes_in

    def _publish_l1(self, new_runs: List[SSTable],
                    consumed_l0: Optional[List[SSTable]] = None,
                    old_runs: Optional[List[SSTable]] = None,
                    publish_lock=None, mcft: int = 0) -> None:
        """Swap in a freshly-compacted L1 under `publish_lock` (None =
        the caller already excludes writers): manifest first (atomic),
        then remove inputs — boot cleans up either crash window. Both
        compaction paths share this so the crash-safety ordering lives
        in exactly one place.

        consumed_l0=None: the merge consumed the LIVE overlay (caller
        held the writer lock throughout) — memtable and L0 reset
        wholesale. consumed_l0=[...]: snapshot mode — exactly those L0
        tables leave; the memtable and any newer L0 flushes
        (post-snapshot writes) survive and keep shadowing the new base.
        old_runs: the L1 snapshot the merge consumed, revalidated
        against the live list under the lock — compactions are
        serialized (engine.compact_lock), so a mismatch means a torn
        merge whose output must not publish.
        mcft: manual-compaction finish time, recorded HERE (with the
        manifest) so a failed mid-run compaction never satisfies a
        re-delivered env trigger."""
        import contextlib

        lock = publish_lock if publish_lock is not None \
            else contextlib.nullcontext()
        old_l0: List[SSTable] = []
        with lock:
            if old_runs is not None and \
                    [id(t) for t in self.l1_runs] != \
                    [id(t) for t in old_runs]:
                for t in new_runs:
                    try:
                        t.close()
                        os.remove(t.path)
                    except OSError:
                        pass
                raise RuntimeError(
                    "concurrent L1 publish detected; compaction output "
                    "discarded")
            if mcft:
                self.compact_finish_time = mcft
            live_l0: List[SSTable] = []
            if consumed_l0 is not None:
                consumed = {id(t) for t in consumed_l0}
                live_l0 = [t for t in self.l0 if id(t) not in consumed]
            self._write_manifest([os.path.basename(t.path)
                                  for t in new_runs], live_l0)
            superseded = self.l1_runs
            self.l1_runs = new_runs
            self.generation += 1
            if consumed_l0 is None:
                old_l0, self.l0 = self.l0, []
                self.memtable = Memtable()
            else:
                old_l0, self.l0 = list(consumed_l0), live_l0
            # Input files are unlinked now (crash-safe: the manifest no
            # longer names them) but their HANDLES are released by GC,
            # not closed here: a reader admitted before the swap may
            # still be serving from these runs (the env-triggered
            # compaction thread publishes concurrently with serving),
            # and on encrypted stores a hard close() would yank the
            # CipherFile out from under its next read_block. POSIX
            # keeps unlinked-but-open files readable; the refcount
            # drops to zero as soon as the last in-flight scan state /
            # superseded plan cache lets go. Unlinking INSIDE the lock
            # keeps checkpoint's file-copy walk (which takes the same
            # lock) from racing the removals.
            for t in old_l0 + superseded:
                os.remove(t.path)
        hook = self.on_publish
        if hook is not None:
            # cache owners evict entries keyed by the dead runs
            hook({t.path for t in new_runs})

    # ---- bulk block-level compaction (the GB/s path) -------------------

    def bulk_compact_snapshot(self, frozen: bool = False
                              ) -> Optional["BulkSnapshot"]:
        """What a block-path compaction would read and, at publish,
        replace — or None where only `compact` (the per-record merge)
        can read the store: a live memtable (`frozen` False and rows in
        it: the legacy lock-held mode merges it), a v1 file without the
        hash_lo column, nothing on disk. `frozen`: the caller froze the
        memtable with a flush (snapshot mode), so what decides is the
        file snapshot alone — a write that lands after the freeze stays
        in the overlay the publish leaves behind.

        The snapshot's tables are split by their own key ranges. The
        CHAIN: the L1 runs, and every L0 table of CHAIN_MIN_BLOCKS
        blocks or more whose [first_key, last_key] is disjoint from
        every older table's — concatenated in key order, their blocks
        flow through the path unchanged. The OVERLAY: every other L0
        table (it overlaps an older one, or is small), whose rows are
        spliced into the chain's blocks. An overlay table is therefore
        newer than every chain table its range meets, so an overlay
        row wins against a chain row of the same key."""
        if not frozen and len(self.memtable):
            return None
        l0, runs = list(self.l0), list(self.l1_runs)
        tables = [t for t in l0 + runs if t.blocks]
        if not tables or not all(getattr(t, "_has_hash_lo", False)
                                 for t in tables):
            return None
        chain = [t for t in runs if t.blocks]
        overlay: List[SSTable] = []
        for t in reversed(l0):  # oldest first
            if not t.blocks:
                continue
            if len(t.blocks) >= CHAIN_MIN_BLOCKS and all(
                    t.last_key < o.first_key or o.last_key < t.first_key
                    for o in chain + overlay):
                chain.append(t)
            else:
                overlay.append(t)
        chain.sort(key=lambda t: t.first_key)
        overlay.reverse()  # newest first: the merge order
        return BulkSnapshot(l0, runs, chain, overlay)

    def bulk_compact_eligible(self, frozen: bool = False) -> bool:
        """The block path can compact the store as it stands: see
        bulk_compact_snapshot. True for pure L1 (the manual-compact
        steady state), for an L0 left by a flush (a table's first
        compaction, a live table's every one) whether its tables chain
        by key range or overlap, and for both together; False over a
        live memtable and over v1 files."""
        return self.bulk_compact_snapshot(frozen) is not None

    @property
    def filter_batch_rows(self) -> int:
        """Rows a filter batch of compact() holds: much larger than the
        write-block size, because a high-RTT device pays per dispatch,
        so the compactor amortizes 16 blocks of records into each
        filter evaluation."""
        return self._block_capacity * 16

    def bulk_compact_entries(self, snap: Optional["BulkSnapshot"] = None):
        """The snapshot (default: the L1 runs alone) as ONE key-ordered
        sequence of entries: `(table, idx, BlockMeta)` for a block that
        flows unchanged, a `Splice` where overlay rows fall into a
        chain block's span `[first_key, next block's first_key)` (the
        first span open below, the last above): that block — with an
        undersized neighbour, and the next blocks that take overlay
        rows too, up to SPLICE_GROUP_BLOCKS — is decoded and merged
        with those rows."""
        chain = self.l1_runs if snap is None else snap.chain
        blocks = [(t, i, bm) for t in chain
                  for i, bm in enumerate(t.blocks)]
        if snap is None or not snap.overlay:
            return blocks
        ov = _Overlay(snap.overlay)
        cap = self._block_capacity
        m = len(blocks)
        # overlay rows [cut[j], cut[j + 1]) fall into block j's span
        cut = [0] + [bisect.bisect_left(ov.keys, b[2].first_key)
                     for b in blocks[1:]] + [len(ov.keys)]
        if m == 0:
            return [Splice([], ov, 0, len(ov.keys), cap)]
        out: list = []
        j = 0
        while j < m:
            if cut[j] == cut[j + 1]:
                out.append(blocks[j])
                j += 1
                continue
            g0 = j
            if out and not isinstance(out[-1], Splice) \
                    and out[-1][2].count * 2 < cap:
                out.pop()   # an undersized block before: packed too
                g0 = j - 1
            g1 = j + 1
            while g1 < m and g1 - g0 < SPLICE_GROUP_BLOCKS and (
                    cut[g1] < cut[g1 + 1]
                    or blocks[g1][2].count * 2 < cap):
                g1 += 1
            out.append(Splice(blocks[g0:g1], ov, cut[g0], cut[g1], cap))
            j = g1
        return out

    def bulk_compact_rewrite(self, per_block, meta,
                             ttl_may_change: bool,
                             patch_headers: bool = False,
                             publish_lock=None,
                             transform_workers: int = 0,
                             snap: Optional["BulkSnapshot"] = None) -> None:
        """Rewrite the L1 level from precomputed per-block filter results.

        `per_block`: [(source, idx, blk, drop, new_ets)] in key order
        (drop / new_ets sized to the block's real count): the blocks of
        `snap`'s entries — a chain table's own block as read, or a
        block a Splice merged from a chain block and the overlay rows
        in its span (decoded columns) — whatever shape the snapshot
        has: pure L1, one L0 or several chained by key range, an
        overlay over L1. Untouched blocks are
        re-serialized straight from their already-decoded columns (no
        gather, no crc recompute, no second disk read); touched blocks
        are rebuilt with numpy gathers — the value heap survivor bytes
        via one boolean-repeat mask, expire_ts headers patched with
        scatter stores — so no per-record Python runs at any drop
        rate. A chained L0 block's tombstones drop with the filter's
        rows (survivor_mask). The rewrite never touches the memtable,
        and of the L0 only the snapshot's tables, so with
        `publish_lock` the whole disk pass runs with writes flowing and
        the lock is taken only for the publish cut-over, which replaces
        exactly `snap.l0` and `snap.runs` (default: no L0 table and the
        live L1 runs).

        `transform_workers` > 0 (the engine passes a pool size for a
        snapshot of more than one TRANSFORM_CHUNK_BLOCKS chunk, 0 for
        a smaller one): the per-block transform — subset kernel, heap
        inflate/re-deflate, numpy gathers — runs on an ordered worker
        pool while this thread only appends results, so the GIL-free
        kernel work of block N+1..N+k overlaps block N's writer append.
        The transform is ONE function executed identically inline or
        pooled, so output bytes cannot depend on the mode."""
        import concurrent.futures as _cf

        from pegasus_tpu.storage.bloom import bloom_build_bits
        from pegasus_tpu.storage.sstable import (
            SSTable,
            SSTableWriter,
            block_codec,
        )

        runs_snap = list(self.l1_runs) if snap is None else snap.runs
        l0_snap = [] if snap is None else snap.l0
        # filled runs finish on the shared _FinishPool (fsync releases
        # the GIL) while this thread keeps appending; joined before
        # the manifest publish
        finish_pool = _FinishPool()

        from pegasus_tpu import native

        cblock_subset = native.cblock_subset_fn()
        writer: Optional[SSTableWriter] = None
        written_in_run = 0
        ok = False

        def roll_writer() -> SSTableWriter:
            nonlocal writer, written_in_run
            if writer is not None and written_in_run >= self._l1_run_capacity:
                finish_pool.submit(writer)
                writer = None
                written_in_run = 0
            if writer is None:
                writer = SSTableWriter(self._next_path("l1"),
                                       block_capacity=self._block_capacity,
                                       meta=meta, async_io=True)
            return writer

        def copy_block(blk) -> None:
            nonlocal written_in_run
            w = roll_writer()
            w.add_block_columnar(blk.keys, blk.key_len, blk.expire_ts,
                                 blk.hash_lo, blk.flags, blk.value_offs,
                                 blk.value_heap)
            written_in_run += blk.count

        # writer-independent state the TRANSFORM latches once, so the
        # same decisions compute on any thread: every writer this
        # rewrite rolls latches the identical flag values at creation.
        # `sidecar_now` (bloom OR phash) decides whether the subset
        # kernel must emit per-row hashes — either sidecar needs them
        codec_now = block_codec()
        from pegasus_tpu.storage.phash import phash_build_enabled

        sidecar_now = bloom_build_bits() > 0 or phash_build_enabled()

        def transform(item):
            """Stateless per-block transform -> (kind, payload). The
            expensive work lives here — subset kernel (GIL-free), heap
            inflate, numpy gathers — and runs identically inline
            (`transform_workers` 0) or on the ordered worker pool."""
            _run, _idx, blk, drop, new_ets = item
            # a chained L0 block may hold tombstones: they never stay
            dropped = bool(drop.any()) or bool(np.any(blk.flags))
            encoded = isinstance(blk, EncodedBlock)
            ets_changed = ttl_may_change and \
                not np.array_equal(new_ets, blk.expire_ts)
            if not dropped and not ets_changed:
                if encoded:
                    if codec_now != CODEC_NONE:
                        # untouched compressed block: the on-disk
                        # bytes copy VERBATIM — no heap inflate, no
                        # re-encode, no re-deflate
                        return "verbatim", blk
                    blk = blk.decode()  # codec turned off mid-store
                return "copy", blk
            n = blk.count
            if encoded:
                # survivor check first: a fully-dropped block must
                # never roll a writer (an empty L1 run would publish
                # when every block drops every row)
                keep = survivor_mask(drop, blk.flags)
                if not keep.any():
                    return "skip", None
                if codec_now != CODEC_NONE and cblock_subset is not None \
                        and codec_accepts(codec_now, blk.version):
                    # rows drop (or TTLs rewrite): subset the block
                    # in the ENCODED domain — one GIL-free native
                    # pass (dict remap + ragged gathers + heap
                    # inflate/re-deflate) instead of the Python
                    # decode -> gather -> re-encode round trip that
                    # serialized the compaction thread pool
                    res = cblock_subset(
                        blk.raw, blk.raw_heap_len, blk.key_width,
                        keep, new_ets if ets_changed else None,
                        ets_changed and patch_headers,
                        want_hashes=sidecar_now)
                    if res is not None:
                        return "raw", (res, blk.key_width)
                # native kernel unavailable (or codec flipped off
                # mid-store): materialize once and take the
                # vectorized gather path below
                blk = blk.decode()
            keep = survivor_mask(drop, blk.flags)
            kept = np.flatnonzero(keep)
            if kept.size == 0:
                return "skip", None
            vo = blk.value_offs.astype(np.int64)
            lens = vo[1:] - vo[:-1]
            heap_arr = blk.value_heap
            if not isinstance(heap_arr, np.ndarray):
                heap_arr = np.frombuffer(heap_arr, dtype=np.uint8)
            ets_col = new_ets if ets_changed else blk.expire_ts
            if ets_changed and patch_headers:
                # patch the big-endian u32 expire_ts value header in
                # place (vectorized scatter, value_schema.h: header
                # starts every encoded value)
                heap_arr = heap_arr.copy()
                chg = np.flatnonzero((new_ets != blk.expire_ts)
                                     & keep)
                if chg.size:
                    pos = vo[chg]
                    vals = new_ets[chg].astype(np.uint32)
                    heap_arr[pos] = (vals >> 24).astype(np.uint8)
                    heap_arr[pos + 1] = \
                        ((vals >> 16) & 0xFF).astype(np.uint8)
                    heap_arr[pos + 2] = \
                        ((vals >> 8) & 0xFF).astype(np.uint8)
                    heap_arr[pos + 3] = (vals & 0xFF).astype(np.uint8)
            if kept.size == n:
                new_heap = heap_arr
                new_offs = blk.value_offs
                keys2d, klen = blk.keys, blk.key_len
                hlo, flg = blk.hash_lo, blk.flags
                ets_out = ets_col
            else:
                keep_bytes = np.repeat(keep, lens)
                new_heap = heap_arr[keep_bytes]
                kept_lens = lens[kept]
                new_offs = np.zeros(kept.size + 1, dtype=np.uint32)
                new_offs[1:] = np.cumsum(kept_lens)
                keys2d = blk.keys[kept]
                klen = blk.key_len[kept]
                ets_out = np.asarray(ets_col)[kept]
                hlo = blk.hash_lo[kept]
                flg = blk.flags[kept]
            return "columnar", (keys2d, klen, ets_out, hlo, flg,
                                new_offs, new_heap, int(kept.size))

        def consume(kind, payload) -> None:
            """Writer appends, strictly in block order on THIS thread
            (the writers are single-threaded; ordering is the format
            contract)."""
            nonlocal written_in_run
            if kind == "skip":
                return
            if kind == "verbatim":
                w = roll_writer()
                # add_block_encoded transcodes a version the writer's
                # codec cannot contain (flag moved mid-store)
                w.add_block_encoded(payload)
                written_in_run += payload.count
            elif kind == "copy":
                copy_block(payload)
            elif kind == "raw":
                (buf, hashes, m, vsub, fk, lk), kw = payload
                w = roll_writer()
                w.add_block_encoded_raw(buf, m, kw, vsub, fk, lk,
                                        hashes)
                written_in_run += m
            else:
                w = roll_writer()
                w.add_block_columnar(*payload[:7])
                written_in_run += payload[7]

        try:
            if transform_workers > 0:
                # ordered lookahead: transforms run CHUNKED on the
                # pool (one future per TRANSFORM_CHUNK_BLOCKS) while
                # results append in order — the write stage's own
                # intra-stage parallelism
                from collections import deque

                CHUNK = TRANSFORM_CHUNK_BLOCKS
                depth = 2 * transform_workers + 2

                def transform_chunk(chunk):
                    return [transform(x) for x in chunk]

                tpool = _cf.ThreadPoolExecutor(
                    max_workers=transform_workers)
                try:
                    pend: deque = deque()
                    chunk: list = []
                    for item in per_block:
                        chunk.append(item)
                        if len(chunk) >= CHUNK:
                            pend.append(tpool.submit(transform_chunk,
                                                     chunk))
                            chunk = []
                            if len(pend) >= depth:
                                for r in pend.popleft().result():
                                    consume(*r)
                    if chunk:
                        pend.append(tpool.submit(transform_chunk,
                                                 chunk))
                    while pend:
                        for r in pend.popleft().result():
                            consume(*r)
                finally:
                    tpool.shutdown(wait=True)
            else:
                for item in per_block:
                    consume(*transform(item))
            if writer is not None:
                finish_pool.submit(writer)
                writer = None
            new_runs = finish_pool.results()
            ok = True
        finally:
            finish_pool.shutdown(ok, open_writer=writer)
        mark("compact_write")
        # exactly the snapshot's tables leave; writes that arrived
        # since stay in the live overlay (memtable, newer L0 flushes)
        self._publish_l1(new_runs, consumed_l0=l0_snap, old_runs=runs_snap,
                         publish_lock=publish_lock,
                         mcft=(meta or {}).get(
                             "manual_compact_finish_time", 0))
        mark("compact_publish")


class BulkSnapshot:
    """The tables one block-path compaction reads (LSMStore.
    bulk_compact_snapshot): `l0` (newest first) and `runs` are what
    its publish replaces; `chain` (key order) and `overlay` (newest
    first) are the same tables by how they are read."""

    __slots__ = ("l0", "runs", "chain", "overlay")

    def __init__(self, l0: List[SSTable], runs: List[SSTable],
                 chain: List[SSTable], overlay: List[SSTable]) -> None:
        self.l0, self.runs = l0, runs
        self.chain, self.overlay = chain, overlay


class _Overlay:
    """The overlay tables' rows as one key-ordered sequence, the newest
    table's row for a key that several hold, tombstones kept (they
    shadow a chain row, then drop). A row is (block, row in it): the
    columns stay in the decoded source blocks, which a Splice gathers
    from; `keys` is the one per-row Python list, for the bisects."""

    def __init__(self, tables: List[SSTable]) -> None:  # newest first
        self.blocks: List[Block] = []
        keys: List[bytes] = []
        blk_id, row, tomb, row_bytes = [], [], [], []
        for t in tables:
            for i in range(len(t.blocks)):
                blk = t.read_block(i)
                keys.extend(blk.key_list())
                blk_id.append(np.full(blk.count, len(self.blocks),
                                      dtype=np.int64))
                row.append(np.arange(blk.count, dtype=np.int64))
                tomb.append(np.asarray(blk.flags) != 0)
                row_bytes.append(blk.key_len.astype(np.int64) + np.diff(
                    blk.value_offs.astype(np.int64)))
                self.blocks.append(blk)
        blk_id, row = np.concatenate(blk_id), np.concatenate(row)
        tomb, row_bytes = np.concatenate(tomb), np.concatenate(row_bytes)
        if len(tables) > 1:
            # stable, and the newest table's rows come first: of equal
            # keys the first is the winner
            order = sorted(range(len(keys)), key=keys.__getitem__)
            order = [o for n, o in enumerate(order)
                     if n == 0 or keys[o] != keys[order[n - 1]]]
            keys = [keys[o] for o in order]
            blk_id, row = blk_id[order], row[order]
            tomb, row_bytes = tomb[order], row_bytes[order]
        self.keys = keys
        self.blk_id, self.row = blk_id, row
        self.tomb, self.row_bytes = tomb, row_bytes
        self.codec = getattr(tables[0], "codec", None)


class Splice:
    """One entry of the block path's sequence that is merged, not
    copied: consecutive chain blocks `base` ([(table, idx, BlockMeta)],
    none where the snapshot has no chain) and the overlay rows
    [lo, hi) that fall into their spans."""

    __slots__ = ("base", "overlay", "lo", "hi", "capacity")

    def __init__(self, base, overlay: _Overlay, lo: int, hi: int,
                 capacity: int) -> None:
        self.base, self.overlay = base, overlay
        self.lo, self.hi, self.capacity = lo, hi, capacity

    @property
    def codec(self):
        """The codec of the tables it reads: where the engine's filter
        stage evaluates such a table's blocks, it evaluates these."""
        return (self.base[0][0].codec if self.base
                else self.overlay.codec)

    @property
    def base_bytes(self) -> int:
        """On-disk bytes of the chain blocks it reads."""
        return sum(bm.size for _t, _i, bm in self.base)

    @property
    def overlay_bytes(self) -> int:
        """Key and value bytes of its overlay rows."""
        return int(self.overlay.row_bytes[self.lo:self.hi].sum())

    def blocks(self) -> List[Block]:
        """The merged rows as columnar blocks of at most `capacity`
        rows, every one full but the last: `_merge`'s rules (the
        overlay's row wins, a tombstone shadows then drops), every
        column gathered from the source blocks' own (hash_lo included:
        no crc64 is computed again)."""
        ov, lo, hi = self.overlay, self.lo, self.hi
        sources = [t.read_block(i) for t, i, _bm in self.base]
        n_base = len(sources)
        sources += ov.blocks
        base_keys: List[bytes] = []
        for blk in sources[:n_base]:
            base_keys.extend(blk.key_list())
        n = len(base_keys)
        # where an overlay row goes: before chain row pos[j], which it
        # replaces when the keys are equal
        pos = np.empty(hi - lo, dtype=np.int64)
        shadowed = np.zeros(n, dtype=bool)
        for j, key in enumerate(ov.keys[lo:hi]):
            p = pos[j] = bisect.bisect_left(base_keys, key)
            if p < n and base_keys[p] == key:
                shadowed[p] = True
        ov_src = ov.blk_id[lo:hi] + n_base
        ov_row = ov.row[lo:hi]
        ov_live = np.flatnonzero(~ov.tomb[lo:hi])
        if n:
            base_src = np.concatenate([
                np.full(b.count, s, dtype=np.int64)
                for s, b in enumerate(sources[:n_base])])
            base_row = np.concatenate([
                np.arange(b.count, dtype=np.int64)
                for b in sources[:n_base]])
            base_live = np.flatnonzero(~shadowed & (np.concatenate(
                [b.flags for b in sources[:n_base]]) == 0))
        else:
            base_src = base_row = base_live = np.zeros(0, dtype=np.int64)
        # chain row i sorts at 2i + 1, an overlay row at 2 pos: before
        # the chain row it precedes, after the overlay rows before it
        order = np.argsort(np.concatenate(
            [2 * base_live + 1, 2 * pos[ov_live]]), kind="stable")
        src = np.concatenate([base_src[base_live], ov_src[ov_live]])[order]
        row = np.concatenate([base_row[base_live], ov_row[ov_live]])[order]
        return [_gather_block(sources, src[off:off + self.capacity],
                              row[off:off + self.capacity])
                for off in range(0, len(src), self.capacity)]


def _gather_block(sources: List[Block], src: np.ndarray,
                  row: np.ndarray) -> Block:
    """A columnar block of rows (sources[src[i]], row[i]), in that
    order: one vectorized gather a source and column, the value heap
    by ragged byte ranges."""
    n = len(src)
    used = [(s, np.flatnonzero(src == s)) for s in np.unique(src)]
    width = max(sources[s].keys.shape[1] for s, _at in used)
    keys = np.zeros((n, width), dtype=np.uint8)
    key_len = np.empty(n, dtype=np.int32)
    ets = np.empty(n, dtype=np.uint32)
    hash_lo = np.empty(n, dtype=np.uint32)
    lens = np.empty(n, dtype=np.int64)
    starts = np.empty(n, dtype=np.int64)
    for s, at in used:
        blk, r = sources[s], row[at]
        keys[at, :blk.keys.shape[1]] = blk.keys[r]
        key_len[at] = blk.key_len[r]
        ets[at] = blk.expire_ts[r]
        hash_lo[at] = blk.hash_lo[r]
        vo = blk.value_offs.astype(np.int64)
        starts[at] = vo[r]
        lens[at] = vo[r + 1] - vo[r]
    offs = np.zeros(n + 1, dtype=np.uint32)
    offs[1:] = np.cumsum(lens)
    heap = np.empty(int(offs[-1]), dtype=np.uint8)
    for s, at in used:
        src_heap = sources[s].value_heap
        if not isinstance(src_heap, np.ndarray):
            src_heap = np.frombuffer(src_heap, dtype=np.uint8)
        ragged_scatter(heap, offs[:-1][at].astype(np.int64), src_heap,
                        starts[at], lens[at])
    return Block(keys, key_len, ets, hash_lo,
                 np.zeros(n, dtype=np.uint8), offs, heap)


class _FinishPool:
    """Shared write-stage finisher for both compaction paths: filled
    runs finish() (flush + fsync + rename + dir-fsync — ~half the wall
    of a disk-bound compaction) on helper threads while the producer
    keeps writing the next run; `results()` joins every future BEFORE
    the manifest publish, so the durability ordering (all runs
    durable, then manifest) is unchanged. `shutdown(ok=False,
    open_writer=...)` is the crash cleanup: nothing may leak the pool,
    in-flight finishes, a half-written handle, or — critically —
    already-renamed partial l1-*.sst outputs (a legacy pre-manifest
    boot would adopt the highest-seq orphan as the whole L1)."""

    def __init__(self) -> None:
        import concurrent.futures as _cf

        self._pool = _cf.ThreadPoolExecutor(max_workers=2)
        self._futures: list = []
        self._writers: list = []

    @staticmethod
    def _finish_one(w) -> "SSTable":
        w.finish()
        return SSTable(w.path)

    def submit(self, w) -> None:
        self._writers.append(w)
        self._futures.append(self._pool.submit(self._finish_one, w))

    def results(self) -> List["SSTable"]:
        return [f.result() for f in self._futures]

    def shutdown(self, ok: bool, open_writer=None) -> None:
        self._pool.shutdown(wait=True)
        if ok:
            return
        for f, w in zip(self._futures, self._writers):
            try:
                t = f.result()
            except Exception:  # noqa: BLE001 - finish() died
                try:
                    w.abandon()
                except Exception:  # noqa: BLE001 - best-effort
                    pass
                continue
            try:
                t.close()
                os.remove(t.path)
            except OSError:
                pass
        if open_writer is not None:
            try:
                open_writer.abandon()
            except Exception:  # noqa: BLE001 - best-effort
                pass


class _HeapEntry:
    """Heap ordering: key asc (or desc when reverse), then source index asc —
    so for equal keys the newest source (lowest index) pops first."""

    __slots__ = ("key", "src_idx", "record", "it", "reverse")

    def __init__(self, key, src_idx, record, it, reverse):
        self.key = key
        self.src_idx = src_idx
        self.record = record
        self.it = it
        self.reverse = reverse

    def __lt__(self, other: "_HeapEntry") -> bool:
        if self.key != other.key:
            return self.key > other.key if self.reverse else self.key < other.key
        return self.src_idx < other.src_idx


def _merge(sources: List[Iterator[Record]], reverse: bool = False
           ) -> Iterator[Record]:
    """K-way merge; on duplicate keys the lowest source index (newest) wins;
    shadowed duplicates are skipped and tombstone winners are dropped."""
    heap: List[_HeapEntry] = []
    for src_idx, it in enumerate(sources):
        first = next(it, None)
        if first is not None:
            heap.append(_HeapEntry(first[0], src_idx, first, it, reverse))
    heapq.heapify(heap)
    prev_key: Optional[bytes] = None
    while heap:
        entry = heapq.heappop(heap)
        key, value, ets = entry.record
        if key != prev_key:
            prev_key = key
            if value is not None:  # tombstone winners are invisible
                yield key, value, ets
        nxt = next(entry.it, None)
        if nxt is not None:
            heapq.heappush(heap,
                           _HeapEntry(nxt[0], entry.src_idx, nxt, entry.it,
                                      reverse))


def _chain_runs(runs: List[SSTable], start: bytes, stop: Optional[bytes],
                reverse: bool) -> Iterator[Record]:
    """Iterate non-overlapping key-ordered runs as one ordered stream,
    skipping runs outside [start, stop)."""
    for run in runs:
        first = run.first_key or b""
        last = run.last_key or b""
        if stop is not None and first >= stop:
            continue
        if start and last < start:
            continue
        yield from run.iterate(start, stop, reverse)
