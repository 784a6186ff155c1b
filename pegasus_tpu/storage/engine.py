"""StorageEngine: WAL + LSM with decree watermark discipline.

Parity: src/server/rocksdb_wrapper.{h,cpp} + src/base/meta_store.{h,cpp} —
every committed write batch atomically carries its decree into engine
metadata (rocksdb_wrapper.cpp:205 puts `pegasus_last_flushed_decree` into
the meta CF inside the same WriteBatch), so any flushed/checkpointed state
knows exactly which decree it contains. Here:

- write_batch(items, decree): one WAL frame (decree-stamped) + memtable
  apply; last_committed_decree advances.
- flush(): memtable -> L0 SST whose footer meta records
  {last_flushed_decree, data_version}; WAL truncates after the SST is
  durable (replay contract preserved).
- boot: recover last_flushed_decree = max over SST metas, then replay WAL
  frames with decree > last_flushed_decree into the memtable.
- manual_compact(): full merge through the device TTL/stale-split filter
  (ops/compaction.compaction_filter_block) — the manual-compaction path
  (src/server/pegasus_manual_compact_service.h:48).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from pegasus_tpu.base.value_schema import epoch_now
from pegasus_tpu.ops.compaction import (
    compaction_filter_block,
    note_filter_program,
)
from pegasus_tpu.ops.record_block import build_record_block
# imported for its flag definitions (compact_max_mbps etc. must exist
# before any config file applies)
from pegasus_tpu.storage import compact_governor  # noqa: F401
from pegasus_tpu.storage.lsm import (
    TRANSFORM_CHUNK_BLOCKS,
    LSMStore,
    Splice,
)
from pegasus_tpu.storage.wal import OP_DEL, OP_PUT, WalRecord, WriteAheadLog
from pegasus_tpu.utils.tracing import frame_span, layer, mark


# the sets of shapes warm_manual_compact has compiled in this process
_WARMED: set = set()


@dataclass
class WriteBatchItem:
    op: int                 # OP_PUT | OP_DEL
    key: bytes
    value: bytes = b""      # full pegasus-encoded value for puts
    expire_ts: int = 0


class StorageEngine:
    def __init__(self, data_dir: str, data_version: int = 1,
                 block_capacity: int = 1024,
                 values_carry_expire_header: bool = False) -> None:
        self.data_dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self.data_version = data_version
        # the engine's expire_ts COLUMN is authoritative; values are
        # opaque bytes here. The server layer stores pegasus-encoded
        # values whose leading BE-u32 duplicates the TTL — it sets this
        # flag so compaction TTL rewrites also patch the embedded header
        # (keeping forensic readers of the raw value consistent).
        self.values_carry_expire_header = values_carry_expire_header
        self.lsm = LSMStore(os.path.join(data_dir, "sst"),
                            block_capacity=block_capacity)

        # recover the decree watermark from SST metas; data_version comes
        # from the table with the NEWEST watermark (an older L1 must not
        # revert a schema upgrade recorded by a newer L0 flush)
        self.last_flushed_decree = 0
        for table in list(self.lsm.l0) + list(self.lsm.l1_runs):
            d = int(table.meta.get("last_flushed_decree", 0))
            if d >= self.last_flushed_decree and "data_version" in table.meta:
                self.data_version = int(table.meta["data_version"])
            self.last_flushed_decree = max(self.last_flushed_decree, d)
        self.last_committed_decree = self.last_flushed_decree

        # auto-maintenance knobs (the usage-scenario env rewires them:
        # normal / prefer_write / bulk_load — common/replica_envs.h:81)
        self.memtable_flush_trigger = 100_000  # records
        self.auto_compact = True
        self.auto_compact_ctx = None  # server installs its filter context
        # write-through invalidation hook: called with the key list of
        # every applied batch BEFORE the write returns, so row-cache
        # owners (PartitionServer) can never serve a value this batch
        # replaced
        self.on_write_keys = None
        # serializes compactions: the env-triggered manual path holds it
        # across its (unlocked) merge; the write path's auto-compaction
        # try-acquires and SKIPS when a manual run is in flight (the
        # running compaction covers the trigger) — blocking there would
        # deadlock write-lock->compact-lock against the manual path's
        # compact-lock->write-lock publish ordering
        import threading as _threading

        self.compact_lock = _threading.Lock()

        # flush/compaction event metrics (parity: pegasus_event_listener)
        from pegasus_tpu.utils.metrics import METRICS

        ev = METRICS.entity("engine", data_dir, {"dir": data_dir})
        self._ev_flush_count = ev.counter("flush_count")
        self._ev_flush_bytes = ev.counter("flush_bytes")
        self._ev_flush_ms = ev.percentile("flush_duration_ms")
        self._ev_compact_count = ev.counter("compaction_count")
        self._ev_compact_bytes = ev.counter("compaction_bytes")
        self._ev_compact_ms = ev.percentile("compaction_duration_ms")
        # what the compaction filter saw and did, both paths (a dropped
        # row counts under `ttl` when its rewritten expire_ts has run
        # out or it is stale split data, else under `rules`)
        self._ev_compact_bytes_in = ev.counter("compact_bytes_in")
        self._ev_compact_rows_in = ev.counter("compact_rows_in")
        self._ev_dropped_rules = ev.counter("compact_rows_dropped_rules")
        self._ev_dropped_ttl = ev.counter("compact_rows_dropped_ttl")
        self._ev_ttl_rewritten = ev.counter("compact_rows_ttl_rewritten")
        self._ev_path_bulk = ev.counter("compact_path_bulk")
        self._ev_path_merge = ev.counter("compact_path_merge")
        # the block path's sequence: blocks that flowed unchanged into
        # the filter, chain blocks decoded and merged with overlay rows,
        # and those rows
        self._ev_blocks_chained = ev.counter("compact_blocks_chained")
        self._ev_blocks_spliced = ev.counter("compact_blocks_spliced")
        self._ev_overlay_rows = ev.counter("compact_overlay_rows")

        # replay WAL beyond the flushed watermark
        self._wal_path = os.path.join(data_dir, "wal.log")
        for decree, records in WriteAheadLog.replay(self._wal_path):
            if decree <= self.last_flushed_decree:
                continue
            for r in records:
                if r.op == OP_DEL:
                    self.lsm.delete(r.key)
                else:
                    self.lsm.put(r.key, r.value, r.expire_ts)
            self.last_committed_decree = max(self.last_committed_decree, decree)
        self.wal = WriteAheadLog(self._wal_path)

    def close(self) -> None:
        self.wal.close()
        self.lsm.close()

    # ---- write path ---------------------------------------------------

    def write_batch(self, items: Sequence[WriteBatchItem], decree: int,
                    sync: bool = False, wal_flush: bool = True) -> None:
        """Apply one decree's mutations atomically (WAL first).
        `wal_flush=False` leaves the WAL frame in the IO buffer instead
        of flushing per decree — only valid under replication, where
        the private log (hardened by the group-commit window before any
        ack) covers everything this WAL could recover."""
        if decree <= self.last_committed_decree:
            raise ValueError(
                f"decree {decree} <= last committed {self.last_committed_decree}")
        self.wal.append_batch(
            decree,
            [WalRecord(i.op, i.key, i.value, i.expire_ts) for i in items],
            sync=sync, flush=wal_flush)
        for i in items:
            if i.op == OP_DEL:
                self.lsm.delete(i.key)
            else:
                self.lsm.put(i.key, i.value, i.expire_ts)
        self.last_committed_decree = decree
        hook = self.on_write_keys
        if hook is not None and items:
            hook([i.key for i in items])
        self._maybe_maintain()

    def _maybe_maintain(self) -> None:
        """Auto flush + compaction (parity: rocksdb's write-buffer flush
        and level-0 compaction trigger, tuned by the usage-scenario env,
        pegasus_server_impl.cpp:1758): without this a write-heavy table
        never flushes — unbounded memtable, unbounded WAL replay.
        Callers hold the single-writer context already."""
        if len(self.lsm.memtable) < self.memtable_flush_trigger:
            return
        self.flush()
        if self.auto_compact and self.lsm.should_compact():
            if not self.compact_lock.acquire(blocking=False):
                return  # manual compaction in flight covers this trigger
            try:
                ctx = (self.auto_compact_ctx() if self.auto_compact_ctx
                       else {})
                self.manual_compact(**ctx)
            finally:
                self.compact_lock.release()

    def flush(self) -> bool:
        """Memtable -> durable L0 SST stamped with the decree watermark."""
        import time as _time

        t0 = _time.perf_counter()
        # a flush a traced request triggers is that request's time
        with layer("engine.flush"):
            table = self.lsm.flush(meta={
                "last_flushed_decree": self.last_committed_decree,
                "data_version": self.data_version,
            })
        if table is None:
            return False
        self.last_flushed_decree = self.last_committed_decree
        self.wal.truncate()
        # event-listener hooks (parity: pegasus_event_listener —
        # rocksdb flush/compaction events -> metrics)
        self._ev_flush_count.increment()
        self._ev_flush_ms.set((_time.perf_counter() - t0) * 1000.0)
        self._ev_flush_bytes.increment(os.path.getsize(table.path))
        return True

    # ---- read path ----------------------------------------------------

    def get(self, key: bytes) -> Optional[Tuple[bytes, int]]:
        return self.lsm.get(key)

    def iterate(self, start: bytes = b"", stop: Optional[bytes] = None,
                reverse: bool = False):
        return self.lsm.iterate(start, stop, reverse)

    # ---- checkpoint (parity: replication_app_base.h:171-236 +
    # rocksdb Checkpoint::CreateCheckpoint usage in pegasus_server_impl) --

    def checkpoint(self, dest_dir: str) -> int:
        """Flush, then materialize a consistent snapshot of the store into
        `dest_dir` (the checkpoint.<decree> analogue). Returns the decree
        the checkpoint contains."""
        import shutil

        self.flush()
        os.makedirs(dest_dir, exist_ok=True)
        sst_dir = os.path.join(self.data_dir, "sst")
        for name in os.listdir(sst_dir):
            # the manifest MUST travel with the runs: without it a
            # restored multi-run store would fall into the legacy
            # newest-l1-wins recovery and silently drop runs
            if name.endswith(".sst") or name == "MANIFEST.json":
                shutil.copy2(os.path.join(sst_dir, name),
                             os.path.join(dest_dir, name))
        return self.last_flushed_decree

    @staticmethod
    def restore_from_checkpoint(checkpoint_dir: str, data_dir: str
                                ) -> "StorageEngine":
        """Open a fresh engine whose state is the checkpoint's content
        (parity: storage_apply_checkpoint / restore-from-backup branch,
        pegasus_server_impl.cpp:1624)."""
        import shutil

        sst_dir = os.path.join(data_dir, "sst")
        shutil.rmtree(sst_dir, ignore_errors=True)
        os.makedirs(sst_dir, exist_ok=True)
        for name in os.listdir(checkpoint_dir):
            if name.endswith(".sst") or name == "MANIFEST.json":
                shutil.copy2(os.path.join(checkpoint_dir, name),
                             os.path.join(sst_dir, name))
        wal = os.path.join(data_dir, "wal.log")
        if os.path.exists(wal):
            os.remove(wal)
        return StorageEngine(data_dir)

    # ---- ingestion (parity: rocksdb_wrapper.cpp:248-266 IngestExternalFile
    # with the decree watermark carried atomically) ----------------------

    def ingest_sst_file(self, path: str, decree: int) -> None:
        """Adopt an externally-built columnar SST as the newest L0 run.

        The ingested file's meta is rewritten to carry the ingesting
        decree (the reference puts last_flushed_decree into the meta CF in
        the same atomic step as the ingestion), so checkpoints and
        learning know exactly what state they contain. The memtable is
        flushed FIRST: the ingest decree becomes the flushed watermark,
        and unflushed earlier writes must not be skipped by WAL recovery
        nor outrank the (newer-decree) ingested run in merge order.
        """
        from pegasus_tpu.storage.sstable import SSTable, SSTableWriter

        if decree <= self.last_committed_decree:
            raise ValueError(
                f"ingest decree {decree} <= last committed "
                f"{self.last_committed_decree}")
        self.flush()
        src = SSTable(path)

        def build(dest: str, meta) -> None:
            writer = SSTableWriter(dest, meta=meta)
            for key, value, ets in src.iterate():
                writer.add(key, value or b"", ets, tombstone=value is None)
            writer.finish()

        try:
            self.lsm.ingest(build, meta={
                "last_flushed_decree": decree,
                "data_version": self.data_version,
            })
        finally:
            src.close()
        self.last_committed_decree = decree
        self.last_flushed_decree = decree

    # ---- compaction ---------------------------------------------------

    def _count_filtered(self, nbytes: int, ets_orig, drop, new_ets,
                        now_s: int, not_rules=None, flags=None) -> None:
        """One filtered block of the block path into the engine's
        counters. `not_rules`: rows known dropped for another reason
        than the rules (stale split data: the path has the hash
        column). `flags`: a chained L0 block's tombstones are no rows
        (the per-record path's merge has dropped them before it
        counts)."""
        ets_orig = np.asarray(ets_orig, dtype=np.uint32)
        new_ets = ets_orig if new_ets is None else np.asarray(new_ets)
        by_ttl = (new_ets > 0) & (new_ets <= np.uint32(now_s))
        if not_rules is not None:
            by_ttl = by_ttl | not_rules
        n_rows = len(ets_orig)
        if flags is not None and np.any(flags):
            live = np.asarray(flags) == 0
            drop = drop & live
            new_ets = np.where(live, new_ets, ets_orig)
            n_rows = int(np.count_nonzero(live))
        n_rules = int(np.count_nonzero(drop & ~by_ttl))
        self._ev_compact_bytes_in.increment(int(nbytes))
        self._ev_compact_rows_in.increment(n_rows)
        self._ev_dropped_rules.increment(n_rules)
        self._ev_dropped_ttl.increment(int(np.count_nonzero(drop)) - n_rules)
        self._ev_ttl_rewritten.increment(
            int(np.count_nonzero(~drop & (new_ets != ets_orig))))

    def _manual_compact_bulk(self, snap, now_s: int, default_ttl: int,
                             pidx: int, partition_version: int,
                             do_validate: bool, operations,
                             publish_lock=None) -> None:
        """Block-level compaction over `snap` (lsm.bulk_compact_snapshot:
        pure L1, L0 tables chained by key range, an overlay spliced
        into the blocks it overlaps).

        One set of stage functions (read, filter submit/drain, write),
        two loops over them, chosen by what this call observes. A
        snapshot of more than one window (`pipeline_window()` entries)
        on a host of 4+ cores: the block-read and filter-eval stages
        run on dedicated threads connected by bounded queues
        (storage/compact_pipeline.py) and the rewrite transforms on a
        worker pool — disk reads, device/XLA filter programs, the
        native subset kernel and the output writers all overlap.
        Anything smaller, or fewer cores: the windowed loop runs the
        stages inline on the calling thread with one-window device
        lookahead. Either way the read stage pays the
        CompactionGovernor's token bucket, so background bandwidth
        answers foreground pressure, and both loops produce the
        identical (block, mask) stream, so output bytes match.

        Mesh-filtered: when the table's blocks are resident on the
        device mesh (parallel/mesh_resident.py), the whole store's drop
        masks come back from ONE SPMD dispatch shared across every
        sibling partition compacting under the same filter params —
        submit_window then serves each window from the mask dict with
        no per-window device program at all. Declines (gate, watchdog
        trip, non-resident blocks) fall through to the host/XLA stages
        above, byte-identical by construction."""
        from pegasus_tpu.ops.compaction import (
            choose_eval_device,
            compaction_eval_drain,
            compaction_eval_submit,
            encoded_drop_mask,
            rules_workload,
        )
        from pegasus_tpu.storage.compact_governor import GOVERNOR
        from pegasus_tpu.storage.compact_pipeline import (
            CompactPipeline,
            pipeline_depth,
            pipeline_window,
            stage_threads_enabled,
            transform_workers,
            window_count,
        )

        self._ev_path_bulk.increment()
        ttl_may_change = bool(default_ttl) or bool(
            operations and any(op.op == "update_ttl" for op in operations))
        entries = self.lsm.bulk_compact_entries(snap)
        if snap.overlay:
            # the overlay's tables were read whole to order their rows
            GOVERNOR.acquire(sum(
                bm.size for t in snap.overlay for bm in t.blocks))
        # mesh FILTER pre-pass: one whole-table dispatch (or a sibling's
        # cached one) hands back every block's drop mask up front; the
        # READ stage below still pays the governor, the WRITE stage is
        # untouched. The resident image holds a store without an
        # overlay: pure L1 alone.
        mesh_masks = None
        if entries and not snap.l0:
            from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
            try:
                mesh_masks = MESH_SERVING.try_compact_masks(
                    self.lsm, entries, now_s, default_ttl, pidx,
                    partition_version, do_validate, operations,
                    want_ets=ttl_may_change,
                    n_windows=window_count(len(entries)))
            except Exception:  # noqa: BLE001 - the host filter stage
                # below produces the identical masks; the failure is
                # logged and counted as a mesh fallback, never dropped
                MESH_SERVING.note_compact_failure()
        meta = {
            # snapshot mode: the output only covers decrees flushed at
            # freeze time — claiming last_committed would make boot skip
            # the WAL frames of writes that raced the merge
            "last_flushed_decree": (
                self.last_flushed_decree if publish_lock is not None
                else self.last_committed_decree),
            "data_version": self.data_version,
            "manual_compact_finish_time": epoch_now(),
        }

        # direct compute on compressed blocks: a ruleset that touches
        # no key bytes (TTL + default-TTL rewrite + stale-split)
        # evaluates straight off the encoded block's raw predicate
        # columns — no key-matrix rebuild, no value-heap inflate, no
        # device program; unchanged blocks then copy verbatim
        def direct(run) -> bool:
            return (operations is None
                    and getattr(run, "codec", None) is not None)

        def load(entry):
            """READ stage: one entry's blocks off disk, paced by the
            governor (this is the only place background compaction
            touches the disk for input): a chain block as it is, or
            the blocks a splice merges from the chain blocks and the
            overlay rows in their spans. A compressed block stays
            ENCODED whatever evaluates it: rules read its key matrix
            (EncodedBlock.keys, no heap inflate) and the write stage
            copies or subsets its bytes as they are. -> [(source, idx,
            blk, host-direct?, bytes read)]"""
            if isinstance(entry, Splice):
                GOVERNOR.acquire(entry.base_bytes)
                self._ev_blocks_spliced.increment(len(entry.base))
                self._ev_overlay_rows.increment(entry.hi - entry.lo)
                # its bytes in: on the first of its blocks
                out = [(entry, j, blk, direct(entry),
                        0 if j else entry.base_bytes + entry.overlay_bytes)
                       for j, blk in enumerate(entry.blocks())]
            else:
                run, i, bm = entry
                GOVERNOR.acquire(bm.size)
                self._ev_blocks_chained.increment()
                blk = (run.read_block_encoded(i)
                       if getattr(run, "codec", None) is not None
                       else run.read_block(i))
                out = [(run, i, blk, direct(run), bm.size)]
            mark("compact_read")
            return out

        def submit_window(items):
            """FILTER stage phase 1: dispatch without waiting."""
            if mesh_masks is not None:
                served = {}
                for run, i, _blk, _d, _n in items:
                    m = mesh_masks.get((run, i))
                    if m is None:
                        break
                    served[(run, i)] = m
                else:
                    # whole window pre-filtered on the mesh: nothing
                    # in flight, eager-forward straight to WRITE
                    return items, [], served
            blocks = [((run, i), blk, pidx)
                      for run, i, blk, is_direct, _n in items
                      if not is_direct]
            host_done = {}
            for run, i, blk, is_direct, _n in items:
                if is_direct:
                    host_done[(run, i)] = encoded_drop_mask(
                        blk, now_s, default_ttl, pidx,
                        partition_version, do_validate,
                        want_ets=ttl_may_change)
            # placed only where a program is dispatched: the link probe
            # behind it (once a process: 16 MiB each way) is not a
            # host-direct compaction's to pay
            pend = compaction_eval_submit(
                blocks, now_s, default_ttl, partition_version,
                do_validate, operations=operations,
                eval_device=choose_eval_device(
                    workload=rules_workload(operations)),
                want_ets=ttl_may_change) if blocks else []
            mark("compact_filter_submit")
            return items, pend, host_done

        def drain_window(token):
            """FILTER stage phase 2: materialize one window's masks."""
            items, pend, host_done = token
            got = {}
            for tag, drop, new_ets in compaction_eval_drain(
                    pend, want_ets=ttl_may_change):
                got[tag] = (drop, new_ets)
            out = []
            for run, i, blk, is_direct, nbytes in items:
                # host_done holds both direct-on-encoded masks and
                # mesh-served ones; device programs land in got
                m = host_done.get((run, i))
                if m is None:
                    m = got[(run, i)]
                drop, new_ets = m
                self._count_filtered(
                    nbytes, blk.expire_ts, drop, new_ets,
                    now_s, not_rules=(
                        (np.asarray(blk.hash_lo)
                         & np.uint32(max(partition_version, 0)))
                        != np.uint32(pidx)
                        if do_validate and blk.hash_lo is not None
                        else None), flags=blk.flags)
                out.append((run, i, blk, drop, new_ets))
            mark("compact_filter_drain")
            return out

        # A snapshot of one window gives the stage threads nothing to
        # overlap (the window is read, then filtered, then written),
        # and one of a single transform chunk gives the transform
        # workers nothing: such a compaction (a replica of a few
        # thousand rows) runs its stages inline on the calling thread.
        # Beside other compactions of a pool its helper threads only
        # queue for the interpreter lock.
        if stage_threads_enabled() and len(entries) > pipeline_window():
            pipe = CompactPipeline(
                entries, load, submit_window, drain_window,
                window=pipeline_window(), depth=pipeline_depth(),
                span=frame_span(),
                # a window whose masks all computed host-direct at
                # submit has no in-flight device program to hide:
                # forward it immediately instead of holding the
                # one-window lookahead
                eager=lambda token: not token[1])
            results = pipe.results()
        else:
            def inline_results():
                # one-window lookahead ONLY for windows with an
                # in-flight device program: while window w's masks
                # drain and its survivors rewrite, window w+1 is
                # already uploaded and evaluating. Host-direct windows
                # (every mask computed at submit) yield immediately —
                # holding them back starves the write stage for a full
                # window of reads with nothing async to hide.
                W = pipeline_window()
                pending = None
                for off in range(0, len(entries), W):
                    token = submit_window(
                        [item for e in entries[off:off + W]
                         for item in load(e)])
                    if pending is not None:
                        yield from drain_window(pending)
                        pending = None
                    if not token[1]:
                        yield from drain_window(token)
                    else:
                        pending = token
                if pending is not None:
                    yield from drain_window(pending)

            results = inline_results()

        self.lsm.bulk_compact_rewrite(
            results, meta, ttl_may_change=ttl_may_change,
            patch_headers=self.values_carry_expire_header,
            publish_lock=publish_lock,
            transform_workers=(
                transform_workers()
                if len(entries) > TRANSFORM_CHUNK_BLOCKS else 0),
            snap=snap)

    def manual_compact(self, default_ttl: int = 0, pidx: int = 0,
                       partition_version: int = -1,
                       validate_hash: bool = False,
                       rules_filter=None,
                       now: Optional[int] = None,
                       publish_lock=None) -> None:
        """Full compaction with the device TTL/stale-split filter.

        `rules_filter(keys, expire_ts, now) -> (drop, new_ets)` is the
        optional user-specified compaction hook (compaction_rules.py),
        applied after the default-TTL rewrite, before expiry — matching the
        reference's Filter() ordering (key_ttl_compaction_filter.h:71-90).

        `publish_lock` (narrow-critical-section mode): the caller froze
        the memtable with a flush and holds engine.compact_lock; the
        merge runs over the immutable file snapshot with writes flowing
        and the lock is taken only for the publish cut-over.
        """
        now_s = epoch_now() if now is None else now
        # pv<0 / pidx>pv -> no stale-split dropping (keep), per
        # check_if_stale_split_data.
        do_validate = bool(validate_hash and partition_version >= 0
                           and pidx <= partition_version)

        # The block path (the GB/s shape) takes every store whose
        # memtable is frozen (or empty) and whose files carry hash_lo:
        # pure L1, the lone L0 of a table's first compaction, L0 tables
        # that chain by key range, an L0 that overlaps L1 (spliced into
        # the blocks it meets). Whole columnar blocks are evaluated in a
        # handful of stacked programs and surviving rows rewritten with
        # numpy gathers — no per-record Python but over the overlay's
        # own rows. The per-record merge path below keeps what that
        # cannot read: a live memtable under the caller's lock (legacy
        # mode), v1 files, a rules callable without a parsed ruleset.
        operations = getattr(rules_filter, "operations", None)
        snap = (self.lsm.bulk_compact_snapshot(
            frozen=publish_lock is not None)
            if rules_filter is None or operations is not None else None)
        if snap is not None:
            self._compact_with_epilogue(
                lambda: self._manual_compact_bulk(
                    snap, now_s, default_ttl, pidx, partition_version,
                    do_validate, operations, publish_lock=publish_lock),
                advance_watermark=publish_lock is None)
            return

        self._ev_path_merge.increment()
        record_filter = self._merge_record_filter(
            now_s, default_ttl, pidx, partition_version, do_validate,
            rules_filter)

        self._compact_with_epilogue(
            lambda: self._ev_compact_bytes_in.increment(self.lsm.compact(
                record_filter=record_filter,
                patch_headers=self.values_carry_expire_header,
                publish_lock=publish_lock,
                meta={
                    # see _manual_compact_bulk: snapshot mode covers
                    # only the freeze-time watermark
                    "last_flushed_decree": (
                        self.last_flushed_decree
                        if publish_lock is not None
                        else self.last_committed_decree),
                    "data_version": self.data_version,
                    "manual_compact_finish_time": epoch_now(),
                })),
            advance_watermark=publish_lock is None)

    def _merge_record_filter(self, now_s: int, default_ttl: int, pidx: int,
                             partition_version: int, do_validate: bool,
                             rules_filter, count: bool = True):
        """The per-record path's filter for lsm.compact: `(keys, ets) ->
        (drop, new_ets)`, both lazy device values at the batch's
        power-of-two bucket. With `count` every batch lands on the
        engine's counters from what is on the host already (the rules'
        mask and the rewritten expire_ts; expiry is one compare; the
        stale split rows the device drops besides stay uncounted
        here)."""
        import jax.numpy as jnp

        def record_filter(keys: List[bytes], ets: List[int]):
            n = len(keys)
            # Stage 1 — default-TTL rewrite (reference does this FIRST and
            # hands the rewritten value to the user rules, Filter():72-79).
            ets_in = np.asarray(ets, dtype=np.uint32)
            ets_arr = ets_in
            if default_ttl:
                ets_arr = np.where(ets_arr == 0,
                                   np.uint32(now_s + default_ttl), ets_arr)
            # Stage 2 — user-specified rules see the rewritten TTLs.
            if rules_filter is not None:
                rule_drop, ets_arr = rules_filter(keys, ets_arr, now_s)
                rule_drop = np.asarray(rule_drop)
                ets_arr = np.asarray(ets_arr, dtype=np.uint32)
            else:
                rule_drop = np.zeros(n, dtype=bool)
            if count:
                expired = (ets_arr > 0) & (ets_arr <= np.uint32(now_s))
                self._ev_compact_rows_in.increment(n)
                self._ev_dropped_rules.increment(
                    int(np.count_nonzero(rule_drop & ~expired)))
                self._ev_dropped_ttl.increment(
                    int(np.count_nonzero(expired)))
                self._ev_ttl_rewritten.increment(int(np.count_nonzero(
                    ~rule_drop & ~expired & (ets_arr != ets_in))))
            # Stage 3 — expiry + stale-split drop on device (default_ttl=0:
            # the rewrite already happened; a rule that cleared a TTL must
            # not be re-stamped).
            # power-of-two capacity bucket: arbitrary tail-batch sizes
            # would each compile their own XLA program
            cap = 1024
            while cap < n:
                cap <<= 1
            block = build_record_block(keys, ets_arr, capacity=cap)
            drop, new_ets = compaction_filter_block(
                np.asarray(block.keys), np.asarray(block.key_len),
                np.asarray(block.hashkey_len), np.asarray(block.expire_ts),
                np.asarray(block.valid),
                np.uint32(now_s), np.uint32(0),
                np.uint32(pidx),
                np.uint32(max(partition_version, 0)),
                do_validate)
            # as compile_rules' program, plus the rules' mask up
            note_filter_program(cap, cap * (block.keys.shape[1] + 14 + 5))
            # stay LAZY: combining on device keeps the result an async
            # jax value, so the LSM's double-buffered compaction really
            # overlaps this batch's device work with the next batch's
            # host gathering (materialization happens at drain). At the
            # bucket's width: a slice to n would be a program of its
            # own for every n a partition ever has (the store reads the
            # first n of both)
            padded = np.zeros(cap, dtype=bool)
            padded[:n] = rule_drop
            return jnp.logical_or(drop, jnp.asarray(padded)), new_ets

        return record_filter

    def warm_manual_compact(self, default_ttl: int = 0, pidx: int = 0,
                            partition_version: int = -1,
                            validate_hash: bool = False,
                            rules_filter=None) -> None:
        """Compile now what a manual compaction of this store, as it
        stands, would compile where it first dispatches it: on the
        block path (the path `manual_compact` takes once the run's
        flush has frozen the memtable) the fused program at the
        buckets of its windows, on the per-record path the filter
        programs at the buckets of its batches; the placement probe.
        A window's rows are known to within the overlay's (an overlay
        row adds a row to a spliced block, replaces one, or takes one):
        every bucket in that reach is compiled. Rows of the store's
        own first block ride through the code a compaction runs;
        nothing is written, and of the counters only the dispatched
        filter programs' move. Once a process for each set of shapes:
        the programs are jit-cached process-wide, so the first replica
        of a table pays for its siblings."""
        from pegasus_tpu.ops.compaction import (
            _row_bucket,
            choose_eval_device,
            compaction_eval_drain,
            compaction_eval_submit,
            rules_workload,
        )
        from pegasus_tpu.storage.compact_pipeline import pipeline_window

        lsm = self.lsm
        first = next((t for t in list(lsm.l1_runs) + list(lsm.l0)
                      if t.blocks), None)
        if first is None:
            return
        operations = getattr(rules_filter, "operations", None)
        do_validate = bool(validate_hash and partition_version >= 0
                           and pidx <= partition_version)
        snap = (lsm.bulk_compact_snapshot(frozen=True)
                if rules_filter is None or operations is not None else None)
        n_mem = len(lsm.memtable)   # the run's flush makes it an L0
        batches, windows = set(), set()
        if snap is None:
            rows = n_mem + sum(t.total_count
                               for t in lsm.l0 + lsm.l1_runs)
            batch = lsm.filter_batch_rows
            batches = {min(rows, batch), rows % batch or batch}
        # the block path evaluates on the device with a parsed ruleset,
        # or with none over an uncompressed store
        elif operations is not None or (
                rules_filter is None and getattr(first, "codec", None) is None):
            counts = [bm.count for t in snap.chain for bm in t.blocks]
            k = n_mem + sum(t.total_count for t in snap.overlay)
            win = pipeline_window()
            for off in range(0, max(len(counts), 1), win):
                n = sum(counts[off:off + win])
                bucket = _row_bucket(max(n - k, 1))
                while bucket <= _row_bucket(n + k):
                    windows.add(bucket)
                    bucket <<= 1
        shapes = (rules_filter, first.blocks[0].key_width,
                  frozenset(_row_bucket(n) for n in batches),
                  frozenset(windows), do_validate, bool(default_ttl),
                  getattr(first, "codec", None) is not None)
        if shapes in _WARMED:
            return
        blk = first.read_block(0)
        now_s = epoch_now()
        if batches:
            record_filter = self._merge_record_filter(
                now_s, default_ttl, pidx, partition_version, do_validate,
                rules_filter, count=False)
            for n in batches:
                for lazy in record_filter([blk.key_at(0)] * n, [0] * n):
                    np.asarray(lazy)
        if windows:
            ttl_may_change = bool(default_ttl) or any(
                op.op == "update_ttl" for op in operations or ())
            eval_device = choose_eval_device(
                workload=rules_workload(operations))
            for bucket in windows:
                # as many of the block as the bucket holds: its rows
                # are over half of it (a block is 1,024 of >= 4,096)
                list(compaction_eval_drain(compaction_eval_submit(
                    [(i, blk, pidx)
                     for i in range(max(1, bucket // blk.count))],
                    now_s, default_ttl, partition_version, do_validate,
                    operations=operations, eval_device=eval_device,
                    want_ets=ttl_may_change), want_ets=ttl_may_change))
        _WARMED.add(shapes)

    def _compact_with_epilogue(self, body,
                               advance_watermark: bool = True) -> None:
        """Shared post-compaction bookkeeping for both compaction paths:
        advance the flushed watermark (everything committed is now in
        the SSTs), truncate the WAL, and record metrics.

        `advance_watermark=False` (snapshot-mode compaction): writes
        flowed DURING the merge, so committed > covered — the freeze
        flush already advanced the watermark and truncated the WAL for
        everything the compaction merged, and the newer writes' WAL
        frames must survive for crash recovery."""
        import time as _time

        t0 = _time.perf_counter()
        with layer("engine.compact"):
            body()
        if advance_watermark:
            self.last_flushed_decree = self.last_committed_decree
            self.wal.truncate()
        self._ev_compact_count.increment()
        self._ev_compact_ms.set((_time.perf_counter() - t0) * 1000.0)
        self._ev_compact_bytes.increment(sum(
            os.path.getsize(t.path) for t in self.lsm.l1_runs))
