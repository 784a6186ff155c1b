"""Table: a partitioned rrdb app.

In-process stand-in for the cluster side of the reference's client stack:
the partition resolver maps pegasus_key_hash(key) % partition_count to a
partition (src/client/partition_resolver.cpp:48,
pegasus_client_impl.cpp:124) and dispatches to that partition's primary. Here the "primaries" are local PartitionServer
instances; the RPC/meta layers (resolver cache, config refresh) take over
dispatch in the distributed deployment.
"""

from __future__ import annotations

import os
import shutil
from typing import Dict, List, Optional

from pegasus_tpu.base.key_schema import partition_index
from pegasus_tpu.server.partition_server import PartitionServer


def compact_partitions_parallel(servers, parallel: Optional[int] = None,
                                device=None, **compact_kwargs) -> None:
    """Manually compact many PartitionServers on a small thread pool
    (parity: the manual compact service's
    max_concurrent_running_count).

    parallel defaults to 8 for BOTH placements: on an accelerator each
    partition's eval waits on the link (GIL released) so overlap hides
    round-trips; on the host XLA backend the eval and the disk
    flush/fsync both release the GIL, and overlapping partitions keeps
    cores and the disk queue busy (measured: serial host compaction ran
    3-5x slower than 8-way on two independent environments — the
    round-3 serial heuristic was the single largest bench regression).

    The pool is for stores of more than one pipeline window of blocks
    (`compact_pipeline.PIPELINE_WINDOW`): their native kernels, device
    waits and fsyncs are long enough to overlap. A smaller store's compaction
    is a few hundred short calls under the interpreter lock, and beside
    other threads each of them waits for that lock (my chip run, PR 28:
    192 replicas of 8 blocks took 11.1 s one after another, 15.3 s on 3
    threads, 15.8 s on 8): such stores are compacted on the calling
    thread, one after another, before the pool starts.

    `device` pins workers' jax dispatch: jax.default_device is
    thread-local, so the caller's context does not reach the pool."""
    import contextlib
    from concurrent.futures import ThreadPoolExecutor

    from pegasus_tpu.storage.compact_pipeline import pipeline_window

    if parallel is None:
        parallel = 8

    def one_window(srv) -> bool:
        lsm = srv.engine.lsm
        rows = len(lsm.memtable) + sum(
            t.total_count for t in list(lsm.l0) + list(lsm.l1_runs))
        return rows <= pipeline_window() * lsm._block_capacity

    def one(srv):
        ctx = contextlib.nullcontext()
        if device is not None:
            import jax

            ctx = jax.default_device(device)
        with ctx:
            srv.manual_compact(**compact_kwargs)

    pooled = []
    for srv in servers:
        if parallel > 1 and not one_window(srv):
            pooled.append(srv)
        else:
            one(srv)
    if pooled:
        with ThreadPoolExecutor(max_workers=max(1, parallel)) as ex:
            for f in [ex.submit(one, s) for s in pooled]:
                f.result()


class Table:
    def __init__(self, data_dir: str, app_id: int = 1, app_name: str = "temp",
                 partition_count: int = 8, data_version: int = 1) -> None:
        if partition_count < 1:
            raise ValueError("partition_count must be >= 1")
        self.data_dir = data_dir
        self.app_id = app_id
        self.app_name = app_name
        self.partition_count = partition_count
        self.data_version = data_version
        self.partitions: Dict[int, PartitionServer] = {}
        for pidx in range(partition_count):
            self.partitions[pidx] = PartitionServer(
                os.path.join(data_dir, f"{app_id}.{pidx}"),
                app_id=app_id, pidx=pidx, partition_count=partition_count,
                data_version=data_version)

    def resolve(self, hash_key: bytes,
                sort_key: bytes = b"") -> PartitionServer:
        """Route by pegasus_key_hash of the full key (see partition_index):
        single-key ops pass their sort_key; multi-key ops pass b"" —
        matching the reference client's tmp_key construction
        (pegasus_client_impl.cpp:212)."""
        return self.route(hash_key, sort_key)[0]

    def route(self, hash_key: bytes,
              sort_key: bytes = b"") -> "tuple[PartitionServer, int]":
        """(server, partition_hash): the hash is computed once and carried
        with the request — the server validates it against its post-split
        partition_version so a request routed under a stale partition
        count is rejected instead of silently acked (parity: the
        rpc-header partition_hash, rpc_message.h:81-126)."""
        from pegasus_tpu.base.key_schema import key_hash_parts

        h = key_hash_parts(hash_key, sort_key)
        return self.partitions[h % self.partition_count], h

    def all_partitions(self) -> List[PartitionServer]:
        return [self.partitions[i] for i in range(self.partition_count)]

    def flush_all(self) -> None:
        for p in self.all_partitions():
            p.flush()

    def manual_compact_all(self, default_ttl=None, rules_filter=None,
                           parallel: int = 8, device=None) -> None:
        """None defaults defer to each partition's app-envs. Partitions
        overlap via compact_partitions_parallel."""
        compact_partitions_parallel(
            self.all_partitions(), parallel=parallel, device=device,
            default_ttl=default_ttl, rules_filter=rules_filter)

    def update_app_envs(self, envs: dict) -> None:
        """Propagate per-table envs to every partition (parity: meta
        config-sync pushing app-envs to replicas)."""
        for p in self.all_partitions():
            p.update_app_envs(envs)

    def split(self) -> None:
        """In-place 2x partition split (parity: replica/split/
        replica_split_manager.h:58 — each child copies its parent's state,
        the group flips to the doubled partition count, and the stale half
        of every partition is dropped lazily: masked from scans by the
        partition-hash predicate, physically removed at the next manual
        compaction via the same predicate in the compaction filter,
        key_ttl_compaction_filter.h:114-121).

        Known limitation: scanners opened before the split keep their old
        partition groups and may miss records that moved to the children
        mid-drain; the reference's clients detect this via partition-
        version mismatch errors on the wire — re-open scanners after a
        split (the wire layer will carry the same signal here).
        """
        old_count = self.partition_count
        if old_count & (old_count - 1):
            # the stale-half mask predicate is an &-mask: only meaningful
            # for power-of-two counts (reference split counts are pow2 by
            # construction)
            raise ValueError(
                f"partition split requires a power-of-two count, "
                f"have {old_count}")
        new_count = old_count * 2
        created = []
        touched_dirs = []
        # hold EVERY parent's write lock from first checkpoint through the
        # partition-count flip: a write accepted by a parent after its
        # child's checkpoint (routed by the old count) whose hash maps to
        # the child under the new count would be absent from the child and
        # later GC'd from the parent as stale-half data — silent loss. The
        # reference avoids this with a child catch-up from the parent's
        # private log plus a write fence before the flip
        # (replica_split_manager.h:76-123); this offline table-level split
        # fences instead. Locks in pidx order (the only multi-lock site).
        from contextlib import ExitStack
        with ExitStack() as stack:
            for pidx in range(old_count):
                stack.enter_context(self.partitions[pidx]._write_lock)
            try:
                for pidx in range(old_count):
                    parent = self.partitions[pidx]
                    child_pidx = pidx + old_count
                    child_dir = os.path.join(self.data_dir,
                                             f"{self.app_id}.{child_pidx}")
                    # track + clear the dir BEFORE writing anything into
                    # it: a failed earlier attempt must not leave stale
                    # SSTs that a retry would merge with fresh ones
                    touched_dirs.append(child_dir)
                    shutil.rmtree(child_dir, ignore_errors=True)
                    # checkpoint straight into the child's sst dir (no
                    # tempdir double-copy); writes are fenced table-wide
                    parent.engine.checkpoint(os.path.join(child_dir, "sst"))
                    child = PartitionServer(
                        child_dir, app_id=self.app_id, pidx=child_pidx,
                        partition_count=new_count,
                        data_version=self.data_version)
                    created.append((child_pidx, child))
                    if parent.app_envs:
                        child.update_app_envs(dict(parent.app_envs))
            except BaseException:
                # roll back: a half-split table must not leak open children
                # or partially-written child dirs
                for _, child in created:
                    child.close()
                for child_dir in touched_dirs:
                    shutil.rmtree(child_dir, ignore_errors=True)
                raise
            for child_pidx, child in created:
                self.partitions[child_pidx] = child
            for p in self.partitions.values():
                p.update_partition_count(new_count)
            self.partition_count = new_count

    def close(self) -> None:
        for p in self.partitions.values():
            p.close()

    def drop(self) -> None:
        self.close()
        shutil.rmtree(self.data_dir, ignore_errors=True)
