"""PartitionServer: the rrdb storage app for one partition.

Parity: src/server/pegasus_server_impl.{h,cpp} — implements the full rrdb
service surface (idl/rrdb.thrift:347-364): get / multi_get / batch_get /
sortkey_count / ttl / get_scanner / scan / clear_scanner on the read side,
put / multi_put / remove / multi_remove / incr / check_and_set /
check_and_mutate on the write side.

The TPU-first difference is the ranged-read hot loop: where the reference
validates records one-by-one in scalar C++ (on_multi_get:496, hot loop
:643; validate_key_value_for_scan:2382), we gather candidates into
columnar batches and evaluate filter/TTL/partition-hash predicates for a
whole batch in one device program (ops.scan_block_predicate).

Standalone mode assigns decrees locally; under replication the replica
layer drives apply with its own decrees.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import OrderedDict
from typing import Iterator, List, Optional, Tuple

import numpy as np

from pegasus_tpu.base.key_schema import (
    generate_key,
    generate_next_bytes,
    restore_key,
)
from pegasus_tpu.base.value_schema import (
    PEGASUS_EPOCH_BEGIN,
    check_if_ts_expired,
    epoch_now,
    extract_expire_ts,
    extract_user_data,
    expire_ts_from_ttl,
    header_length,
)
from pegasus_tpu.ops.predicates import (
    FT_MATCH_ANYWHERE,
    FT_MATCH_POSTFIX,
    FT_MATCH_PREFIX,
    FT_NO_FILTER,
    FilterSpec,
    host_match_filter,
    scan_block_predicate,
)
from pegasus_tpu.ops import pushdown as pushdown_ops

from pegasus_tpu.ops.record_block import build_record_block
from pegasus_tpu.server.capacity_units import (
    CapacityUnitCalculator,
    units as cu_units,
)
from pegasus_tpu.server.read_limiter import RangeReadLimiter
from pegasus_tpu.server.row_cache import ROW_CACHE
from pegasus_tpu.server.scan_context import ScanContext, ScanContextCache
from pegasus_tpu.server.types import (
    BatchGetRequest,
    BatchGetResponse,
    CheckAndMutateRequest,
    CheckAndMutateResponse,
    CheckAndSetRequest,
    CheckAndSetResponse,
    FullData,
    GetScannerRequest,
    IncrRequest,
    IncrResponse,
    KeyValue,
    MultiGetRequest,
    MultiGetResponse,
    MultiPutRequest,
    MultiRemoveRequest,
    SCAN_CONTEXT_ID_COMPLETED,
    SCAN_CONTEXT_ID_NOT_EXIST,
    ScanResponse,
)
from pegasus_tpu.server.write_service import WriteService

from pegasus_tpu.storage.bloom import bloom_probe_enabled
from pegasus_tpu.storage import compact_governor
from pegasus_tpu.storage.compact_governor import MANUAL_COMPACT_MAX_RUNNING
from pegasus_tpu.storage.phash import phash_probe_enabled
from pegasus_tpu.storage.engine import StorageEngine
from pegasus_tpu.utils.errors import (
    ErrorCode,
    StorageCorruptionError,
    StorageStatus,
)
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.flags import FLAGS, define_flag
from pegasus_tpu.utils.metrics import METRICS

define_flag("pegasus.server", "scan_pushdown_enabled", True,
            "evaluate GetScannerRequest.pushdown specs (value filters "
            "+ aggregates) inside the scan-page path; off simulates a "
            "pre-pushdown server — specs are ignored, pushdown_applied "
            "stays False, clients fall back to local evaluation",
            mutable=True)

# the no-filter flavor's mask key component (and the normal form of any
# empty-pattern filter, which matches everything)
_NO_FILTER_KEY = (FT_NO_FILTER, b"", FT_NO_FILTER, b"")
# the sets of read-path mask shapes _warm_manual_compact has compiled
_WARMED_MASKS: set = set()


def _normalize_filter_key(r) -> tuple:
    """(hash type, hash pattern, sort type, sort pattern), with
    empty-pattern components collapsed to FT_NO_FILTER and patterns
    under FT_NO_FILTER dropped — the matchers treat both as match-all,
    so distinct keys would only split batches and duplicate masks."""
    hft, hfp = r.hash_key_filter_type, r.hash_key_filter_pattern
    sft, sfp = r.sort_key_filter_type, r.sort_key_filter_pattern
    if hft == FT_NO_FILTER or not hfp:
        hft, hfp = FT_NO_FILTER, b""
    if sft == FT_NO_FILTER or not sfp:
        sft, sfp = FT_NO_FILTER, b""
    return (hft, hfp, sft, sfp)

# candidate records gathered per device predicate dispatch
PREDICATE_BATCH = 2048

# node-wide twin of the per-replica bloom counter (same RelaxedCounter
# object the sstable solo path ticks — the registry dedupes by name)
_STORAGE_BLOOM_USEFUL = METRICS.entity(
    "storage", "node").relaxed_counter("bloom_useful_count")

# requests bounced for routing under a stale partition count (the
# ERR_PARENT_PARTITION_MISUSED hash-gate) — the node-level split-fence
# observability the stub's ERR_SPLITTING rejects share
_SPLIT_FENCE_REJECTS = METRICS.entity(
    "storage", "node").counter("split_fence_reject_count")

# what a scan costs against what it gives, as counters a window can
# difference (the PerfContext holds the same per op): rows the scan
# paths examined and rows they returned, one add per scan batch; the
# memtable + L0 rows a batch's request ranges made _overlay_windows
# evaluate; and the requests whose range held overlay rows, which the
# Python merge loop of finish_scan_batch serves, not page.serve_batch
_SCAN_ROWS_EVALUATED = METRICS.entity(
    "storage", "node").counter("scan_rows_evaluated")
_SCAN_ROWS_RETURNED = METRICS.entity(
    "storage", "node").counter("scan_rows_returned")
_OVERLAY_ROWS_WALKED = METRICS.entity(
    "storage", "node").counter("overlay_rows_walked")
_SCAN_MERGE_PATH_REQUESTS = METRICS.entity(
    "storage", "node").counter("scan_merge_path_requests")
# the point path's twin: distinct keys a get/multi-get plan resolved
# (row cache, memtable or base alike) and those of them the memtable
# answered before any base look-up, one add per plan
_POINT_KEYS_RESOLVED = METRICS.entity(
    "storage", "node").counter("point_keys_resolved")
_POINT_OVERLAY_HITS = METRICS.entity(
    "storage", "node").counter("point_overlay_hits")
# the paging path: scanners whose first page left a context behind
# (on_get_scanner) and later pages served from a held context
# (on_scan); and the static-mask cache of both read paths, one
# look-up a (block, flavor), one add per plan or window of blocks
_SCAN_CONTEXTS_OPENED = METRICS.entity(
    "storage", "node").counter("scan_contexts_opened")
_SCAN_PAGES_SERVED = METRICS.entity(
    "storage", "node").counter("scan_pages_served")
_MASK_CACHE_HIT = METRICS.entity(
    "storage", "node").counter("mask_cache_hit")
_MASK_CACHE_MISS = METRICS.entity(
    "storage", "node").counter("mask_cache_miss")
# blocks evaluated into a stack's padding ahead of a look-ahead window
# (_static_keep_window's fill), one add a window that fills
_MASK_FILL_BLOCKS = METRICS.entity(
    "storage", "node").counter("mask_fill_blocks")

# blocks a ranged read's look-ahead window gathers: the window's mask
# misses go to the device in one stacked wave
LOOKAHEAD = 8



# point-location-cache miss sentinel (None is a valid cached value:
# "definitively absent from the L1 runs")
_POINT_MISS = object()

# _overlay_windows' entry for a key the batch's key filter excludes
# from every window (None is a valid entry: a hidden shadow)
_OVERLAY_EXCLUDED = object()


def _after(key: bytes) -> bytes:
    """Immediate lexicographic successor of an exact key."""
    return key + b"\x00"


# Server-side caps on one scan page: client-supplied batch_size is
# untrusted, and page blob offsets are uint32 (ScanPage /
# pegasus_gather_page) — a >4GiB page would silently wrap them. The
# byte cap bounds the page by VALUE weight too (values can be multi-MB
# each); a capped page returns stop_early with a resume cursor, exactly
# like a record-capped one. The reference likewise caps scan batches
# server-side (pegasus_server_impl scan batch limits).
SCAN_BATCH_CAP = 65536
SCAN_BYTES_CAP = 64 << 20


def _lower_bound(blk, key: bytes) -> int:
    """First row index in a sorted SST block whose key >= `key`.

    Hot blocks (zipfian traffic re-plans the same boundaries) bisect
    C-speed over the materialized key list; cold blocks keep the
    O(log n) row-probe loop so a one-shot uniform scan never pays the
    full materialization (same gating as SSTable.get)."""
    import bisect as _b

    kl = blk._key_list
    if kl is None:
        blk._gets += 1
        if blk._gets >= 4:
            kl = blk.key_list()
    if kl is not None:
        return _b.bisect_left(kl, key)
    lo, hi = 0, blk.count
    while lo < hi:
        mid = (lo + hi) // 2
        if blk.key_at(mid) < key:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _scan_windows(sorted_runs, start_key: bytes, stop_key: Optional[bytes],
                  limiter: RangeReadLimiter):
    """The look-ahead windows of a ranged read over the sorted L1 runs,
    in key order: lists of up to LOOKAHEAD (ckey, blk, lo, hi). A
    boundary block's rows outside [start_key, stop_key) are trimmed off
    by (lo, hi), a bisect on its sorted keys, and only in-range rows
    count against `limiter` (out-of-range rows were never "examined"),
    as the block enters a window. Fetching one window past the stop
    point costs unused masks, never correctness. Each window comes with
    the range's blocks after it (_blocks_after): what a stack's padding
    may be filled with."""

    def ranged_blocks():
        for ri, run in enumerate(sorted_runs):
            if stop_key is not None and (run.first_key or b"") >= stop_key:
                continue
            if start_key and (run.last_key or b"") < start_key:
                continue
            for bm, blk in run.iter_blocks(start_key, stop_key):
                yield ri, run, bm, blk

    blocks = ranged_blocks()
    while True:
        window = []
        for ri, run, bm, blk in itertools.islice(blocks, LOOKAHEAD):
            lo, hi = 0, blk.count
            if start_key and bm.first_key < start_key:
                lo = _lower_bound(blk, start_key)
            if stop_key is not None and bm.last_key >= stop_key:
                hi = _lower_bound(blk, stop_key)
            limiter.add_count(hi - lo)
            window.append(((run.path, bm.offset), blk, lo, hi))
        if not window:
            return
        yield window, _blocks_after(sorted_runs, ri, bm, stop_key)


def _blocks_after(sorted_runs, ri: int, bm, stop_key: Optional[bytes]):
    """(ckey, read) of the blocks after block `bm` of run `ri` that
    start before `stop_key`, in key order. Lazy, and `read()` decodes
    the block: a caller pays only for the blocks it takes."""
    bi = sorted_runs[ri].block_index(bm) + 1
    for run in sorted_runs[ri:]:
        for i in range(bi, len(run.blocks)):
            nxt = run.blocks[i]
            if stop_key is not None and nxt.first_key >= stop_key:
                return
            yield (run.path, nxt.offset), functools.partial(run.read_block, i)
        bi = 0


class PartitionServer:
    def __init__(self, data_dir: str, app_id: int = 1, pidx: int = 0,
                 partition_count: int = 1, data_version: int = 1,
                 cluster_id: int = 1) -> None:
        self.app_id = app_id
        self.pidx = pidx
        self.partition_count = partition_count
        # partition_version starts at count-1; split updates it
        # (parity: replica_split semantics via key_ttl/scan hash checks).
        # The &-mask check (check_pegasus_key_hash) is only meaningful for
        # power-of-two counts — routing is `% partition_count`, and
        # `& (count-1)` disagrees with it otherwise, silently dropping
        # records from scans. The reference only runs this check around
        # partition split, where counts are powers of two by construction.
        self.partition_version = partition_count - 1
        self.validate_partition_hash = (
            partition_count > 1 and (partition_count & (partition_count - 1)) == 0)
        self.data_version = data_version
        self.engine = StorageEngine(data_dir, data_version=data_version,
                                    values_carry_expire_header=True)
        self.write_service = WriteService(self.engine, data_version,
                                          cluster_id)
        self._write_lock = threading.Lock()  # single-writer invariant
        self._scan_cache = ScanContextCache()
        # (store-instance, generation, {(start, stop, want-bucket) ->
        # (plan, unique-entries)}): one dict PER GENERATION, replaced
        # wholesale when the run set (or the whole engine — learner
        # checkpoint apply / restore swap it) changes, so stale plans
        # can neither serve pre-swap blocks nor pin dead files
        self._plan_cache = None
        # (ckey, static-mask-id) -> (second, alive, expired_count, live):
        # per-second TTL-applied serving masks (see prepare_serve)
        self._live_cache: dict = {}
        # ((generation, second), {plan-id -> (plan, expired-count)}):
        # flavor-independent per-request expired accounting, reset
        # wholesale each second / store generation so it never pins
        # compacted-away blocks (see finish_scan_batch)
        self._plan_expired_cache: tuple = (None, {})
        # (store-instance, generation, {key -> None | ("l1", blk,
        # row)}): the point-read location cache — zipfian point traffic
        # re-probes the same hot keys constantly, and a key's (block,
        # row) location is pure over the immutable run set, so cache
        # hits skip the run/block/row bisects entirely. Same
        # invalidation discipline as _plan_cache (replaced wholesale on
        # generation change).
        self._point_cache = None
        # (store, generation, phash-flag, MultiProbe, {id(table) ->
        # filter col}, PHashMultiProbe, {id(table) -> index col}): the
        # run set's sidecar structures prepared for the one-call
        # batched probes; pure over the immutable run set (+ the
        # mutable phash kill switch, which decides whether indexed
        # tables still need bloom columns)
        self._index_probe_cache = None
        self.metrics = METRICS.entity(
            "replica", f"{app_id}.{pidx}",
            {"table": str(app_id), "partition": str(pidx)})
        self.cu = CapacityUnitCalculator(self.metrics)
        # nanosecond time source for range-read time budgets: None =
        # wall perf_counter_ns; sim-hosted partitions get the virtual
        # clock threaded in by the stub (the scrub_tick/health_tick
        # discipline) so compressed schedules can't spuriously trip —
        # or never trip — rocksdb_iteration_threshold_time_ms
        self.clock_ns = None
        self._abnormal_reads = self.metrics.counter("abnormal_read_count")
        # filter/row-cache observability, per partition (the node-wide
        # twins live on the "storage" entity): incremented BATCHED, once
        # per read flush
        self._bloom_useful = self.metrics.counter("bloom_useful_count")
        self._phash_useful = self.metrics.counter("phash_useful_count")
        self._row_cache_hits = self.metrics.counter("row_cache_hit")
        self._row_cache_misses = self.metrics.counter("row_cache_miss")
        # follower-read observability, per partition (node-wide twins on
        # the "storage" entity): reads this SECONDARY answered, reads it
        # bounced ERR_STALE_REPLICA, and the subset of those bounces
        # caused by a lapsed beacon lease — incremented by the hosting
        # stub's consistency gate
        self._follower_reads = self.metrics.counter("follower_read_count")
        self._stale_bounces = self.metrics.counter("stale_bounce_count")
        self._lease_rejects = self.metrics.counter(
            "read_lease_reject_count")
        # resident index memory as a first-class signal: per-table
        # bloom-vs-phash byte split, refreshed whenever the probe
        # structures rebuild (exactly when the run set changes) and
        # scraped by tools/collector.py
        self._index_bloom_bytes = self.metrics.gauge("index_bloom_bytes")
        self._index_phash_bytes = self.metrics.gauge("index_phash_bytes")
        # slow-read dumps (parity: slow-query threshold app-env +
        # latency_tracer dumps); threshold configurable per table via
        # replica.slow_query_threshold_ms
        from pegasus_tpu.utils.latency_tracer import SlowQueryLog

        self.slow_log = SlowQueryLog()
        self._scan_log_key = f"scan_batch.{app_id}.{pidx}"
        self._get_log_key = f"point_get_batch.{app_id}.{pidx}"
        # per-table read-latency percentile (the collector aggregates
        # p50/p99 per table from these each round)
        self._read_latency = self.metrics.percentile("read_latency_ms")
        # env-driven remote manual compaction (one-shot trigger times)
        self._mc_trigger_seen = 0
        self._mc_running = False
        self._mc_max_running = MANUAL_COMPACT_MAX_RUNNING
        # whose ring and layer counters a traced run's spans land on
        # (the stub names its node, as it threads in clock_ns)
        self.trace_node = "local"
        # on-demand hotkey detection (parity: hotkey_collector.h:93 —
        # started via on_detect_hotkey; the request stream feeds capture
        # while a detection runs, else a None-check costs nothing)
        from pegasus_tpu.server.hotkey import HotkeyCollector

        self.hotkey_collectors = {"read": HotkeyCollector(),
                                  "write": HotkeyCollector()}
        # per-table workload shape stats (server/workload.py): op mix,
        # batch/value-size distributions, scan selectivity, hot-hashkey
        # share — recorded on a "workload" metric entity so the flight
        # recorder rings them and config-sync ships the summary to meta
        from pegasus_tpu.server.workload import WorkloadStats

        self.workload = WorkloadStats(app_id, pidx,
                                      self.hotkey_collectors)
        self.write_service.workload = self.workload
        # device-resident block cache: hot SST blocks stay in device memory
        # across scans (the HBM analogue of RocksDB's block cache), keyed by
        # (sst path, block offset) which is immutable per file
        self._device_block_cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._device_block_cache_cap = 1024
        # materialized keep-mask cache keyed by (block, now, pv): the
        # predicate is a deterministic function of immutable block content
        # + the CURRENT SECOND (epoch_now granularity) + the partition
        # static masks: (ckey, pv, validate, filter_key) -> bool[cap].
        # `now`-independent (TTL applies host-side at assembly), so a
        # block's mask lives as long as the block — the device evaluates
        # each block ONCE, proportional to data instead of requests or
        # elapsed seconds
        self._mask_cache: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._mask_cache_cap = 4096
        # value-filter keep masks keyed by (ckey, (type, pattern)):
        # the pushdown twin of _mask_cache — a block's value bytes are
        # immutable, so the vectorized region match runs once per
        # (block, pattern) lifetime, like the static key masks. Not
        # part of the device mask flavors: value heaps never ride the
        # device (placement class "scan_pushdown" routes host)
        self._vmask_cache: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        self._vmask_cache_cap = 8192
        # mask/device caches are shared with the MaskPrefresher thread
        self._mask_lock = threading.Lock()
        # scan flavors (validate, filter_key) seen recently: after a
        # flush/compaction replaces the SSTs, the prefresher re-evaluates
        # the NEW blocks for these flavors in the background
        self._warm_flavors: "OrderedDict[tuple, float]" = OrderedDict()
        self._warm_flavors_cap = 64
        # filter flavors seen recently: filter_key -> last wall_ts. A
        # filtered flavor joins the warm set on its SECOND occurrence
        # within the window — one-shot filter patterns must not multiply
        # background device work
        self._filter_seen: "OrderedDict[tuple, float]" = OrderedDict()
        self._filter_seen_cap = 256
        self._filter_seen_window = 30.0
        # per-table dynamic app-envs (parity: src/common/replica_envs.h:39-83
        # propagated through config-sync; here set via update_app_envs)
        self.app_envs: dict = {}
        self._deny_client = ""          # "", "all", "write", "read"
        self._write_throttle = None     # TokenBucket (reject mode)
        self._read_throttle = None
        self._default_ttl = 0
        self._compaction_rules = None   # compiled rules_filter
        # external publish subscribers (e.g. the resident mesh layer):
        # fanned out from _on_store_publish AFTER the server's own cache
        # eviction, and rewired for free across engine swaps because the
        # single lsm.on_publish slot always points at this server's
        # bound method
        self.publish_listeners: list = []
        self.install_engine(self.engine)

    def install_engine(self, engine: StorageEngine) -> None:
        """(Re)wire a storage engine into this server: write service,
        auto-compaction filter context, and the store publish hook that
        keeps the serving caches from pinning dead runs. Used at
        __init__ and by every path that swaps the engine wholesale
        (restore from backup, learner checkpoint apply)."""
        self.engine = engine
        ws = getattr(self, "write_service", None)
        if ws is not None:
            ws.engine = engine
        # auto-compaction runs with THIS partition's filter context
        # (TTL + stale-split + user rules), like every rocksdb
        # compaction runs the filter in the reference
        engine.auto_compact_ctx = lambda: {
            "default_ttl": self._default_ttl,
            "pidx": self.pidx,
            "partition_version": self.partition_version,
            "validate_hash": self.validate_partition_hash,
            "rules_filter": self._compaction_rules,
        }
        engine.lsm.on_publish = self._on_store_publish
        # write-through row-cache invalidation: every applied mutation
        # batch drops its keys from the node cache BEFORE the write is
        # acked, and an engine swap orphans every entry of the old store
        engine.on_write_keys = self._invalidate_rows
        ROW_CACHE.invalidate_gid((self.app_id, self.pidx))

    def _invalidate_rows(self, keys) -> None:
        lsm = self.engine.lsm
        ROW_CACHE.invalidate((self.app_id, self.pidx), lsm.store_uid,
                             lsm.generation, keys)

    def _on_store_publish(self, live_paths: set) -> None:
        """Store publish hook (every compaction publish, including the
        write path's auto-compaction): evict cache entries keyed by
        runs that just left the manifest, so idle-scan partitions stop
        pinning pre-compaction fds/mmaps/device blocks/disk until GC.
        Warm FLAVORS survive — the prefresher re-evaluates the NEW
        blocks' masks in the background before the next scan pays the
        round-trip."""
        with self._mask_lock:
            for mkey in [k for k in self._mask_cache
                         if k[0][0] not in live_paths]:
                del self._mask_cache[mkey]
            for vkey in [k for k in self._vmask_cache
                         if k[0][0] not in live_paths]:
                del self._vmask_cache[vkey]
            for ckey in [k for k in self._device_block_cache
                         if k[0] not in live_paths]:
                del self._device_block_cache[ckey]
        # per-second / per-generation caches: rebind wholesale (cheap to
        # rebuild, and rebinding is safe against concurrent readers on
        # the serving thread)
        self._live_cache = {}
        self._plan_cache = None
        self._point_cache = None
        self._plan_expired_cache = (None, {})
        ROW_CACHE.invalidate_gid((self.app_id, self.pidx))
        for fn in list(self.publish_listeners):
            try:
                fn(live_paths)
            except Exception:  # noqa: BLE001 - a subscriber must never
                pass           # break the publish path

    # env key -> (derived attr, reset-to-default parsed value); used when
    # a FULL env set arrives and a previously-set key is now absent
    # (del_app_envs/clear_app_envs must un-apply, not just stop updating)
    _ENV_DEFAULTS = {
        "replica.deny_client_request": ("_deny_client", ""),
        "replica.write_throttling": ("_write_throttle", None),
        "replica.read_throttling": ("_read_throttle", None),
        "default_ttl": ("_default_ttl", 0),
        "replica.slow_query_threshold_ms": ("_slow_threshold_ms", 20.0),
        "rocksdb.usage_scenario": ("_usage_scenario", "normal"),
        "user_specified_compaction": ("_compaction_rules", None),
        "manual_compact.max_concurrent_running_count": (
            "_mc_max_running", MANUAL_COMPACT_MAX_RUNNING),
    }

    def update_app_envs(self, envs: dict, full_set: bool = False) -> None:
        """Apply per-table dynamic settings (parity: replica_envs keys
        ROCKSDB_ENV_* / deny_client_request / *throttling /
        user_specified_compaction / default_ttl). Validation is two-phase:
        every value parses first, then everything applies — a malformed
        env never leaves half-applied state (parity:
        meta/app_env_validator rejects before propagation).

        `full_set=True` means `envs` is the table's COMPLETE env map
        (meta propagation / config sync): recognized keys that were set
        before but are absent now reset to their defaults, so
        del_app_envs/clear_app_envs converge on the replicas."""
        from pegasus_tpu.ops.compaction_rules import compile_rules
        from pegasus_tpu.utils.token_bucket import parse_throttle_env

        staged = []
        if full_set:
            for key, (attr, dflt) in self._ENV_DEFAULTS.items():
                if key in self.app_envs and key not in envs:
                    staged.append((attr, dflt))
        for key, value in envs.items():
            try:
                if key == "replica.deny_client_request":
                    staged.append(("_deny_client",
                                   value.split("*")[-1] if value else ""))
                elif key == "replica.write_throttling":
                    staged.append(("_write_throttle",
                                   parse_throttle_env(value)))
                elif key == "replica.read_throttling":
                    staged.append(("_read_throttle",
                                   parse_throttle_env(value)))
                elif key == "default_ttl":
                    staged.append(("_default_ttl", int(value)))
                elif key == "replica.slow_query_threshold_ms":
                    staged.append(("_slow_threshold_ms", float(value)))
                elif key == "rocksdb.usage_scenario":
                    if value not in ("normal", "prefer_write",
                                     "bulk_load"):
                        raise ValueError("unknown scenario")
                    staged.append(("_usage_scenario", value))
                elif key == "user_specified_compaction":
                    staged.append(("_compaction_rules",
                                   compile_rules(value) if value else None))
                elif key == "manual_compact.max_concurrent_running_count":
                    staged.append(("_mc_max_running",
                                   int(value) if value != ""
                                   else MANUAL_COMPACT_MAX_RUNNING))
                elif key == "manual_compact.once.trigger_time":
                    # accepts unix seconds (the reference's `date +%s`
                    # convention) or pegasus-epoch seconds; normalized
                    # to pegasus epoch (unambiguous: pegasus-epoch
                    # "now" stays far below PEGASUS_EPOCH_BEGIN)
                    ts = int(value) if value else 0
                    if ts > PEGASUS_EPOCH_BEGIN:
                        ts -= PEGASUS_EPOCH_BEGIN
                    staged.append(("_mc_once_trigger", ts))
            except Exception as exc:
                raise ValueError(f"invalid app-env {key}={value!r}: {exc}") \
                    from exc
        trigger = None
        for attr, parsed in staged:
            if attr == "_slow_threshold_ms":
                self.slow_log.threshold_ms = parsed
            elif attr == "_usage_scenario":
                self._apply_usage_scenario(parsed)
            elif attr == "_mc_once_trigger":
                trigger = parsed
            else:
                setattr(self, attr, parsed)
        if full_set:
            self.app_envs = dict(envs)
        else:
            self.app_envs.update(envs)
        if trigger is not None:
            # last: a trigger compacts under the ruleset and the bound
            # that came with it, whatever the map's order
            self._maybe_start_manual_compact(trigger)

    def _maybe_start_manual_compact(self, trigger_ts: int) -> None:
        """Env-driven remote manual compaction (parity:
        pegasus_manual_compact_service.cpp, the
        `manual_compact.once.trigger_time` replica env): a trigger time
        NEWER than the last one seen starts one asynchronous full
        compaction; config-sync re-deliveries of the same env value are
        idempotent, and a trigger arriving while one run is in flight
        is absorbed (the running compaction already covers it — the
        reference's queued/running distinction). A trigger older than
        the store's recorded compaction finish time is already
        satisfied — a restarted replica re-syncing a stale env must not
        re-compact (check_once_compact's trigger-vs-finish compare).

        Why a thread is safe against concurrent serving: writes race
        only the brief freeze-flush and publish cut-over (manual_compact
        merges OFF the write lock from an immutable snapshot and
        revalidates the run set at publish); point reads and
        per-request scans snapshot the run list once and read
        memtable-before-runs (the safe order against the publish
        sequence); the batch planners bracket their reads with the
        store generation and fall back to per-key/per-request serving
        on a torn read (plan_scan_batch / plan_get_batch); superseded
        runs are unlinked but their handles are released by GC so
        in-flight readers — including encrypted CipherFile stores —
        finish on the files they hold (lsm._publish_l1); dead-run cache
        entries evict through the store publish hook
        (_on_store_publish). Running it synchronously instead would
        hold the node lock (timers + dispatch share it) for the whole
        compaction — stalling FD beacons long enough to get the node
        declared dead.

        How many run at once in this process (a node; every node of an
        in-process SimCluster together) is bounded by its
        ManualCompactPool (`manual_compact.max_concurrent_running_count`,
        8 unless the table's env says otherwise): past the bound an
        accepted run waits for a slot, counted as running here, so the
        triggers that arrive meanwhile are absorbed all the same. An
        accepted trigger first compiles what the run will dispatch
        (_warm_manual_compact): the first one a process hears costs
        the delivering thread that compile, and no later moment beside
        traffic does."""
        from pegasus_tpu.storage.compact_governor import GOVERNOR

        # <=: a re-delivered trigger that already STARTED a run is
        # absorbed even when the trigger is future-dated relative to
        # the recorded finish time (an operator stamping a skewed-ahead
        # timestamp must not re-compact every sync round). A DEFERRED
        # trigger never advances trigger_seen, so its re-delivery
        # passes this guard and re-attempts under a fresh grant.
        if trigger_ts <= 0 or trigger_ts <= self._mc_trigger_seen:
            return
        if trigger_ts <= self.engine.lsm.compact_finish_time:
            # persisted in the manifest independently of the run set, so
            # an all-tombstone compaction still satisfies its trigger
            # across restarts
            self._mc_trigger_seen = trigger_ts
            return
        if self._mc_running:
            self._mc_trigger_seen = trigger_ts
            return
        if not GOVERNOR.heavy_allowed():
            # cluster stagger: another node holds the heavy-compaction
            # slot. DEFER, don't block — the trigger env is
            # re-delivered by every config-sync round, and trigger_seen
            # is deliberately NOT advanced, so the next delivery
            # re-attempts under a (possibly fresh) grant. The governor
            # records the demand so this node's report asks for a slot.
            GOVERNOR.note_deferred()
            return
        self._mc_trigger_seen = trigger_ts
        self._mc_running = True
        GOVERNOR.begin_heavy()

        def run() -> None:
            try:
                # a recent trigger doubles as the table-shared filter
                # timestamp: every partition of the table sees the same
                # env in the same sync round, so they all filter at
                # `now=trigger_ts` — identical params let the mesh
                # filter stage serve the whole table from ONE dispatch
                # (a stale/future-skewed trigger falls back to each
                # partition's own clock)
                shared_now = (trigger_ts
                              if abs(epoch_now() - trigger_ts) <= 600
                              else None)
                # the thread's own root: its self time lands on the
                # layer counters while a profiler session (or
                # sampling) is on, as a client op's does
                with tracing.background_root(self.trace_node, "compact.run"):
                    self.manual_compact(now=shared_now)
            finally:
                self._mc_running = False
                GOVERNOR.end_heavy()

        self._warm_manual_compact()
        # bounded per process (manual_compact.max_concurrent_running_count):
        # past the bound the run waits for a slot, _mc_running already
        # set, so triggers that arrive meanwhile are absorbed
        compact_governor.MANUAL_COMPACT_POOL.submit(
            self, run, f"manual-compact-{self.app_id}.{self.pidx}",
            limit=self._mc_max_running)

    def _warm_manual_compact(self) -> None:
        """On the thread that delivers an accepted trigger, before the
        run is handed to the pool: compile what the run, and the reads
        beside it, would otherwise compile where they first dispatch it
        beside traffic. That is the engine's filter programs
        (StorageEngine.warm_manual_compact) and, for every scan flavor
        serving has used, what a scan batch falls back to when a
        publish tears its plan: the static mask program over one block
        and over a stack (_encoded_static_mask declines a replaced
        run), and the per-request scan's predicate over a store with an
        overlay, at each of _validate_batch's buckets. Once a process
        for each set of shapes; a failure here is the compaction's to
        meet again, not the env's."""
        from pegasus_tpu.server.scan_coordinator import (
            _fetch_wave,
            stacked_block_submit,
        )

        try:
            self.engine.warm_manual_compact(
                default_ttl=self._default_ttl, pidx=self.pidx,
                partition_version=self.partition_version,
                validate_hash=self.validate_partition_hash,
                rules_filter=self._compaction_rules)
            run = next((r for r in list(self.engine.lsm.l1_runs)
                        if r.blocks), None)
            with self._mask_lock:
                flavors = list(self._warm_flavors)
            if run is None or not flavors:
                return
            bm = run.blocks[0]
            shapes = (bm.key_width, bm.count,
                      getattr(run, "codec", None) is not None,
                      self.partition_version >= 0,
                      tuple((v, fk[0], fk[2]) for v, fk in flavors))
            if shapes in _WARMED_MASKS:
                return
            blk = run.read_block(0)
            dev = self._device_cached_block((run.path, bm.offset), blk)
            row = (blk.key_at(0), b"", 0)
            now = epoch_now()
            for validate, filter_key in flavors:
                for height in (1, 2):   # one block; a padded stack
                    _fetch_wave([keep for _g, _c, keep in
                                 stacked_block_submit(
                                     [(i, dev, self.pidx)
                                      for i in range(height)],
                                     validate, self.partition_version,
                                     filter_key=filter_key)])
                hft, hfp, sft, sfp = filter_key
                cap = 256       # _validate_batch's smallest bucket
                while cap <= PREDICATE_BATCH:
                    self._validate_batch(
                        [row] * cap, now, FilterSpec.make(hft, hfp),
                        FilterSpec.make(sft, sfp), validate)
                    cap <<= 1
            _WARMED_MASKS.add(shapes)
        except Exception:  # noqa: BLE001 - see the docstring
            import traceback

            traceback.print_exc()

    def _apply_usage_scenario(self, scenario: str) -> None:
        """Parity: the usage-scenario dynamic tuning
        (pegasus_server_impl.cpp:1758; envs common/replica_envs.h:81):
        normal serves balanced; prefer_write buffers more before
        flushing; bulk_load buffers maximally and defers compaction
        entirely until the load finishes (ingest-behind style)."""
        eng = self.engine
        if scenario == "normal":
            eng.memtable_flush_trigger = 100_000
            eng.auto_compact = True
            eng.lsm._l0_trigger = 4
        elif scenario == "prefer_write":
            eng.memtable_flush_trigger = 250_000
            eng.auto_compact = True
            eng.lsm._l0_trigger = 8
        else:  # bulk_load
            eng.memtable_flush_trigger = 500_000
            eng.auto_compact = False

    def _gate(self, bucket, denied: bool) -> int:
        """Shared deny/throttle gate (parity: the gate stack at
        replica_2pc.cpp:117-207 and replica_throttle.cpp). Delay-mode
        throttling sleeps briefly (capped); reject-mode returns
        TryAgain."""
        if denied:
            return int(StorageStatus.TRY_AGAIN)
        if bucket is not None:
            delay_b, reject_b = bucket
            if reject_b is not None and not reject_b.try_consume():
                return int(StorageStatus.TRY_AGAIN)
            if reject_b is None and delay_b is not None:
                wait = delay_b.consume_or_delay()
                if wait > 0:
                    time.sleep(min(wait, 0.1))
        return int(StorageStatus.OK)

    def _write_gate(self) -> int:
        return self._gate(self._write_throttle,
                          self._deny_client in ("all", "write"))

    def _read_gate(self) -> int:
        return self._gate(self._read_throttle,
                          self._deny_client in ("all", "read"))

    def close(self) -> None:
        # an env-triggered compaction of this replica ends, or never
        # starts, before its engine closes under it
        if compact_governor.MANUAL_COMPACT_POOL.drain(self):
            self._mc_running = False
            compact_governor.GOVERNOR.end_heavy()
        self.engine.close()

    # ---- decree management (standalone mode) --------------------------

    def _next_decree(self) -> int:
        return self.engine.last_committed_decree + 1

    def _hash_gate(self, partition_hash: Optional[int]) -> int:
        """Reject requests whose routing hash no longer maps to this
        partition. The reference client carries its routing hash in the rpc
        header (rpc_message.h:81-126 `partition_hash`) and the replica
        rejects mismatches during/after a split so the client re-resolves
        (ERR_PARENT_PARTITION_MISUSED, replica_split_manager.h). Without
        this, a write that resolved under the old partition count but
        reached the parent after the count flip would be acked and then
        dropped as stale-half data. Callers on the write path must invoke
        this AFTER taking the write lock so the check is against the
        post-flip partition_version."""
        if partition_hash is None or not self.validate_partition_hash:
            return 0
        if (partition_hash & self.partition_version) != self.pidx:
            _SPLIT_FENCE_REJECTS.increment()
            return int(ErrorCode.ERR_PARENT_PARTITION_MISUSED)
        return 0

    # ---- write handlers ----------------------------------------------

    def on_put(self, key: bytes, user_data: bytes, ttl_seconds: int = 0,
               decree: Optional[int] = None,
               partition_hash: Optional[int] = None) -> int:
        gate = self._write_gate()
        if gate:
            return gate
        hc = self.hotkey_collectors["write"]
        if hc.state.value != "stopped":
            from pegasus_tpu.base.key_schema import restore_key

            hc.capture([restore_key(key)[0]])
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                return gate
            d = self._next_decree() if decree is None else decree
            expire_ts = expire_ts_from_ttl(ttl_seconds)
            self.cu.add_write(len(key) + len(user_data))
            return self.write_service.put(key, user_data, expire_ts, d)

    def on_remove(self, key: bytes, decree: Optional[int] = None,
                  partition_hash: Optional[int] = None) -> int:
        gate = self._write_gate()
        if gate:
            return gate
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                return gate
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(len(key))
            return self.write_service.remove(key, d)

    def on_multi_put(self, req: MultiPutRequest,
                     decree: Optional[int] = None,
                     partition_hash: Optional[int] = None) -> int:
        gate = self._write_gate()
        if gate:
            return gate
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                return gate
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(sum(len(kv.key) + len(kv.value)
                                  for kv in req.kvs) + len(req.hash_key))
            return self.write_service.multi_put(req, d)

    def on_multi_remove(self, req: MultiRemoveRequest,
                        decree: Optional[int] = None,
                        partition_hash: Optional[int] = None
                        ) -> Tuple[int, int]:
        gate = self._write_gate()
        if gate:
            return gate, 0
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                return gate, 0
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(len(req.hash_key)
                              + sum(len(sk) for sk in req.sort_keys))
            return self.write_service.multi_remove(req, d)

    def on_incr(self, req: IncrRequest,
                decree: Optional[int] = None,
                partition_hash: Optional[int] = None) -> IncrResponse:
        gate = self._write_gate()
        if gate:
            resp = IncrResponse()
            resp.error = gate
            return resp
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                resp = IncrResponse()
                resp.error = gate
                return resp
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(len(req.key))
            return self.write_service.incr(req, d)

    def on_check_and_set(self, req: CheckAndSetRequest,
                         decree: Optional[int] = None,
                         partition_hash: Optional[int] = None
                         ) -> CheckAndSetResponse:
        gate = self._write_gate()
        if gate:
            resp = CheckAndSetResponse()
            resp.error = gate
            return resp
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                resp = CheckAndSetResponse()
                resp.error = gate
                return resp
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(len(req.hash_key) + len(req.set_sort_key)
                              + len(req.set_value))
            return self.write_service.check_and_set(req, d)

    def on_check_and_mutate(self, req: CheckAndMutateRequest,
                            decree: Optional[int] = None,
                            partition_hash: Optional[int] = None
                            ) -> CheckAndMutateResponse:
        gate = self._write_gate()
        if gate:
            resp = CheckAndMutateResponse()
            resp.error = gate
            return resp
        with self._write_lock:
            gate = self._hash_gate(partition_hash)
            if gate:
                resp = CheckAndMutateResponse()
                resp.error = gate
                return resp
            d = self._next_decree() if decree is None else decree
            self.cu.add_write(len(req.hash_key) + sum(
                len(m.sort_key) + len(m.value) for m in req.mutate_list))
            return self.write_service.check_and_mutate(req, d)

    # ---- point reads --------------------------------------------------

    def on_get(self, key: bytes,
               partition_hash: Optional[int] = None) -> Tuple[int, bytes]:
        """Parity: on_get (pegasus_server_impl.cpp:418): expired records are
        NotFound and counted as abnormal reads.

        The solo fallback populates the SAME PerfContext fields as the
        batched path (LSMStore.get / SSTable.get tick the ambient
        context), so a solo slow-log entry stays field-comparable with
        a batched one — the observe_simple fallback attaches it."""
        from pegasus_tpu.utils import perf_context as perf

        hc = self.hotkey_collectors["read"]
        if hc.state.value != "stopped":
            from pegasus_tpu.base.key_schema import restore_key

            hc.capture([restore_key(key)[0]])
        gate = self._read_gate() or self._hash_gate(partition_hash)
        if gate:
            return gate, b""
        pc = perf.current()
        if pc is None:
            pc = perf.start("point_get")
        t0 = time.perf_counter()
        with perf.activate(pc):
            now = epoch_now()
            hit = self.engine.get(key)
            status = int(StorageStatus.OK)
            data = b""
            if hit is None:
                status = int(StorageStatus.NOT_FOUND)
            else:
                value, ets = hit
                if check_if_ts_expired(now, ets):
                    self._abnormal_reads.increment()
                    if pc is not None:
                        pc.expired_rows += 1
                    status = int(StorageStatus.NOT_FOUND)
                else:
                    data = extract_user_data(self.data_version, value)
                    self.cu.add_read(len(key) + len(data))
            if pc is not None:
                pc.ops += 1
                pc.keys_resolved += 1
                pc.rows_evaluated += 1
                pc.placement = pc.placement or "native"
                if status == int(StorageStatus.OK):
                    pc.rows_survived += 1
                    pc.bytes_returned += len(key) + len(data)
                from pegasus_tpu.utils.tracing import current_span

                sp = current_span()
                if sp is not None:
                    # the solo op's cost vector rides its dispatch
                    # span, same as the batched paths — `shell
                    # explain --from-trace` reads both shapes
                    perf.merge_span_perf(sp.tags, pc)
            self.workload.note_point(1, 1, [len(data)] if data else ())
            self.slow_log.observe_simple(
                f"point_get.{self.app_id}.{self.pidx}",
                (time.perf_counter() - t0) * 1000.0)
        return status, data

    def on_ttl(self, key: bytes,
               partition_hash: Optional[int] = None) -> Tuple[int, int]:
        """Returns (error, ttl_seconds); -1 = no TTL (parity on_ttl:1092)."""
        gate = self._read_gate() or self._hash_gate(partition_hash)
        if gate:
            return gate, 0
        now = epoch_now()
        hit = self.engine.get(key)
        if hit is None:
            return int(StorageStatus.NOT_FOUND), 0
        _, ets = hit
        if check_if_ts_expired(now, ets):
            self._abnormal_reads.increment()
            return int(StorageStatus.NOT_FOUND), 0
        return int(StorageStatus.OK), (ets - now) if ets > 0 else -1

    def on_batch_get(self, req: BatchGetRequest) -> BatchGetResponse:
        """Parity: on_batch_get (pegasus_server_impl.cpp:906)."""
        gate = self._read_gate()
        if gate:
            resp = BatchGetResponse()
            resp.error = gate
            return resp
        if self.validate_partition_hash:
            # per-key staleness gate: a client that grouped this batch
            # under a pre-split partition count must be told to re-resolve
            # (missing-with-OK would silently hide moved keys)
            from pegasus_tpu.base.key_schema import key_hash_parts

            for fk in req.keys:
                h = key_hash_parts(fk.hash_key, fk.sort_key)
                if (h & self.partition_version) != self.pidx:
                    resp = BatchGetResponse()
                    resp.error = int(
                        ErrorCode.ERR_PARENT_PARTITION_MISUSED)
                    return resp
        now = epoch_now()
        resp = BatchGetResponse()
        size = 0
        for fk in req.keys:
            key = generate_key(fk.hash_key, fk.sort_key)
            hit = self.engine.get(key)
            if hit is None:
                continue
            value, ets = hit
            if check_if_ts_expired(now, ets):
                self._abnormal_reads.increment()
                continue
            data = extract_user_data(self.data_version, value)
            resp.data.append(FullData(fk.hash_key, fk.sort_key, data))
            size += len(key) + len(data)
        self.cu.add_read(size)
        return resp

    # ---- batched point reads (the point-read twin of the batched scan
    # path: a flush of concurrent get / ttl / multi_get(sort_keys) /
    # batch_get requests resolves overlay hits host-side, locates base
    # keys through the per-generation point cache with ONE vectorized
    # probe per touched block, gathers every needed value with one
    # native call per block, and batches expired/CU accounting — the
    # plan/serve/finish split mirrors plan_scan_batch so the node-level
    # read coordinator can stack the gathers across partitions) --------

    POINT_CACHE_CAP = 65536
    # keys in one OP before its blocks are routed through the native
    # page gather (the co-located multi_get/batch_get shape); below it
    # a direct per-row heap slice beats the per-chunk ctypes call
    POINT_GATHER_MIN = 16

    def on_point_read_batch(self, ops) -> list:
        """Solo-node form of the batched point-read path. `ops`:
        [(op, args, partition_hash)] with op in get / ttl / multi_get
        (explicit sort keys) / batch_get; returns one result per op,
        byte-identical to the corresponding single-request handler."""
        return self.serve_get_batch(self.plan_get_batch(ops))

    def serve_get_batch(self, state) -> list:
        """Solo-form phases 2+3: gather this batch's co-located values
        (one native call per block via page.build_page) and assemble
        responses. The node-level read coordinator splits these phases
        apart to stack the gathers ACROSS partitions into one page."""
        from pegasus_tpu.server.page import build_page

        chunks = self.point_chunks(state)
        page = None
        if chunks:
            page, _size, _last = build_page(
                chunks, header_length(self.data_version))
        return self.finish_get_batch(state, page, 0)

    def plan_get_batch(self, ops, now: Optional[int] = None) -> dict:
        """Phase 1: gates + key decomposition + location.

        Per-op gates replicate the solo handlers exactly (per-request
        throttle consumption, per-key split-staleness for batch_get —
        batched through ops.predicates.host_key_hash_lo). Unique keys
        resolve once whatever the hot-key overlap: overlay first
        (memtable-before-runs, the safe order against a concurrent
        flush/compaction publish), then the per-generation point cache,
        then batched run/block bisects + vectorized block probes for
        the misses. A publish racing the plan (generation moved) makes
        the batch re-resolve every key through the per-key safe order
        instead of trusting the possibly-torn snapshot.

        A PerfContext (utils/perf_context.py) rides the flush: ambient
        while planning so the storage layer's block/sidecar hooks tick
        it, stashed in the state so finish_get_batch can complete it —
        an outer ambient context (shell explain) is reused instead."""
        from pegasus_tpu.utils import perf_context as perf

        pc = perf.current()
        if pc is None:
            pc = perf.start("point_get_batch")
        with perf.activate(pc):
            return self._plan_get_batch_inner(ops, now, pc)

    def _plan_get_batch_inner(self, ops, now, ppc) -> dict:
        from pegasus_tpu.storage.memtable import TOMBSTONE
        from pegasus_tpu.utils.latency_tracer import LatencyTracer

        t0 = time.perf_counter()
        # real stage chain for the batched point-read window (parity
        # with the write path's per-mutation tracer): slow_queries shows
        # WHERE a read stalled, and the stages double as annotations on
        # the active distributed-tracing span
        tracer = LatencyTracer(self._get_log_key)
        tracer.perf = ppc
        now = epoch_now() if now is None else now
        lsm = self.engine.lsm
        gen = lsm.generation  # read BEFORE the overlay/run snapshots
        results: list = [None] * len(ops)
        op_keys: list = [None] * len(ops)
        probes: List[Tuple[bytes, bool]] = []
        capture_hks: list = []
        wide = False  # any op wide enough for the native gather path
        hc = self.hotkey_collectors["read"]
        hc_running = hc.state.value != "stopped"
        for i, (op, args, ph) in enumerate(ops):
            if op in ("get", "ttl"):
                gate = self._read_gate() or self._hash_gate(ph)
                if gate:
                    results[i] = (gate, b"") if op == "get" else (gate, 0)
                    continue
                if op == "get" and hc_running:
                    capture_hks.append(restore_key(args)[0])
                op_keys[i] = (args,)
                probes.append((args, op == "get"))
            elif op == "multi_get":
                capture_hks.append(args.hash_key)
                # split-staleness gate per op, like the stub applies to
                # every solo wire read — a stale-routed multi_get must
                # tell the client to re-resolve, not silently miss
                gate = self._read_gate() or self._hash_gate(ph)
                if gate:
                    resp = MultiGetResponse()
                    resp.error = gate
                    results[i] = resp
                    continue
                if not args.hash_key:
                    resp = MultiGetResponse()
                    resp.error = int(StorageStatus.INVALID_ARGUMENT)
                    results[i] = resp
                    continue
                keys = tuple(generate_key(args.hash_key, sk)
                             for sk in args.sort_keys)
                op_keys[i] = keys
                want = not args.no_value
                if want and len(keys) >= self.POINT_GATHER_MIN:
                    wide = True
                probes.extend((k, want) for k in keys)
            elif op == "batch_get":
                gate = self._read_gate()
                if gate:
                    resp = BatchGetResponse()
                    resp.error = gate
                    results[i] = resp
                    continue
                if self.validate_partition_hash and args.keys:
                    # per-key staleness gate, one vectorized crc pass
                    # for the whole request (parity: on_batch_get)
                    from pegasus_tpu.ops.predicates import host_key_hash_lo

                    lo = host_key_hash_lo(
                        [fk.hash_key for fk in args.keys],
                        [fk.sort_key for fk in args.keys])
                    pv = np.uint32(self.partition_version & 0xFFFFFFFF)
                    if np.any((lo & pv) != np.uint32(self.pidx)):
                        resp = BatchGetResponse()
                        resp.error = int(
                            ErrorCode.ERR_PARENT_PARTITION_MISUSED)
                        results[i] = resp
                        continue
                keys = tuple(generate_key(fk.hash_key, fk.sort_key)
                             for fk in args.keys)
                op_keys[i] = keys
                if len(keys) >= self.POINT_GATHER_MIN:
                    wide = True
                probes.extend((k, True) for k in keys)
            else:
                # a ValueError so the RPC handler can answer
                # INVALID_PARAMETERS instead of dying unreplied
                raise ValueError(f"unknown point-read op {op!r}")
        if capture_hks:
            hc.capture(capture_hks)
        tracer.add_point("plan")

        memget = lsm.memtable.get
        l0 = lsm.l0
        runs = lsm.l1_runs
        pc = self._point_cache
        if pc is None or pc[0] is not lsm or pc[1] != gen:
            pc = self._point_cache = (lsm, gen, {})
        loc_cache = pc[2]
        gid = (self.app_id, self.pidx)
        suid = lsm.store_uid
        rc = ROW_CACHE
        rc_on = rc.enabled
        # invalidation epoch observed BEFORE any LSM read: admission
        # below hands it back, and the cache refuses the entry if a
        # write/publish invalidated this gid in between (the populate
        # race a plain write-through LRU would lose)
        rc_epoch = rc.epoch(gid) if rc_on else 0
        rc_hits = rc_misses = 0
        rc_cached = None
        if rc_on and probes:
            # ONE lock round against the node-shared cache serves the
            # whole flush (get_many); per-key acquisition would make
            # every partition's read flush contend on one lock
            ukeys = list(dict.fromkeys(k for k, _nv in probes))
            rc_cached = rc.get_many(gid, suid, gen, ukeys)
            rc_hits = len(rc_cached)
            rc_misses = len(ukeys) - rc_hits
        tracer.add_point("row_cache")
        uniq: dict = {}
        base_pending: list = []  # missed the row cache AND the overlay
        ov_hits = 0
        for key, _nv in probes:
            if key in uniq:
                continue
            if rc_cached is not None:
                ent = rc_cached.get(key)
                if ent is not None:
                    # cached rows carry the FULL encoded value + ets, so
                    # the serve path below is byte-identical to the
                    # overlay form; hot hashkeys never enter the LSM
                    uniq[key] = ("ov", ent[0], ent[1])
                    continue
            hit = memget(key)
            if hit is not None:
                ov_hits += 1
                uniq[key] = (None if hit[0] is TOMBSTONE
                             else ("ov", hit[0], hit[1]))
                continue
            uniq[key] = None  # placeholder until base resolution
            base_pending.append(key)
        tracer.add_point("overlay_merge")

        # disk-bound residue: ONE vectorized full-key hash pass feeds
        # BOTH sidecar probes — one native multi-filter bloom call for
        # filter-only tables, one native multi-index perfect-hash call
        # (`pegasus_phash_probe_multi`) for indexed tables — answering
        # the whole (key x L0-table / L1-run) candidacy AND location
        # matrix of the flush before any block is decoded. Definitive
        # "absent" cells skip the decode + bisect entirely; located
        # cells go straight to their (block, slot) row with no fence
        # bisect and no in-block search
        probe = None  # (matrix bytes, {id(table)->col}, {key->row base})
        pprobe = None  # (loc memoryview, hit-mask bytes, cols, mp, rows)
        bloom_useful = 0
        phash_useful = 0
        useful_box = [0, 0]  # [phash-pruned, phash-located]
        want_phash = phash_probe_enabled()
        if base_pending and (bloom_probe_enabled() or want_phash):
            mp, cols, pp, pcols = self._index_probes(lsm, gen,
                                                     want_phash)
            # ONE shared hash pass, and only when a probe will consume
            # it (bloom filters present with probing on, or any
            # indexed run) — a store with probing killed or no
            # structures must not pay the vectorized crc per flush
            if (mp is not None and bloom_probe_enabled()) \
                    or pp is not None:
                from pegasus_tpu.ops.predicates import bloom_key_hashes

                hashes = bloom_key_hashes(base_pending)
                key_row = {k: i for i, k in enumerate(base_pending)}
            if mp is not None and bloom_probe_enabled():
                mat = mp.probe(hashes)
                nfil = mp.n
                probe = (mat, cols,
                         {k: i * nfil for i, k in enumerate(
                             base_pending)})
            tracer.add_point("bloom")
            if pp is not None:
                pmat, pmask = pp.probe(hashes)
                pprobe = (pmat, pmask, pcols, pp, key_row)
            tracer.add_point("phash_probe")
        else:
            tracer.add_point("bloom")
            tracer.add_point("phash_probe")
        pending = base_pending
        if pending and l0:
            pending, bloom_useful = self._probe_l0(
                l0, pending, probe, uniq, pprobe, useful_box)
        if pending:
            still = []
            for key in pending:
                ent = loc_cache.get(key, _POINT_MISS)
                if ent is not _POINT_MISS:
                    uniq[key] = ent
                else:
                    still.append(key)
            pending = still
        if pending:
            bloom_useful += self._locate_points(runs, pending, uniq,
                                                probe, pprobe,
                                                useful_box)
        phash_useful = useful_box[0]
        if lsm.generation != gen:
            # a compaction/flush published mid-plan: the overlay misses
            # above may have raced the cut-over (key consumed from the
            # overlay before the run snapshot saw its new home) —
            # re-resolve every key through the per-key safe order and
            # cache nothing (neither locations nor rows)
            for key in list(uniq):
                hit = lsm.get(key)
                uniq[key] = (None if hit is None
                             else ("ov", hit[0], hit[1]))
        else:
            if pending and self._point_cache is pc:
                for key in pending:
                    loc_cache[key] = uniq[key]
                while len(loc_cache) > self.POINT_CACHE_CAP:
                    loc_cache.pop(next(iter(loc_cache)))
            if rc_on and base_pending:
                self._maybe_admit_rows(rc, gid, suid, gen, rc_epoch,
                                       base_pending, uniq, hc)
        if bloom_useful:
            self._bloom_useful.increment(bloom_useful)
            _STORAGE_BLOOM_USEFUL.increment(bloom_useful)
        if phash_useful:
            from pegasus_tpu.storage.phash import PHASH_USEFUL

            self._phash_useful.increment(phash_useful)
            PHASH_USEFUL.increment(phash_useful)
        if useful_box[1]:
            from pegasus_tpu.storage.phash import PHASH_HIT

            PHASH_HIT.increment(useful_box[1])
        if rc_hits:
            self._row_cache_hits.increment(rc_hits)
        if rc_misses:
            self._row_cache_misses.increment(rc_misses)
        if uniq:
            _POINT_KEYS_RESOLVED.increment(len(uniq))
        if ov_hits:
            _POINT_OVERLAY_HITS.increment(ov_hits)
        if ppc is not None:
            # the flush's cost vector, batched like the counters it
            # mirrors: ONE attribute pass per plan, never per key.
            # (blocks_decoded / block_cache_hit / bytes ticked ambient
            # by the storage layer during the probes above.)
            ppc.ops += len(ops)
            ppc.keys_resolved += len(uniq)
            ppc.overlay_hits += ov_hits
            ppc.runs_considered += len(l0) + len(runs)
            ppc.bloom_pruned += bloom_useful
            ppc.phash_pruned += phash_useful
            ppc.phash_located += useful_box[1]
            ppc.row_cache_hit += rc_hits
            ppc.row_cache_miss += rc_misses
            # point predicates are the "probe" workload class: host
            # native kernels, never a device round-trip
            ppc.placement = ppc.placement or "native"
        tracer.add_point("block_probe")
        return {"ops": ops, "results": results, "op_keys": op_keys,
                "uniq": uniq, "now": now, "t0": t0, "wide": wide,
                "tracer": tracer, "perf": ppc}

    def _index_probes(self, lsm, gen: int, want_phash: bool):
        """The run set's sidecar structures prepared for the one-call
        batched probes: (bloom MultiProbe, {id(table) -> filter col},
        PHashMultiProbe, {id(table) -> index col}). When phash probing
        is ON, indexed tables are EXCLUDED from the bloom probe — the
        perfect hash already answers candidacy (definitive absent) and
        location in one gather, so probing both structures would just
        double the per-pair work ("retiring the bloom+bisect pair" at
        probe time). Pure over the immutable run set (+ the phash
        flag) — rebuilt once per store generation, so the plan hot
        path pays one identity compare; the rebuild also refreshes the
        per-table resident-index-memory gauges."""
        c = self._index_probe_cache
        if c is not None and c[0] is lsm and c[1] == gen \
                and c[2] == want_phash:
            return c[3], c[4], c[5], c[6]
        from pegasus_tpu.storage.bloom import MultiProbe
        from pegasus_tpu.storage.phash import PHashMultiProbe

        filters = []
        cols: dict = {}
        indexes = []
        pcols: dict = {}
        bloom_bytes = phash_bytes = 0
        for t in list(lsm.l0) + list(lsm.l1_runs):
            if t.bloom is not None:
                bloom_bytes += t.bloom.bits.nbytes
            if t.phash is not None:
                phash_bytes += t.phash.mem_bytes()
            if want_phash and t.phash is not None:
                pcols[id(t)] = len(indexes)
                indexes.append(t.phash)
            elif t.bloom is not None:
                cols[id(t)] = len(filters)
                filters.append(t.bloom)
        mp = MultiProbe(filters) if filters else None
        pp = PHashMultiProbe(indexes) if indexes else None
        self._index_bloom_bytes.set(bloom_bytes)
        self._index_phash_bytes.set(phash_bytes)
        self._index_probe_cache = (lsm, gen, want_phash, mp, cols, pp,
                                   pcols)
        return mp, cols, pp, pcols

    def _probe_l0(self, l0, keys: list, probe, uniq: dict,
                  pprobe=None, useful_box=None) -> Tuple[list, int]:
        """Resolve `keys` through the L0 overlay newest-first (first
        table hit wins, the solo-get order). `probe` is the flush's
        precomputed bloom answer (matrix bytes, {id(table) -> column},
        {key -> row base}): a 0 cell is a definitive absent — no block
        is touched. `pprobe` is the perfect-hash LOCATION answer (u32
        loc memoryview, hit-mask bytes, {id(table) -> index column},
        multiprobe, {key -> row}): a 0 mask cell is definitive with
        zero block touches, and a hit cell's loc reads its (block,
        slot) row directly — one row compare (against a fingerprint
        collision) replaces the whole table bisect. Filterless,
        index-less tables (pre-filter files) gate on their
        first/last-key fences instead. Returns (unresolved keys,
        bloom-pruned count); phash-pruned probes accumulate into
        `useful_box[0]`."""
        useful = 0
        p_useful = 0
        p_hits = 0
        if probe is not None:
            mat, cols, key_row = probe
        else:
            mat = cols = key_row = None
        if pprobe is not None:
            pmat, pmask, pcols, pp, pkey_row = pprobe
            npt = pp.n
        else:
            pmat = pmask = pcols = pp = pkey_row = None
            npt = 0
        # (table, filter column | None, index column | None, index
        # geometry) resolved once per flush — id()+dict (and per-hit
        # attribute walks) per (key, table) pair was measurable at
        # depth 16
        pairs = [(t, cols.get(id(t)) if cols is not None else None,
                  pcols.get(id(t)) if pcols is not None else None,
                  t.phash.slot_bits if t.phash is not None else 0)
                 for t in l0]
        out_keys = []
        for k in keys:
            row = key_row[k] if key_row is not None else 0
            prow = pkey_row[k] * npt if pkey_row is not None else 0
            resolved = False
            for table, col, pcol, sb in pairs:
                if pcol is not None:
                    cell = prow + pcol
                    if not pmask[cell]:
                        p_useful += 1
                        continue
                    loc = pmat[cell]
                    bi = loc >> sb
                    slot = loc & ((1 << sb) - 1)
                    if bi >= len(table.blocks) \
                            or slot >= table.blocks[bi].count:
                        h = table.get(k)  # corrupt loc: bisect path
                    else:
                        blk = table.read_block(bi)
                        if blk.key_at(slot) != k:
                            p_useful += 1  # fp collision: absent here
                            continue
                        p_hits += 1
                        h = ((None, 0) if blk.is_tombstone(slot)
                             else (blk.value_at(slot),
                                   int(blk.expire_ts[slot])))
                elif col is not None:
                    if not mat[row + col]:
                        useful += 1
                        continue
                    h = table.get(k)
                else:
                    fk = table.first_key
                    if fk is None or k < fk or k > table.last_key:
                        continue
                    h = table.get(k)
                if h is not None:
                    uniq[k] = (None if h[0] is None
                               else ("ov", h[0], h[1]))
                    resolved = True
                    break
            if not resolved:
                out_keys.append(k)
        if useful_box is not None:
            useful_box[0] += p_useful
            useful_box[1] += p_hits
        return out_keys, useful

    def _maybe_admit_rows(self, rc, gid, suid: int, gen: int, epoch: int,
                          keys: list, uniq: dict, hc) -> None:
        """Offer this flush's base-resolved rows (L0/L1 hits — overlay
        hits are already memory-speed) to the node row cache. Admission
        is repeat-gated inside the cache; a FINISHED hotkey detection
        fast-admits its hashkey; `epoch` voids the admission if any
        write invalidated this gid since planning began. One lock
        round for the touch gate, one for the inserts — never per
        key."""
        cands = [k for k in keys if uniq.get(k)]
        if not cands:
            return  # absent / tombstone rows are never cached
        hot = hc.hot_hash_key()
        fast = ()
        if hot is not None:
            fast = {k for k in cands if restore_key(k)[0] == hot}
        granted = rc.note_and_check_many(gid, cands, fast)
        if not granted:
            return
        items = []
        for key in granted:
            ent = uniq[key]
            if ent[0] == "ov":
                value, ets = ent[1], int(ent[2])
            else:
                _t, blk, row = ent
                value = blk.value_at(row)
                ets = int(blk.expire_ts[row])
            items.append((key, value, ets))
        rc.admit_many(gid, suid, gen, items, epoch=epoch)

    def _locate_points(self, runs, keys: list, out: dict,
                       probe=None, pprobe=None, useful_box=None) -> int:
        """Batch-locate keys in the non-overlapping L1 runs: bisect each
        key to its run, then answer each candidacy from the flush's
        precomputed sidecar matrices. An INDEXED run (`pprobe`, the
        perfect-hash location matrix) answers candidacy and location
        in the same cell: ABSENT is definitive with zero block
        touches, a located cell goes straight to its (block, slot) row
        — no block-fence bisect, no searchsorted — and the row's key
        is verified in one vectorized compare per touched block
        (ops.predicates.phash_verify_rows) to reject fingerprint
        collisions. Filter-only runs keep the bloom cell + bisect +
        probe_rows path; structure-less runs bisect unconditionally.
        out[key] = ("l1", blk, row) | None (absent or tombstone — L1
        is the last level). Returns the bloom-pruned count;
        phash-pruned probes accumulate into `useful_box[0]`."""
        import bisect as _b

        from pegasus_tpu.server.page import probe_rows

        if not runs:
            for key in keys:
                out[key] = None
            return 0
        if probe is not None:
            mat, cols, key_row = probe
        else:
            mat = cols = key_row = None
        if pprobe is not None:
            pmat, pmask, pcols, pp, pkey_row = pprobe
            npt = pp.n
        else:
            pmat = pmask = pcols = pp = pkey_row = None
            npt = 0
        run_last = [r.last_key or b"" for r in runs]
        by_run: "OrderedDict[int, list]" = OrderedDict()
        for key in keys:
            ri = _b.bisect_left(run_last, key)
            if ri >= len(runs) or (runs[ri].first_key or b"") > key:
                out[key] = None
                continue
            by_run.setdefault(ri, []).append(key)
        useful = 0
        p_useful = 0
        by_block: "OrderedDict[tuple, list]" = OrderedDict()
        by_slot: "OrderedDict[tuple, list]" = OrderedDict()
        for ri, ks in by_run.items():
            run = runs[ri]
            pcol = pcols.get(id(run)) if pcols is not None else None
            if pcol is not None:
                sb = run.phash.slot_bits
                sm = (1 << sb) - 1
                nblocks = len(run.blocks)
                blocks = run.blocks
                for k in ks:
                    cell = pkey_row[k] * npt + pcol
                    if not pmask[cell]:
                        p_useful += 1
                        out[k] = None
                        continue
                    loc = pmat[cell]
                    bi = loc >> sb
                    slot = loc & sm
                    if bi >= nblocks or slot >= blocks[bi].count:
                        # corrupt loc: this key takes the bisect path
                        bj = run._block_for_key(k)
                        if bj is None:
                            out[k] = None
                        else:
                            by_block.setdefault((ri, bj),
                                                []).append(k)
                        continue
                    by_slot.setdefault((ri, bi), []).append((k, slot))
                continue
            col = cols.get(id(run)) if cols is not None else None
            if col is not None:
                kept = []
                for k in ks:
                    if mat[key_row[k] + col]:
                        kept.append(k)
                    else:
                        useful += 1
                        out[k] = None
                ks = kept
            for key in ks:
                bi = run._block_for_key(key)
                if bi is None:
                    out[key] = None
                    continue
                by_block.setdefault((ri, bi), []).append(key)
        if by_slot:
            from pegasus_tpu.ops.predicates import phash_verify_rows
        for (ri, bi), pairs in by_slot.items():
            # located rows: ONE vectorized key-verify per touched
            # block (the fingerprint-collision rejector); hits were
            # going to read this block for their values anyway
            blk = runs[ri].read_block(bi)
            rows = np.fromiter((s for _k, s in pairs), dtype=np.int64,
                               count=len(pairs))
            ok = phash_verify_rows(blk.keys, blk.key_len, rows,
                                   [k for k, _s in pairs])
            verified = 0
            for (key, slot), good in zip(pairs, ok):
                if not good:
                    p_useful += 1  # fp collision: definitively absent
                    out[key] = None
                    continue
                verified += 1
                if blk.is_tombstone(slot):
                    out[key] = None
                else:
                    out[key] = ("l1", blk, slot)
            if useful_box is not None:
                useful_box[1] += verified
        for (ri, bi), ks in by_block.items():
            blk = runs[ri].read_block(bi)
            for key, row in zip(ks, probe_rows(blk, ks)):
                row = int(row)
                if row < 0 or blk.is_tombstone(row):
                    out[key] = None
                else:
                    out[key] = ("l1", blk, row)
        if useful_box is not None:
            useful_box[0] += p_useful
        return useful

    def point_chunks(self, state) -> list:
        """Phase 2: this batch's L1 value-gather work as [(blk,
        ascending rows)] chunks for one page.build_page call (one
        native gather per block). Only alive rows some op wants the
        VALUE of are gathered; TTL-only probes read expire_ts straight
        from the block column. The node-level coordinator concatenates
        these chunks ACROSS partitions into a single page; `base` at
        finish maps this state's ordinals into it."""
        if not state["wide"]:
            # the common all-singleton flush: nothing can reach the
            # gather threshold, so skip the grouping pass entirely
            state["page_pos"] = {}
            state["chunk_rows"] = 0
            return []
        now = state["now"]
        uniq = state["uniq"]
        gmin = self.POINT_GATHER_MIN
        by_block: "OrderedDict[int, list]" = OrderedDict()
        blocks: dict = {}
        seen: set = set()
        for i, (op, args, _ph) in enumerate(state["ops"]):
            keys = state["op_keys"][i]
            # only wide ops (the co-located multi_get/batch_get shape)
            # reach the native gather: a flush of independent gets
            # scatters 1-2 rows per block, where a direct heap slice
            # beats the per-chunk ctypes call
            if (state["results"][i] is not None or keys is None
                    or len(keys) < gmin or op == "ttl"
                    or (op == "multi_get" and args.no_value)):
                continue
            for key in keys:
                if key in seen:
                    continue
                seen.add(key)
                ent = uniq.get(key)
                if not ent or ent[0] != "l1":
                    continue
                _tag, blk, row = ent
                # wide ops touch many rows per block: one per-second
                # vectorized alive mask (shared with the scan path's
                # prepare_serve cache) beats per-row scalar checks
                if not blk.alive_mask(now)[row]:
                    continue  # expired rows are never gathered
                bid = id(blk)
                blocks[bid] = blk
                by_block.setdefault(bid, []).append((row, key))
        chunks = []
        pos = 0
        page_pos: dict = {}
        for bid, entries in by_block.items():
            entries.sort()
            rows = np.fromiter((r for r, _k in entries), dtype=np.int64,
                               count=len(entries))
            for j, (_r, key) in enumerate(entries):
                page_pos[key] = pos + j
            chunks.append((blocks[bid], rows))
            pos += len(entries)
        state["page_pos"] = page_pos
        state["chunk_rows"] = pos
        return chunks

    def finish_get_batch(self, state, page=None, base: int = 0) -> list:
        """Phase 3: assemble per-op responses byte-identical to the
        solo handlers, with batched expired/CU accounting (one counter
        touch per flush). `page`/`base`: the (possibly cross-partition)
        build_page result and this state's first row in it."""
        from pegasus_tpu.utils import perf_context as perf

        with perf.activate(state.get("perf")):
            return self._finish_get_batch_inner(state, page, base)

    def _finish_get_batch_inner(self, state, page, base: int) -> list:
        ops = state["ops"]
        results = state["results"]
        op_keys = state["op_keys"]
        uniq = state["uniq"]
        now = state["now"]
        tracer = state.get("tracer")
        if tracer is not None:
            # the (possibly cross-partition) value gather ran between
            # the phases — the time since block_probe is decode/gather
            tracer.add_point("decode")
        page_pos = state.get("page_pos") or {}
        dv = self.data_version
        hdr = header_length(dv)
        expired_total = 0
        cu_total = 0
        looked = 0
        survived = 0
        bytes_out = 0
        vsizes: list = []  # bounded value-size sample (workload stats)

        def lookup(key, want_value):
            """(found, data, ets) with solo-handler TTL semantics."""
            nonlocal expired_total, looked
            looked += 1
            ent = uniq.get(key)
            if ent is None:
                return False, b"", 0
            if ent[0] == "ov":
                _t, value, ets = ent
                if check_if_ts_expired(now, ets):
                    expired_total += 1
                    return False, b"", 0
                return True, (extract_user_data(dv, value)
                              if want_value else b""), ets
            _t, blk, row = ent
            # per-second TTL mask reuse: when the SCAN path already
            # built this block's alive mask for the current second
            # (Block.alive_mask caches one per second), a point probe
            # reads one cell of it instead of re-deriving expiry
            cmp = getattr(blk, "_cmp", None)  # unset slot on cold blocks
            if cmp is not None and cmp[0] == now:
                alive = bool(cmp[1][row])
                ets = int(blk.expire_ts[row])
            else:
                ets = int(blk.expire_ts[row])
                alive = not check_if_ts_expired(now, ets)
            if not alive:
                expired_total += 1
                return False, b"", 0
            if not want_value:
                return True, b"", ets
            pos = page_pos.get(key)
            if pos is not None:
                return True, page.value_at(base + pos), ets
            # sparse block: direct header-stripped heap slice (same
            # bytes as extract_user_data over Block.value_at)
            vo = blk.value_offs
            heap = blk.value_heap
            v0 = int(vo[row]) + hdr
            v1 = int(vo[row + 1])
            data = (heap[v0:v1].tobytes()
                    if isinstance(heap, np.ndarray) else heap[v0:v1])
            return True, data, ets

        out = []
        for i, (op, args, _ph) in enumerate(ops):
            if results[i] is not None:
                out.append(results[i])
                continue
            if op == "get":
                key = op_keys[i][0]
                found, data, _ets = lookup(key, True)
                if not found:
                    out.append((int(StorageStatus.NOT_FOUND), b""))
                else:
                    survived += 1
                    bytes_out += len(key) + len(data)
                    if len(vsizes) < 8:
                        vsizes.append(len(data))
                    cu_total += cu_units(len(key) + len(data))
                    out.append((int(StorageStatus.OK), data))
            elif op == "ttl":
                found, _data, ets = lookup(op_keys[i][0], False)
                if not found:
                    out.append((int(StorageStatus.NOT_FOUND), 0))
                else:
                    survived += 1
                    out.append((int(StorageStatus.OK),
                                (ets - now) if ets > 0 else -1))
            elif op == "multi_get":
                resp = MultiGetResponse()
                want = not args.no_value
                size = 0
                for sk, key in zip(args.sort_keys, op_keys[i]):
                    found, data, _ets = lookup(key, want)
                    if not found:
                        continue
                    survived += 1
                    if len(vsizes) < 8:
                        vsizes.append(len(data))
                    resp.kvs.append(KeyValue(sk, data))
                    size += len(sk) + len(data)
                cu_total += cu_units(size)
                bytes_out += size
                resp.error = int(StorageStatus.OK)
                out.append(resp)
            else:  # batch_get
                resp = BatchGetResponse()
                size = 0
                for fk, key in zip(args.keys, op_keys[i]):
                    found, data, _ets = lookup(key, True)
                    if not found:
                        continue
                    survived += 1
                    if len(vsizes) < 8:
                        vsizes.append(len(data))
                    resp.data.append(FullData(fk.hash_key, fk.sort_key,
                                              data))
                    size += len(key) + len(data)
                cu_total += cu_units(size)
                bytes_out += size
                out.append(resp)
        if expired_total:
            self._abnormal_reads.increment(expired_total)
        self.cu.add_read_units(cu_total)
        self.workload.note_point(len(ops), len(uniq), vsizes)
        pc = state.get("perf")
        if pc is not None:
            pc.rows_evaluated += looked
            pc.rows_survived += survived
            pc.expired_rows += expired_total
            pc.bytes_returned += bytes_out
            sp = tracer.span if tracer is not None else None
            if sp is not None:
                # the cost vector rides the op's span: `shell trace`
                # (and explain --from-trace) shows counts, not just
                # durations. MERGED, not assigned — a batched carrier
                # span collects every partition's flush vector
                from pegasus_tpu.utils import perf_context as perf

                perf.merge_span_perf(sp.tags, pc)
        elapsed_ms = (time.perf_counter() - state["t0"]) * 1000.0
        self._read_latency.set(elapsed_ms)
        if tracer is not None:
            tracer.add_point("finish")
            # the full stage chain (plan/bloom/block_probe/decode/
            # finish) lands in the slow ring — WHERE the read stalled,
            # not just that it did
            self.slow_log.observe(tracer,
                                  {"ops": len(ops), "keys": len(uniq)})
        elif elapsed_ms >= self.slow_log.threshold_ms:
            self.slow_log.observe_simple(
                self._get_log_key, elapsed_ms,
                {"ops": len(ops), "keys": len(uniq)})
        return out

    # ---- ranged reads (the device-batched hot path) -------------------

    def _batched_scan(
        self,
        start_key: bytes,
        stop_key: Optional[bytes],
        now: int,
        hash_filter: FilterSpec,
        sort_filter: FilterSpec,
        validate_hash: bool,
        limiter: RangeReadLimiter,
        max_records: int,
        max_bytes: int,
        reverse: bool = False,
        with_values: bool = True,
        value_filter=None,
        pd_stats=None,
        one_page: bool = True,
    ) -> Tuple[List[Tuple[bytes, bytes, int]], bool, Optional[bytes]]:
        """Core ranged read: iterate candidates, device-validate in batches.

        Returns (records, exhausted, resume_key) where records are
        (key, user_data, expire_ts) triples that passed every predicate,
        exhausted means the range completed, and resume_key is where a
        follow-up should continue when not exhausted.

        `value_filter`: normalized (type, pattern) pushdown value
        predicate ANDed into the keep mask; `pd_stats` accumulates its
        "pruned" count (rows key-alive but value-rejected). `one_page`:
        no later page of this read will follow (a scanner that may page
        on passes False).
        """
        sorted_runs = None if reverse else self.engine.lsm.sorted_runs()
        if sorted_runs is not None:
            return self._columnar_scan(sorted_runs, start_key, stop_key,
                                       now, hash_filter, sort_filter,
                                       validate_hash, limiter, max_records,
                                       max_bytes, with_values,
                                       value_filter, pd_stats, one_page)

        out: List[Tuple[bytes, bytes, int]] = []
        out_bytes = 0
        it = self.engine.iterate(start_key, stop_key, reverse)
        exhausted = True
        resume_key: Optional[bytes] = None
        while True:
            batch: List[Tuple[bytes, bytes, int]] = []
            for key, value, ets in it:
                batch.append((key, value, ets))
                limiter.add_count()
                if len(batch) >= PREDICATE_BATCH or not limiter.valid():
                    break
            if not batch:
                break
            keep = self._validate_batch(batch, now, hash_filter, sort_filter,
                                        validate_hash)
            stop_early = False
            for i, (key, value, ets) in enumerate(batch):
                if not keep[i]:
                    continue
                if value_filter is not None:
                    ud = extract_user_data(self.data_version, value)
                    if not host_match_filter(ud, value_filter[0],
                                             value_filter[1]):
                        if pd_stats is not None:
                            pd_stats["pruned"] = \
                                pd_stats.get("pruned", 0) + 1
                        continue
                    data = ud if with_values else b""
                else:
                    data = (extract_user_data(self.data_version, value)
                            if with_values else b"")
                out.append((key, data, ets))
                out_bytes += len(key) + len(data)
                if ((max_records > 0 and len(out) >= max_records)
                        or (max_bytes > 0 and out_bytes >= max_bytes)):
                    resume_key = _after(key) if not reverse else key
                    stop_early = True
                    break
            if stop_early:
                exhausted = False
                break
            if not limiter.valid():
                last_key = batch[-1][0]
                resume_key = _after(last_key) if not reverse else last_key
                exhausted = False
                break
            if len(batch) < PREDICATE_BATCH:
                break
        return out, exhausted, resume_key

    def _columnar_scan(
        self,
        sorted_runs,
        start_key: bytes,
        stop_key: Optional[bytes],
        now: int,
        hash_filter: FilterSpec,
        sort_filter: FilterSpec,
        validate_hash: bool,
        limiter: RangeReadLimiter,
        max_records: int,
        max_bytes: int,
        with_values: bool,
        value_filter=None,
        pd_stats=None,
        one_page: bool = True,
    ) -> Tuple[List[Tuple[bytes, bytes, int]], bool, Optional[bytes]]:
        """Fast path: the store is a sequence of non-overlapping sorted L1
        runs with no overlay, so SST blocks stream columnar through the
        CACHED static device predicate — the TPU-first replacement for
        the reference's per-record iterator loop. The static mask
        (filters + partition-hash, `now`-independent) is evaluated on
        device once per block lifetime; this scan combines it with TTL
        expiry host-side (one vectorized AND over the expire_ts column)
        and materializes only survivors per record. Blocks come in
        look-ahead windows (_scan_windows) whose mask misses go to the
        device in ONE stacked wave (a cold cache after compaction would
        otherwise pay one serialized round-trip PER block), then
        assemble host-side.
        """
        from pegasus_tpu.ops.predicates import host_alive_mask

        out: List[Tuple[bytes, bytes, int]] = []
        out_bytes = 0
        exhausted = True
        resume_key: Optional[bytes] = None
        filter_key = hash_filter.key + sort_filter.key
        with self._mask_lock:
            self._register_flavor(validate_hash, filter_key,
                                  time.monotonic())

        for window, after in _scan_windows(sorted_runs, start_key,
                                           stop_key, limiter):
            keeps = self._static_keep_window(
                window, validate_hash, filter_key,
                fill=None if one_page else after)
            stopped = False
            for (ckey, blk, lo, hi), static_keep in zip(window, keeps):
                n = blk.count
                ets = blk.expire_ts
                alive = host_alive_mask(ets, now)
                expired = int(np.count_nonzero(~alive[lo:hi]))
                if expired:
                    self._abnormal_reads.increment(expired)
                keep = static_keep[:n] & alive
                if value_filter is not None:
                    # the pushdown value leg joins the mask algebra:
                    # cached per (block, pattern) like the static keep
                    vmask = self._value_mask(ckey, blk, value_filter)
                    before = int(np.count_nonzero(keep[lo:hi]))
                    keep = keep & vmask[:n]
                    if pd_stats is not None:
                        pd_stats["pruned"] = (
                            pd_stats.get("pruned", 0) + before
                            - int(np.count_nonzero(keep[lo:hi])))
                stop_early = False
                for i in np.flatnonzero(keep[lo:hi]):
                    idx = lo + int(i)
                    key = blk.key_at(idx)
                    data = (extract_user_data(self.data_version,
                                              blk.value_at(idx))
                            if with_values else b"")
                    out.append((key, data, int(ets[idx])))
                    out_bytes += len(key) + len(data)
                    if ((max_records > 0 and len(out) >= max_records)
                            or (max_bytes > 0 and out_bytes >= max_bytes)):
                        resume_key = _after(key)
                        stop_early = True
                        break
                if stop_early or not limiter.valid():
                    if not stop_early:
                        resume_key = _after(blk.key_at(n - 1))
                    exhausted = False
                    stopped = True
                    break
            if stopped:
                break
        return out, exhausted, resume_key

    def _validate_batch(self, batch: List[Tuple[bytes, bytes, int]],
                        now: int, hash_filter: FilterSpec,
                        sort_filter: FilterSpec,
                        validate_hash: bool) -> np.ndarray:
        keys = [b[0] for b in batch]
        ets = [b[2] for b in batch]
        # bucket the batch capacity to a power of two: arbitrary merge-path
        # batch sizes would otherwise each compile their own XLA program
        cap = 256
        while cap < len(batch):
            cap <<= 1
        block = build_record_block(keys, ets, capacity=cap)
        masks = scan_block_predicate(
            block, now, hash_filter=hash_filter, sort_filter=sort_filter,
            validate_hash=validate_hash, pidx=self.pidx,
            partition_version=self.partition_version)
        expired = int(np.asarray(masks.expired).sum())
        if expired:
            self._abnormal_reads.increment(expired)
        return np.asarray(masks.keep)

    def on_multi_get(self, req: MultiGetRequest) -> MultiGetResponse:
        """Parity: on_multi_get (pegasus_server_impl.cpp:496)."""
        from pegasus_tpu.utils import perf_context as perf

        self.hotkey_collectors["read"].capture([req.hash_key])
        t0 = time.perf_counter()
        pc = perf.current()
        if pc is None:
            pc = perf.start("multi_get")
        try:
            with perf.activate(pc):
                resp = self._on_multi_get(req)
                if pc is not None:
                    pc.ops += 1
                    pc.rows_survived += len(resp.kvs)
                    pc.placement = pc.placement or "native"
                    from pegasus_tpu.utils.tracing import current_span

                    sp = current_span()
                    if sp is not None:
                        perf.merge_span_perf(sp.tags, pc)
                return resp
        finally:
            elapsed_ms = (time.perf_counter() - t0) * 1000.0
            self._read_latency.set(elapsed_ms)
            with perf.activate(pc):
                self.slow_log.observe_simple(
                    f"multi_get.{self.app_id}.{self.pidx}", elapsed_ms,
                    {"hash_key": req.hash_key.decode(errors="replace")})

    def _on_multi_get(self, req: MultiGetRequest) -> MultiGetResponse:
        gate = self._read_gate()
        if gate:
            resp = MultiGetResponse()
            resp.error = gate
            return resp
        now = epoch_now()
        resp = MultiGetResponse()
        if not req.hash_key:
            resp.error = int(StorageStatus.INVALID_ARGUMENT)
            return resp

        # explicit sort keys -> point lookups (reference uses DB::MultiGet)
        if req.sort_keys:
            size = 0
            for sk in req.sort_keys:
                key = generate_key(req.hash_key, sk)
                hit = self.engine.get(key)
                if hit is None:
                    continue
                value, ets = hit
                if check_if_ts_expired(now, ets):
                    self._abnormal_reads.increment()
                    continue
                data = (b"" if req.no_value
                        else extract_user_data(self.data_version, value))
                resp.kvs.append(KeyValue(sk, data))
                size += len(sk) + len(data)
            self.cu.add_read(size)
            self.workload.note_point(1, len(req.sort_keys),
                                     [len(kv.value)
                                      for kv in resp.kvs[:8]])
            resp.error = int(StorageStatus.OK)
            return resp

        # range mode over [start_sortkey, stop_sortkey]
        start_key = generate_key(req.hash_key, req.start_sortkey)
        if not req.start_inclusive:
            start_key = _after(start_key)
        if req.stop_sortkey:
            stop_key = generate_key(req.hash_key, req.stop_sortkey)
            if req.stop_inclusive:
                stop_key = _after(stop_key)
        else:
            stop_key = generate_next_bytes(req.hash_key)
        if stop_key and start_key >= stop_key:
            resp.error = int(StorageStatus.OK)
            return resp

        limiter = RangeReadLimiter(clock_ns=self.clock_ns)
        records, exhausted, resume_key = self._batched_scan(
            start_key, stop_key or None, now,
            FilterSpec.none(),
            FilterSpec.make(req.sort_key_filter_type,
                            req.sort_key_filter_pattern),
            validate_hash=False, limiter=limiter,
            max_records=req.max_kv_count, max_bytes=req.max_kv_size,
            reverse=req.reverse, with_values=not req.no_value)
        size = 0
        for key, data, ets in records:
            _, sk = restore_key(key)
            resp.kvs.append(KeyValue(sk, data))
            size += len(sk) + len(data)
        if req.reverse:
            resp.kvs.reverse()  # response is ascending by sort key
        self.cu.add_read(size)
        # range-mode multi_get is the dominant ranged-read shape: its
        # examined-vs-returned ratio feeds the table's selectivity
        # profile like every other scan
        self.workload.note_scan(1, limiter.iteration_count,
                                len(records))
        resp.error = (int(StorageStatus.OK) if exhausted
                      else int(StorageStatus.INCOMPLETE))
        if (not exhausted and not req.reverse
                and resume_key is not None):
            # even a fully-filtered page (e.g. a long expired run) stays
            # resumable: the follow-up starts at this sort key
            resp.resume_sort_key = restore_key(resume_key)[1]
        return resp

    def on_sortkey_count(self, hash_key: bytes) -> Tuple[int, int]:
        """Parity: on_sortkey_count (pegasus_server_impl.cpp:1018)."""
        gate = self._read_gate()
        if gate:
            return gate, 0
        now = epoch_now()
        start_key = generate_key(hash_key, b"")
        stop_key = generate_next_bytes(hash_key)
        limiter = RangeReadLimiter(clock_ns=self.clock_ns)
        records, exhausted, _ = self._batched_scan(
            start_key, stop_key or None, now, FilterSpec.none(),
            FilterSpec.none(), validate_hash=False, limiter=limiter,
            max_records=-1, max_bytes=-1, with_values=False)
        if not exhausted:
            return int(StorageStatus.INCOMPLETE), len(records)
        return int(StorageStatus.OK), len(records)

    # ---- scan pushdown (ops/pushdown.py) ------------------------------

    def _pushdown_of(self, req: GetScannerRequest):
        """The request's PushdownSpec when this server will evaluate it,
        else None: no spec, an empty spec (nothing to push down), or the
        kill switch is off — the "pre-pushdown server" case the soft
        version gate is about (the spec is IGNORED, pushdown_applied
        stays False, and the client evaluates locally)."""
        spec = getattr(req, "pushdown", None)
        if spec is None:
            return None
        if not FLAGS.get("pegasus.server", "scan_pushdown_enabled"):
            return None
        spec.check()  # ValueError -> ERR_INVALID_PARAMETERS at the stub
        if spec.value_filter is None and not spec.aggregate:
            return None
        return spec

    def _value_mask(self, ckey, blk, vf) -> np.ndarray:
        """bool[count] value-filter keep mask for one SST block, cached
        per (block, filter) — the value-side leg of the static/dynamic
        predicate split. Forcing blk.value_heap materializes a lazy
        compressed heap, which the filter needs anyway; the mask then
        outlives the decode. The kernel wave is audited against the
        placement cost model like the key-mask waves."""
        vkey = (ckey, vf)
        with self._mask_lock:
            hit = self._vmask_cache.get(vkey)
            if hit is not None:
                self._vmask_cache.move_to_end(vkey)
                return hit
        heap = blk.value_heap
        t0 = time.perf_counter()
        mask = pushdown_ops.value_filter_mask(
            heap, blk.value_offs, header_length(self.data_version),
            vf[0], vf[1])
        measured = time.perf_counter() - t0
        from pegasus_tpu.ops.placement import predict_kernel_seconds
        from pegasus_tpu.server.workload import DRIFT
        from pegasus_tpu.utils import perf_context as perf

        predicted = predict_kernel_seconds("scan_pushdown",
                                           int(np.asarray(heap).size))
        DRIFT.note("scan_pushdown", predicted, measured)
        pc = perf.current()
        if pc is not None:
            pc.predicted_kernel_ms += predicted * 1000.0
            pc.measured_kernel_ms += measured * 1000.0
            pc.placement = pc.placement or "numpy"
        with self._mask_lock:
            self._vmask_cache[vkey] = mask
            while len(self._vmask_cache) > self._vmask_cache_cap:
                self._vmask_cache.popitem(last=False)
        return mask

    # ---- scanners -----------------------------------------------------

    def on_get_scanner(self, req: GetScannerRequest) -> ScanResponse:
        """Parity: on_get_scanner (pegasus_server_impl.cpp:1151)."""
        gate = self._read_gate()
        if gate:
            resp = ScanResponse()
            resp.error = gate
            return resp
        start_key = req.start_key or b""
        if start_key and not req.start_inclusive:
            start_key = _after(start_key)
        stop_key = req.stop_key or b""
        if stop_key and req.stop_inclusive:
            stop_key = _after(stop_key)
        resp = self._serve_scan_batch(req, start_key, stop_key)
        if resp.context_id >= 0:
            _SCAN_CONTEXTS_OPENED.increment()
        return resp

    def on_scan(self, context_id: int) -> ScanResponse:
        """Parity: on_scan (pegasus_server_impl.cpp:1399)."""
        gate = self._read_gate()
        if gate:
            resp = ScanResponse()
            resp.error = gate
            return resp
        ctx = self._scan_cache.take(context_id)
        if ctx is None:
            resp = ScanResponse()
            resp.error = int(StorageStatus.NOT_FOUND)
            resp.context_id = SCAN_CONTEXT_ID_NOT_EXIST
            return resp
        _SCAN_PAGES_SERVED.increment()
        return self._serve_scan_batch(ctx.request, ctx.resume_key,
                                      ctx.stop_key,
                                      agg_state=ctx.agg_state)

    def on_clear_scanner(self, context_id: int) -> None:
        self._scan_cache.remove(context_id)

    def _serve_scan_batch(self, req: GetScannerRequest, start_key: bytes,
                          stop_key: bytes,
                          agg_state=None) -> ScanResponse:
        from pegasus_tpu.utils import perf_context as perf
        from pegasus_tpu.utils.latency_tracer import LatencyTracer

        t0 = time.perf_counter()
        # stage chain for scan pages (plan -> block scan/decode ->
        # assemble): a slow page shows WHERE it stalled, and the stages
        # annotate the active distributed-tracing span
        tracer = LatencyTracer(f"scan.{self.app_id}.{self.pidx}")
        pc = perf.current()
        if pc is None:
            pc = perf.start("scan_page")
        tracer.perf = pc
        try:
            with perf.activate(pc):
                return self._serve_scan_batch_inner(req, start_key,
                                                    stop_key, tracer,
                                                    agg_state)
        finally:
            elapsed_ms = (time.perf_counter() - t0) * 1000.0
            self._read_latency.set(elapsed_ms)
            sp = tracer.span
            if pc is not None and sp is not None:
                perf.merge_span_perf(sp.tags, pc)
            self.slow_log.observe(tracer)

    def _serve_scan_batch_inner(self, req: GetScannerRequest,
                                start_key: bytes,
                                stop_key: bytes,
                                tracer=None,
                                agg_state=None) -> ScanResponse:
        pd = self._pushdown_of(req)
        if pd is not None and pd.aggregate:
            return self._pushdown_aggregate_page(req, pd, start_key,
                                                 stop_key, tracer,
                                                 agg_state)
        vf = pd.value_filter if pd is not None else None
        pd_stats: dict = {}
        now = epoch_now()
        resp = ScanResponse()
        limiter = RangeReadLimiter(clock_ns=self.clock_ns)
        batch_size = min(req.batch_size if req.batch_size > 0 else 1000,
                         SCAN_BATCH_CAP)
        if req.only_return_count:
            batch_size = -1  # count the whole (limiter-bounded) range
        hash_filter = FilterSpec.make(req.hash_key_filter_type,
                                      req.hash_key_filter_pattern)
        sort_filter = FilterSpec.make(req.sort_key_filter_type,
                                      req.sort_key_filter_pattern)
        if tracer is not None:
            tracer.add_point("plan")
        records, exhausted, resume_key = self._batched_scan(
            start_key, stop_key or None, now,
            hash_filter, sort_filter,
            validate_hash=(req.validate_partition_hash
                           and self.validate_partition_hash),
            limiter=limiter, max_records=batch_size,
            max_bytes=-1 if req.only_return_count else SCAN_BYTES_CAP,
            with_values=not req.no_value and not req.only_return_count,
            value_filter=vf, pd_stats=pd_stats, one_page=req.one_page)
        if tracer is not None:
            tracer.add_point("block_scan")
            if pd is not None:
                tracer.add_point("pushdown")
        if req.only_return_count:
            resp.kv_count = len(records)
        else:
            size = 0
            for key, data, ets in records:
                kv = KeyValue(key, data)
                if req.return_expire_ts:
                    kv.expire_ts_seconds = ets
                resp.kvs.append(kv)
                size += len(key) + len(data)
            self.cu.add_read(size)
        if tracer is not None:
            tracer.add_point("assemble")
        pruned = pd_stats.get("pruned", 0)
        _SCAN_ROWS_EVALUATED.increment(limiter.iteration_count)
        _SCAN_ROWS_RETURNED.increment(len(records))
        pc = tracer.perf if tracer is not None else None
        if pc is not None:
            pc.ops += 1
            pc.rows_evaluated += limiter.iteration_count
            pc.rows_survived += len(records)
            pc.keys_resolved += len(records)
            pc.bytes_returned += sum(len(k) + len(d)
                                     for k, d, _e in records)
            pc.pushdown_rows_pruned += pruned
        self.workload.note_scan(1, limiter.iteration_count,
                                len(records))
        if pd is not None:
            self.workload.note_pushdown(1, pruned, 0)
            resp.pushdown_applied = True
        resp.error = int(StorageStatus.OK)
        if exhausted or req.one_page:
            # one_page: the client promised not to page further — no
            # context to cache, no clear_scanner round-trip later
            resp.context_id = SCAN_CONTEXT_ID_COMPLETED
        else:
            resp.context_id = self._scan_cache.put(ScanContext(
                request=req, resume_key=resume_key or start_key,
                stop_key=stop_key))
        return resp

    def _pushdown_aggregate_page(self, req: GetScannerRequest, pd,
                                 start_key: bytes, stop_key: bytes,
                                 tracer=None,
                                 agg_state=None) -> ScanResponse:
        """Aggregate-mode pushdown: fold one (limiter-bounded) slice of
        the range into the partition's PARTIAL aggregate instead of
        returning rows. The partial rides server-side in the scan
        context across pages and ships ONLY on the final page — one agg
        payload per partition on the wire, and a lost context (expiry,
        split bounce) loses the partial WITH the pages it counted, so
        the client's restart-from-original-start never double counts.

        Columnar arm: the same cached static masks + host TTL AND as
        _columnar_scan, but survivors feed AggState.fold_columnar — a
        count folds off the mask alone (a lazy compressed value heap
        stays undecoded unless the value filter already forced it);
        sum/top_k/sample gather straight from the raw value heap."""
        now = epoch_now()
        resp = ScanResponse()
        limiter = RangeReadLimiter(clock_ns=self.clock_ns)
        vf = pd.value_filter
        pd_stats: dict = {}
        state = (agg_state if agg_state is not None
                 else pushdown_ops.AggState(pd))
        folded0 = state.count
        hash_filter = FilterSpec.make(req.hash_key_filter_type,
                                      req.hash_key_filter_pattern)
        sort_filter = FilterSpec.make(req.sort_key_filter_type,
                                      req.sort_key_filter_pattern)
        validate = bool(req.validate_partition_hash
                        and self.validate_partition_hash)
        hdr = header_length(self.data_version)
        stop = stop_key or None
        if tracer is not None:
            tracer.add_point("plan")
        exhausted = True
        resume_key: Optional[bytes] = None
        sorted_runs = self.engine.lsm.sorted_runs()
        if sorted_runs is not None:
            from pegasus_tpu.ops.predicates import host_alive_mask

            filter_key = hash_filter.key + sort_filter.key
            with self._mask_lock:
                self._register_flavor(validate, filter_key,
                                      time.monotonic())

            # resident mesh arm: a fresh whole-range aggregate on an
            # attached table folds off the table-wide SPMD dispatch —
            # count/sum directly from the psum-shaped per-partition
            # counts/lanes, top_k/sample from the all-gathered mask via
            # the same AggState fold. Any decline (paging limiter, L0
            # overlay, stale slab, watchdog, cost model) falls through
            # to the host arm unchanged.
            if agg_state is None and not start_key and stop is None:
                from pegasus_tpu.parallel.mesh_resident import MESH_SERVING

                mesh = (MESH_SERVING.try_aggregate(
                            self, req, pd, validate, filter_key, now)
                        if MESH_SERVING.enabled else None)
                if mesh is not None:
                    state = mesh["agg_state"]
                    if mesh["expired"]:
                        self._abnormal_reads.increment(mesh["expired"])
                    if tracer is not None:
                        tracer.add_point("block_scan")
                        tracer.add_point("pushdown")
                    folded = mesh["folded"]
                    pruned = mesh["pruned"]
                    _SCAN_ROWS_EVALUATED.increment(mesh["rows_evaluated"])
                    _SCAN_ROWS_RETURNED.increment(folded)
                    pc = tracer.perf if tracer is not None else None
                    if pc is not None:
                        pc.ops += 1
                        pc.rows_evaluated += mesh["rows_evaluated"]
                        pc.rows_survived += folded
                        pc.keys_resolved += folded
                        pc.rows_aggregated += folded
                        pc.pushdown_rows_pruned += pruned
                        pc.placement = "mesh"
                        pc.mesh_partitions += mesh["partitions"]
                        pc.mesh_wave_ms += mesh["wave_ms"]
                        pc.predicted_kernel_ms += mesh["predicted_ms"]
                        pc.measured_kernel_ms += mesh["measured_ms"]
                    self.workload.note_scan(1, mesh["rows_evaluated"],
                                            folded)
                    self.workload.note_pushdown(1, pruned, folded)
                    resp.pushdown_applied = True
                    resp.error = int(StorageStatus.OK)
                    resp.context_id = SCAN_CONTEXT_ID_COMPLETED
                    resp.agg = state.to_wire()
                    if tracer is not None:
                        tracer.add_point("assemble")
                    return resp

            for window, after in _scan_windows(sorted_runs, start_key,
                                               stop, limiter):
                keeps = self._static_keep_window(
                    window, validate, filter_key,
                    fill=None if req.one_page else after)
                stopped = False
                for (ckey, blk, lo, hi), static_keep in zip(window,
                                                            keeps):
                    n = blk.count
                    alive = host_alive_mask(blk.expire_ts, now)
                    expired = int(np.count_nonzero(~alive[lo:hi]))
                    if expired:
                        self._abnormal_reads.increment(expired)
                    keep = static_keep[:n] & alive
                    if vf is not None:
                        vmask = self._value_mask(ckey, blk, vf)
                        before = int(np.count_nonzero(keep[lo:hi]))
                        keep = keep & vmask[:n]
                        pd_stats["pruned"] = (
                            pd_stats.get("pruned", 0) + before
                            - int(np.count_nonzero(keep[lo:hi])))
                    sel = np.flatnonzero(keep[lo:hi]) + lo
                    if sel.size:
                        if pd.aggregate == "count":
                            state.fold_columnar(sel)
                        else:
                            state.fold_columnar(
                                sel, heap=blk.value_heap,
                                value_offs=blk.value_offs, hdr=hdr,
                                key_at=blk.key_at)
                    if not limiter.valid():
                        resume_key = _after(blk.key_at(n - 1))
                        exhausted = False
                        stopped = True
                        break
                if stopped:
                    break
        else:
            # overlay / reverse-free generic arm: the iterator merge
            # already applies newest-wins shadowing and tombstones, so
            # scalar folds over its survivors are exact
            records, exhausted, resume_key = self._batched_scan(
                start_key, stop, now, hash_filter, sort_filter,
                validate, limiter, max_records=-1, max_bytes=-1,
                with_values=(pd.aggregate != "count"),
                value_filter=vf, pd_stats=pd_stats)
            for key, data, _ets in records:
                state.fold_row(key, data)
        if tracer is not None:
            tracer.add_point("block_scan")
            tracer.add_point("pushdown")
        folded = state.count - folded0
        pruned = pd_stats.get("pruned", 0)
        _SCAN_ROWS_EVALUATED.increment(limiter.iteration_count)
        _SCAN_ROWS_RETURNED.increment(folded)
        pc = tracer.perf if tracer is not None else None
        if pc is not None:
            pc.ops += 1
            pc.rows_evaluated += limiter.iteration_count
            pc.rows_survived += folded
            pc.keys_resolved += folded
            pc.rows_aggregated += folded
            pc.pushdown_rows_pruned += pruned
            pc.placement = pc.placement or "numpy"
        self.workload.note_scan(1, limiter.iteration_count, folded)
        self.workload.note_pushdown(1, pruned, folded)
        resp.pushdown_applied = True
        resp.error = int(StorageStatus.OK)
        if exhausted or req.one_page:
            resp.context_id = SCAN_CONTEXT_ID_COMPLETED
            resp.agg = state.to_wire()
        else:
            # NOT final: no agg on the wire; the partial continues
            # server-side under a fresh context id
            resp.context_id = self._scan_cache.put(ScanContext(
                request=req, resume_key=resume_key or start_key,
                stop_key=stop_key, agg_state=state))
        if tracer is not None:
            tracer.add_point("assemble")
        return resp

    # ---- batched multi-scan (the request-batching dispatch unit of
    # SURVEY §2.6: MANY concurrent scans share ONE device predicate pass;
    # zipfian traffic re-reads the same hot blocks, which are evaluated
    # once per batch instead of once per scan) ---------------------------

    def on_get_scanner_batch(self, reqs: List[GetScannerRequest]
                             ) -> List[ScanResponse]:
        """Serve a batch of scans with per-block dedup.

        Fast path requires the columnar store (light write overlays
        merge host-side) and plain range scans (no filters/count-only) —
        the YCSB-E shape; anything else falls back to per-request
        serving. Each UNIQUE block touched by the batch gets one device
        predicate evaluation (cached device uploads); per-request
        boundary trimming happens on the host against the materialized
        keep mask, so shared blocks need no per-scan device work at
        all. plan/finish split so a NODE-level coordinator can stack
        blocks across partitions into one dispatch."""
        state = self.plan_scan_batch(reqs)
        if state is None:
            return [self.on_get_scanner(r) for r in reqs]
        if "precomputed" in state:  # read gate rejected the whole batch
            return state["precomputed"]
        keep_masks = self.eval_planned_masks(state)
        return self.finish_scan_batch(state, keep_masks)

    def plan_scan_batch(self, reqs: List[GetScannerRequest],
                        now: Optional[int] = None, flavor=None):
        """Phase 1: qualify + block planning. None = caller must serve
        per-request. `flavor` = the (validate, filter_key) the caller
        already grouped by (scan_coordinator) — passing it skips the
        per-request re-derivation. The flush's PerfContext is created
        (or adopted from an ambient one — shell explain) here and rides
        the state through the mask-eval and finish phases."""
        from pegasus_tpu.utils import perf_context as perf

        pc = perf.current()
        if pc is None:
            pc = perf.start("scan_batch")
        with perf.activate(pc):
            return self._plan_scan_batch_inner(reqs, now, flavor, pc)

    def _plan_scan_batch_inner(self, reqs: List[GetScannerRequest],
                               now, flavor, ppc):
        from pegasus_tpu.utils.latency_tracer import LatencyTracer

        t0 = time.perf_counter()
        tracer = LatencyTracer(self._scan_log_key)
        tracer.perf = ppc
        gate = self._read_gate()
        if gate:
            out = []
            for _r in reqs:
                resp = ScanResponse()
                resp.error = gate
                out.append(resp)
            return {"precomputed": out, "t0": t0}
        lsm = self.engine.lsm
        # generation is read BEFORE the run set and re-checked after the
        # plans are built: an env-triggered compaction publishes off the
        # node lock (l1_runs swap -> generation bump -> overlay clear),
        # and a batch planned across that publish could pair the OLD
        # runs with the NEW (empty) overlay — silently dropping the
        # consumed overlay rows — or cache old-run plans under the new
        # generation. Reading gen first puts any such plans under the
        # OLD generation (correctly invalidated), and the final check
        # sends a torn batch to the per-request path, which reads
        # memtable-before-runs (the safe order against this publish).
        gen = lsm.generation
        runs = lsm.l1_runs
        # a light write overlay (memtable + small L0s) must NOT evict the
        # whole partition from the device path: its rows merge host-side
        # on top of the device-filtered base (the YCSB-E 5%-insert shape
        # leaves a handful of overlay rows per partition)
        overlay_count = len(lsm.memtable) + sum(t.total_count
                                                for t in lsm.l0)
        # the shared-mask trick needs every request to share the mask
        # inputs: ONE effective validate flag and ONE filter spec across
        # the batch (no count-only mode). A batch-wide SHARED filter —
        # the geo covering-cell / prefix-scan shape — rides the same
        # cached-mask machinery: the filter is simply part of the mask
        # key, so repeated popular filters hit like unfiltered scans.
        if flavor is not None:
            validates = {flavor[0]}
            filters = {flavor[1]}
        else:
            validates = {bool(r.validate_partition_hash
                              and self.validate_partition_hash)
                         for r in reqs}
            filters = {_normalize_filter_key(r) for r in reqs}
        known = (FT_NO_FILTER, FT_MATCH_ANYWHERE, FT_MATCH_PREFIX,
                 FT_MATCH_POSTFIX)
        # pushdown on the batched path: ONE shared value filter rides
        # the live-mask machinery (it is part of the live-cache key,
        # like the key filters are part of the mask key); aggregates
        # serve per-request (their reply shape is a partial, not a
        # page), as do mixed-filter batches
        pdl = [self._pushdown_of(r) for r in reqs]
        vfs = {pd.value_filter if pd is not None else None for pd in pdl}
        simple = (runs and overlay_count <= self.OVERLAY_MERGE_LIMIT
                  and len(validates) == 1 and len(filters) == 1
                  and all(f[0] in known and f[2] in known
                          for f in filters)
                  and not any(r.only_return_count for r in reqs)
                  and len(vfs) == 1
                  and not any(pd is not None and pd.aggregate
                              for pd in pdl))
        if not simple:
            return None
        now = epoch_now() if now is None else now
        validate = validates.pop()
        filter_key = filters.pop()
        vf = vfs.pop()
        # 1 — per request: the block list + boundary bounds, capped a bit
        # beyond batch_size so expiry/hash drops don't starve the page.
        # Plans are CACHED per (range, want-bucket, store generation):
        # zipfian traffic re-issues the same popular scans constantly,
        # and a plan is pure over the immutable run set (the generation
        # key invalidates on flush/ingest/compaction). The want bucket
        # (pow2) keeps variants bounded; an over-budgeted cached plan
        # only means a further frontier, never a wrong page.
        req_plans = []
        unique: "OrderedDict[tuple, tuple]" = OrderedDict()
        pc = self._plan_cache
        if pc is None or pc[0] is not lsm or pc[1] != gen:
            pc = self._plan_cache = (lsm, gen, {})
        cache = pc[2]
        for req in reqs:
            start_key = req.start_key or b""
            if start_key and not req.start_inclusive:
                start_key = _after(start_key)
            stop_key = req.stop_key or b""
            if stop_key and req.stop_inclusive:
                stop_key = _after(stop_key)
            want = min(req.batch_size if req.batch_size > 0 else 1000,
                       SCAN_BATCH_CAP)
            wb = 1 << (want - 1).bit_length() if want > 1 else 1
            pkey = (start_key, stop_key, wb)
            hit = cache.get(pkey)
            if hit is not None:
                plan, uniq_entries, geom, nat, frontier = hit
            else:
                plan = []
                uniq_entries = []
                budget = wb * 2 + 64
                for run in runs:
                    if stop_key and (run.first_key or b"") >= stop_key:
                        continue
                    if start_key and (run.last_key or b"") < start_key:
                        continue
                    for bm, blk in run.iter_blocks(start_key,
                                                   stop_key or None):
                        lo, hi = 0, blk.count
                        if start_key and bm.first_key < start_key:
                            lo = _lower_bound(blk, start_key)
                        if stop_key and bm.last_key >= stop_key:
                            hi = _lower_bound(blk, stop_key)
                        ckey = (run.path, bm.offset)
                        uniq_entries.append((ckey, run, bm, blk))
                        plan.append((ckey, blk, lo, hi))
                        budget -= hi - lo
                        if budget <= 0:
                            break
                    if budget <= 0:
                        break
                # plan geometry + native entry table, computed once per
                # cached plan — the native assembly (page.serve_batch)
                # concatenates these instead of re-resolving per-entry
                # pointer rows and numpy scalar reads every flush
                from pegasus_tpu.server.page import plan_geometry, plan_nat

                geom = plan_geometry(plan)
                nat = plan_nat(plan)
                # the resume frontier past a capped plan's last planned
                # row — plan-pure, so computed once here instead of a
                # per-request key_at on the serving path
                frontier = (_after(plan[-1][1].key_at(
                    plan[-1][1].count - 1)) if plan else None)
                if len(cache) >= 8192:
                    cache.pop(next(iter(cache)))
                cache[pkey] = (plan, uniq_entries, geom, nat, frontier)
            for ckey, run, bm, blk in uniq_entries:
                unique.setdefault(ckey, (run, bm, blk))
            # a plan that spent its budget answers up to its frontier
            # only; one that did not reaches the request's stop_key and
            # carries no frontier from here on
            capped = bool(plan) and geom[0] >= want * 2 + 64
            req_plans.append((req, start_key, stop_key, want, plan,
                              geom, nat, frontier if capped else None))
        # 2 — per request: the overlay rows of ITS range, now that the
        # plans say where each range ends
        if overlay_count:
            with tracing.layer("overlay.snapshot"):
                overlay = self._overlay_windows(lsm, req_plans, now,
                                                validate, filter_key, vf)
        else:
            overlay = ([()] * len(req_plans), {})
        if lsm.generation != gen:
            # a compaction published while this batch planned: the runs
            # and overlay above may be from different sides of the swap
            # — serve per-request instead (safe read order)
            return None
        tracer.add_point("plan")
        if ppc is not None:
            ppc.ops += len(reqs)
            ppc.blocks_planned += len(unique)
            ppc.runs_considered += len(runs)
        return {"reqs": reqs, "req_plans": req_plans, "unique": unique,
                "validate": validate, "now": now, "overlay": overlay,
                "filter_key": filter_key, "vf": vf, "pd_list": pdl,
                "t0": t0, "tracer": tracer, "perf": ppc}

    def planned_misses(self, state) -> "OrderedDict[tuple, object]":
        """Unique planned blocks whose STATIC masks are NOT cached (the
        device work remaining); uploads happen here via the block cache.
        Masks are `now`-independent (TTL applies host-side at assembly),
        so a cached block never needs re-evaluation — misses only occur
        on first touch after a flush/compaction or for a new filter.
        Planned misses are noted as HOT so the MaskPrefresher can warm
        sibling flavors ahead of the next scan."""
        keep_masks = {}
        misses: "OrderedDict[tuple, object]" = OrderedDict()
        validate = state["validate"]
        filter_key = state["filter_key"]
        wall = time.monotonic()
        with self._mask_lock:
            self._register_flavor(validate, filter_key, wall)
            for ckey, (run, bm, blk) in state["unique"].items():
                mkey = (ckey, self.partition_version, validate,
                        filter_key)
                cached = self._mask_cache.get(mkey)
                if cached is not None:
                    self._mask_cache.move_to_end(mkey)
                    keep_masks[ckey] = cached
                    continue
                misses[ckey] = (run, bm, blk)
        _MASK_CACHE_HIT.increment(len(keep_masks))
        _MASK_CACHE_MISS.increment(len(misses))
        pv = self.partition_version
        encoded_resolved = []
        for ckey, (run, bm, blk) in list(misses.items()):
            # direct compute on compressed blocks: the static keep
            # (hash validation + hashkey/sortkey filters) evaluates
            # host-side against the ENCODED representation — the
            # hashkey filter once per dictionary entry, the sortkey
            # filter over the packed heap — so a compressed block's
            # first-touch mask costs no device round-trip at all
            keep = self._encoded_static_mask(run, bm, validate,
                                             filter_key, pv)
            if keep is not None:
                keep_masks[ckey] = keep
                encoded_resolved.append((ckey, keep))
                del misses[ckey]
                continue
            misses[ckey] = self._device_cached_block(ckey, blk)
        for ckey, keep in encoded_resolved:
            self.store_mask_for(ckey, validate, filter_key, keep,
                                computed_pv=pv)
        pc = state.get("perf")
        if pc is not None and encoded_resolved:
            # encoded-domain host probes (no decode, no device): the
            # "numpy" compute class; a later device wave overwrites
            pc.placement = "numpy"
        state["cached_keep"] = keep_masks
        return misses

    def _encoded_static_mask(self, run, bm, validate: bool, filter_key,
                             pv: int):
        """bool[n] static keep of one planned block via the encoded
        probe (ops/predicates.encoded_static_keep), or None when the
        run is uncompressed / the block can't take the path."""
        if getattr(run, "codec", None) is None:
            return None
        from pegasus_tpu.ops.predicates import encoded_static_keep

        try:
            enc = run.read_block_encoded(run.block_index(bm))
        except (StorageCorruptionError, OSError):
            # the probe's raw re-read DETECTED on-disk corruption:
            # escalate into the PR 5 quarantine/re-learn loop — falling
            # back to a stale cached decode would serve while hiding a
            # known-corrupt file until the next scrub pass
            raise
        except Exception:  # noqa: BLE001 - run replaced mid-plan: the
            return None    # device path serves from the decoded block
        if enc is None:
            return None
        return encoded_static_keep(enc, validate, self.pidx, pv,
                                   filter_key)

    def _register_flavor(self, validate: bool, filter_key,
                         wall: float) -> None:
        """Remember a scan flavor for background warming (caller holds
        _mask_lock). The no-filter flavor always registers; a FILTERED
        flavor registers once it RECURS within the window — one-shot
        filter patterns must not multiply background device work or
        evict the long-lived warm set. Flavors (not blocks) are
        remembered: compaction replaces the block set, and the warmer's
        job is exactly to re-evaluate the NEW blocks for the flavors
        serving has been using."""
        register = filter_key == _NO_FILTER_KEY
        if not register:
            last = self._filter_seen.get(filter_key)
            register = (last is not None
                        and wall - last <= self._filter_seen_window)
            self._filter_seen[filter_key] = wall
            self._filter_seen.move_to_end(filter_key)
            while len(self._filter_seen) > self._filter_seen_cap:
                self._filter_seen.popitem(last=False)
        if register:
            fl = (validate, filter_key)
            self._warm_flavors[fl] = wall
            self._warm_flavors.move_to_end(fl)
            while len(self._warm_flavors) > self._warm_flavors_cap:
                self._warm_flavors.popitem(last=False)

    def store_mask(self, state, ckey, keep) -> None:
        self.store_mask_for(ckey, state["validate"],
                            state["filter_key"], keep,
                            computed_pv=self.partition_version)

    def _effective_mask_cap(self) -> int:
        """Mask-cache capacity scaled to the data: every current L1 block
        x every warm flavor must fit, or the prefresher and the LRU fight
        forever (warm one mask, evict another still-wanted one) and the
        'each block evaluated once' invariant breaks on large
        partitions."""
        n_blocks = sum(len(run.blocks) for run in self.engine.lsm.l1_runs)
        flavors = max(1, len(self._warm_flavors))
        return max(self._mask_cache_cap, n_blocks * flavors + 256)

    def store_mask_for(self, ckey, validate: bool, filter_key,
                       keep, computed_pv: int) -> None:
        """Publish a static mask under the partition_version it was
        COMPUTED with. The prefresher evaluates on its own thread — if a
        split flipped the version mid-evaluation, publishing under the
        new version would serve pre-split masks (rows now owned by the
        sibling); drop instead."""
        keep = np.asarray(keep)
        if keep.base is not None:
            # slices of stacked multi-flavor eval outputs would pin the
            # whole [K, S*cap] base array per ~1KB cache entry
            keep = keep.copy()
        cap = self._effective_mask_cap()
        with self._mask_lock:
            if computed_pv != self.partition_version:
                return
            self._mask_cache[(ckey, computed_pv, validate,
                              filter_key)] = keep
            while len(self._mask_cache) > cap:
                self._mask_cache.popitem(last=False)

    WARM_BATCH_LIMIT = 256  # blocks loaded per warm pass (bounds IO)

    def hot_block_entries(self, wall: float, horizon_s: float):
        """(ckey, block, validate, filter_key) for CURRENT L1 blocks
        missing a static mask for a recently-used scan flavor — the
        MaskPrefresher's work list. After a flush/compaction replaces
        the SSTs, this is how the new blocks get their masks evaluated
        in the background before the next scan pays the device
        round-trip. Prunes flavors idle past the horizon."""
        with self._mask_lock:
            flavors = []
            for fl in list(self._warm_flavors):
                if wall - self._warm_flavors[fl] > horizon_s:
                    del self._warm_flavors[fl]
                    continue
                flavors.append(fl)
        if not flavors:
            return []
        # cache probing runs WITHOUT the lock (GIL-atomic dict gets; a
        # racing store just makes this pass warm one mask twice) so the
        # serving path never stalls behind a full-data-size iteration
        pv = self.partition_version
        cache_get = self._mask_cache.get
        missing = []
        for run in list(self.engine.lsm.l1_runs):
            for i, bm in enumerate(run.blocks):
                ckey = (run.path, bm.offset)
                for validate, filter_key in flavors:
                    if cache_get((ckey, pv, validate,
                                  filter_key)) is None:
                        missing.append((run, i, ckey, validate,
                                        filter_key))
                        if len(missing) >= self.WARM_BATCH_LIMIT:
                            break
                if len(missing) >= self.WARM_BATCH_LIMIT:
                    break
            if len(missing) >= self.WARM_BATCH_LIMIT:
                break
        # block loads (disk IO) also happen outside the lock
        out = []
        for run, i, ckey, validate, filter_key in missing:
            try:
                blk = run.read_block(i)
            except Exception:  # noqa: BLE001 - run replaced mid-pass
                continue
            out.append((ckey, blk, validate, filter_key))
        return out

    def eval_planned_masks(self, state):
        """Phase 2 (solo-node form): evaluate this partition's misses.
        Runs under the state's PerfContext so the stacked device eval
        records its placement verdict + predicted/measured kernel time
        on the flush's cost vector."""
        from pegasus_tpu.utils import perf_context as perf

        with perf.activate(state.get("perf")):
            misses = self.planned_misses(state)
            keep_masks = state["cached_keep"]
            for ckey, keep in self._eval_blocks_stacked(
                    misses, state["filter_key"], state["validate"]):
                keep_masks[ckey] = keep
                self.store_mask(state, ckey, keep)
        tracer = state.get("tracer")
        if tracer is not None:
            tracer.add_point("block_probe")
        return keep_masks

    def prepare_serve(self, state, keep_masks) -> list:
        """Phase 2.5: combine static keep with host TTL per unique
        block and return the batch's fast-path request windows (those
        whose range the plan found free of overlay rows)
        `(plan, want, no_value, want_ets, live_masks, geom)` for
        native assembly (page.serve_batch's req_windows shape). The
        node-level coordinator concatenates these ACROSS partitions so
        one native call (page.serve_batch) packs every fast request of
        a whole flush. Everything is stashed in `state`; idempotent."""
        if "precomputed" in state or "fast" in state:
            return state.get("fast", [])
        unique = state["unique"]
        now = state["now"]
        vf = state.get("vf")
        live_masks = {}
        live_ptrs = {}
        alive_all = {}
        exp_full = {}
        pushdown_pruned = 0
        cache = self._live_cache
        for ckey, (_run, _bm, blk) in unique.items():
            ets = blk.expire_ts
            static = keep_masks[ckey]
            # (block, flavor-mask, value-filter, second) live-mask
            # cache: TTL validity is one second, so every batch within
            # the second reuses the same static AND alive (AND value
            # mask) result instead of recomputing it — zipfian traffic
            # hits the same hot blocks thousands of times per second
            lkey = (ckey, id(static), vf)
            hit = cache.get(lkey)
            # the entry pins the static array it was built from (id()
            # alone could be a recycled address after a mask evict)
            if hit is not None and hit[0] == now and hit[1] is static:
                _now, _st, alive, exp, live, lptr, prn = hit
                alive_all[ckey] = alive
                exp_full[ckey] = exp
                live_masks[ckey] = live
                live_ptrs[ckey] = lptr
                pushdown_pruned += prn
                continue
            alive = blk.alive_mask(now)
            alive_all[ckey] = alive
            # whole-block expired count once per unique block; requests
            # spanning the full block (the common case) reuse the
            # scalar, boundary slices recount
            exp = len(alive) - int(np.count_nonzero(alive))
            exp_full[ckey] = exp
            live = static[:len(ets)] & alive
            prn = 0
            if vf is not None:
                # the shared pushdown value filter joins the live mask
                # (cached per block+pattern in _value_mask); pruned =
                # key-alive rows the VALUE predicate dropped
                before = int(np.count_nonzero(live))
                live = live & self._value_mask(ckey, blk,
                                               vf)[:len(ets)]
                prn = before - int(np.count_nonzero(live))
            pushdown_pruned += prn
            live_masks[ckey] = live
            # .ctypes.data costs ~a µs: resolve once per (block, flavor,
            # second), not once per request window (page.serve_batch
            # consumes these as the per-entry mask pointers)
            lptr = live.ctypes.data
            live_ptrs[ckey] = lptr
            if len(cache) >= 4096:
                cache.pop(next(iter(cache)))
            cache[lkey] = (now, static, alive, exp, live, lptr, prn)
        state["pushdown_pruned"] = pushdown_pruned
        fast = []
        for (req, _start, _stop, want, plan, geom, nat, _frontier), \
                ov_keys in zip(state["req_plans"], state["overlay"][0]):
            if not ov_keys:
                fast.append((plan, want, req.no_value,
                             req.return_expire_ts, live_masks, geom,
                             nat, live_ptrs))
        state["live_masks"] = live_masks
        state["alive_all"] = alive_all
        state["exp_full"] = exp_full
        state["fast"] = fast
        tracer = state.get("tracer")
        if tracer is not None:
            if vf is not None:
                tracer.add_point("pushdown")
            tracer.add_point("decode")
        return fast

    def finish_scan_batch(self, state, keep_masks, served=None
                          ) -> List[ScanResponse]:
        """Phase 3: assemble responses from (shared) STATIC masks.

        TTL expiry is applied here, host-side: one vectorized AND of the
        static mask with the block's expire_ts column per unique block
        (`now` is the batch's single clock reading). This is the other
        half of the static/dynamic predicate split — the device never
        re-evaluates a block just because the clock ticked.

        `served`: this batch's slice of the coordinator's cross-
        partition native assembly (aligned with prepare_serve's fast
        list); None = run the native assembly here (solo callers)."""
        if "precomputed" in state:
            return state["precomputed"]
        reqs = state["reqs"]
        req_plans = state["req_plans"]
        unique = state["unique"]
        now = state["now"]
        t0 = state["t0"]

        from pegasus_tpu.server.page import build_page, serve_batch

        fast = self.prepare_serve(state, keep_masks)
        live_masks = state["live_masks"]
        alive_all = state["alive_all"]
        exp_full = state["exp_full"]
        ov_windows, overlay_map = state["overlay"]
        hdr = header_length(self.data_version)
        if served is None and fast:
            served = serve_batch(fast, None, SCAN_BYTES_CAP, hdr)
        served_iter = iter(served) if served is not None else None

        # per-(plan, second) expired-count cache: the count is flavor-
        # independent (alive depends only on block + now) and plans are
        # cached objects, so zipfian repeats of a popular scan within
        # one second skip the per-entry accounting loop entirely. The
        # plan object is pinned in the value so its id() cannot be
        # recycled while the entry lives; the whole dict resets each
        # second / generation, so nothing outlives the blocks it counts.
        ptag = (self.engine.lsm.generation, now)
        if self._plan_expired_cache[0] != ptag:
            self._plan_expired_cache = (ptag, {})
        pec = self._plan_expired_cache[1]
        total_expired = 0
        total_read_cu = 0
        total_rows = 0
        total_bytes = 0
        merge_reqs = 0

        out = []
        for (req, start_key, stop_key, want, plan, _geom, _nat,
             frontier), ov_keys in zip(req_plans, ov_windows):
            kvs: list = []
            size = 0
            exhausted = True
            resume_key = None
            stop_early = False
            want_ets = req.return_expire_ts
            no_value = req.no_value

            def base_rows(plan=plan):
                for ckey, blk, lo, hi in plan:
                    keep = live_masks[ckey]
                    for i in np.flatnonzero(keep[lo:hi]):
                        idx = lo + int(i)
                        yield blk.key_at(idx), blk, idx

            hit = pec.get(id(plan))
            if hit is not None:
                req_expired = hit[1]
            else:
                req_expired = 0
                for ckey, blk_, lo, hi in plan:
                    # per-REQUEST expired accounting (the solo path
                    # counts per request served, not per block evaluated)
                    if lo == 0 and hi == blk_.count:
                        req_expired += exp_full[ckey]
                    else:
                        req_expired += int(np.count_nonzero(
                            ~alive_all[ckey][lo:hi]))
                pec[id(plan)] = (plan, req_expired)
            if not ov_keys:
                # fast path: no overlay rows shadow this window, so the
                # kept base rows ARE the answer — already assembled by
                # the batch native call (page.serve_batch -> packer.cpp
                # pegasus_scan_serve_batch); the vectorized-numpy path
                # below is the no-toolchain / arena-overflow fallback.
                served = (next(served_iter) if served_iter is not None
                          else None)
                if served is not None:
                    kvs, size, last_key, truncated = served
                    taken = len(kvs)
                    if ((taken >= want or truncated)
                            and last_key is not None):
                        resume_key = _after(last_key)
                        stop_early = True
                    chunks = None
                else:
                    chunks = []
            else:
                chunks = None
            if chunks is not None:
                taken = 0
                byte_est = 0
                truncated = False
                for ckey, blk, lo, hi in plan:
                    hit = np.flatnonzero(live_masks[ckey][lo:hi])
                    if hit.size > want - taken:
                        hit = hit[:want - taken]
                    if not hit.size:
                        continue
                    hit = hit + lo
                    # byte budget (keys + value-heap span upper bound):
                    # page blob offsets are uint32 and one RPC response
                    # must stay bounded whatever the values weigh. A
                    # keys-only scan serializes no values, so only key
                    # bytes count — else large-value blocks force
                    # needless pagination.
                    vo = blk.value_offs
                    chunk_bytes = int(hit.size) * blk.keys.shape[1]
                    if not no_value:
                        chunk_bytes += (int(vo[int(hit[-1]) + 1])
                                        - int(vo[int(hit[0])]))
                    if byte_est + chunk_bytes > SCAN_BYTES_CAP:
                        if byte_est == 0:
                            # a single oversized chunk: binary-search the
                            # row prefix that fits (per-row byte cumsum
                            # only for this rare path)
                            row_bytes = np.full(hit.size,
                                                blk.keys.shape[1],
                                                dtype=np.int64)
                            if not no_value:
                                row_bytes += (vo[hit + 1].astype(np.int64)
                                              - vo[hit].astype(np.int64))
                            fit = int(np.searchsorted(
                                np.cumsum(row_bytes), SCAN_BYTES_CAP,
                                side="right"))
                            hit = hit[:max(1, fit)]
                            chunks.append((blk, hit))
                            taken += int(hit.size)
                        truncated = True
                        break
                    byte_est += chunk_bytes
                    chunks.append((blk, hit))
                    taken += int(hit.size)
                    if taken >= want:
                        break
                kvs, size, last_key = build_page(
                    chunks, hdr, no_value=no_value, want_ets=want_ets)
                if (taken >= want or truncated) and last_key is not None:
                    resume_key = _after(last_key)
                    stop_early = True
            elif ov_keys:
                # merge path: interleave overlay rows in key order
                # (overlay rows SHADOW base rows: newest wins,
                # tombstones hide)
                merge_reqs += 1
                ov_i = 0
                ov_hi = len(ov_keys)
                base = base_rows()
                base_item = next(base, None)
                while len(kvs) < want:
                    ov_key = ov_keys[ov_i] if ov_i < ov_hi else None
                    if base_item is None and ov_key is None:
                        break
                    take_overlay = (ov_key is not None
                                    and (base_item is None
                                         or ov_key <= base_item[0]))
                    if take_overlay:
                        if base_item is not None and ov_key == base_item[0]:
                            base_item = next(base, None)  # shadowed
                        ov_i += 1
                        entry = overlay_map[ov_key]
                        if entry is None:
                            continue  # tombstone / hidden overlay row
                        data = b"" if no_value else entry[0]
                        kv = KeyValue(ov_key, data)
                        if want_ets:
                            kv.expire_ts_seconds = entry[1]
                        key = ov_key
                    else:
                        key, blk, idx = base_item
                        base_item = next(base, None)
                        data = (b"" if no_value
                                else extract_user_data(self.data_version,
                                                       blk.value_at(idx)))
                        kv = KeyValue(key, data)
                        if want_ets:
                            kv.expire_ts_seconds = int(blk.expire_ts[idx])
                    kvs.append(kv)
                    size += len(key) + len(data)
                    if len(kvs) >= want or size >= SCAN_BYTES_CAP:
                        resume_key = _after(key)
                        stop_early = True
                        break
            if stop_early:
                exhausted = False
            elif frontier is not None:  # a capped plan
                resume_key = frontier
                exhausted = False
            total_expired += req_expired
            total_rows += len(kvs)
            total_bytes += size
            # per-request CU floor preserved: units() per request,
            # summed, one counter touch per batch
            total_read_cu += cu_units(size)
            resp = ScanResponse()
            resp.kvs = kvs
            # pd_list aligns with reqs/req_plans order; len(out) is the
            # current request's index (appends happen once per loop)
            pd_list = state.get("pd_list")
            resp.pushdown_applied = bool(pd_list
                                         and pd_list[len(out)]
                                         is not None)
            resp.error = int(StorageStatus.OK)
            if exhausted or req.one_page:
                resp.context_id = SCAN_CONTEXT_ID_COMPLETED
            else:
                resp.context_id = self._scan_cache.put(ScanContext(
                    request=req, resume_key=resume_key or start_key,
                    stop_key=stop_key))
            out.append(resp)
        # batch-accumulated accounting: one metrics/capacity call per
        # state, not per request (identical totals)
        if total_expired:
            self._abnormal_reads.increment(total_expired)
        self.cu.add_read_units(total_read_cu)
        # mask-evaluated rows = every row of every unique planned block
        # (the kernels see whole blocks); survivors vs evaluated is the
        # table's scan SELECTIVITY — what a server-side pushdown saves
        rows_eval = sum(b.count for _r, _bm, b in unique.values())
        _SCAN_ROWS_EVALUATED.increment(rows_eval)
        _SCAN_ROWS_RETURNED.increment(total_rows)
        if merge_reqs:
            _SCAN_MERGE_PATH_REQUESTS.increment(merge_reqs)
        self.workload.note_scan(len(reqs), rows_eval, total_rows)
        pd_pruned = state.get("pushdown_pruned", 0)
        n_pushdown = sum(1 for pd in state.get("pd_list") or ()
                         if pd is not None)
        if n_pushdown:
            self.workload.note_pushdown(n_pushdown, pd_pruned, 0)
        pc = state.get("perf")
        if pc is not None:
            pc.rows_evaluated += rows_eval
            pc.rows_survived += total_rows
            pc.expired_rows += total_expired
            pc.bytes_returned += total_bytes
            pc.keys_resolved += total_rows
            pc.pushdown_rows_pruned += pd_pruned
            sp = (state["tracer"].span
                  if state.get("tracer") is not None else None)
            if sp is not None:
                from pegasus_tpu.utils import perf_context as perf

                perf.merge_span_perf(sp.tags, pc)
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        self._read_latency.set(elapsed_ms)
        tracer = state.get("tracer")
        if tracer is not None:
            tracer.add_point("finish")
            self.slow_log.observe(
                tracer,
                {"scans": len(reqs), "unique_blocks": len(unique)})
        else:
            self.slow_log.observe_simple(
                self._scan_log_key, elapsed_ms,
                {"scans": len(reqs), "unique_blocks": len(unique)})
        return out

    # overlay rows tolerated on the batched device path before falling
    # back to per-request merged serving
    OVERLAY_MERGE_LIMIT = 4096

    def _overlay_windows(self, lsm, req_plans, now: int, validate: bool,
                         filter_key=None, value_filter=None):
        """([sorted keys, one list a request], key -> None|(user_data,
        ets)): the memtable + L0 rows of each request's OWN range
        `[start_key, min(stop_key, frontier of a capped plan))`,
        newest-wins, with the scan predicates (TTL, stale-split hash,
        and the batch's shared key filter) evaluated HOST-side — the
        overlay is tiny by the fast-path qualifier, so a device dispatch
        would cost more than it filters. The memtable's sorted keys are
        bisected and each L0 table iterated by range, so a short scan
        pays for the overlay rows beside it and not for the partition's;
        a key is evaluated once a batch however many ranges hold it
        (zipfian traffic repeats its hot records inside one window). A
        key failing the KEY filter is excluded entirely (its base copies
        fail the same filter in the device mask, so nothing needs
        shadowing); a row failing the pushdown VALUE filter must instead
        stay as a hidden SHADOW (None) — the base may hold an older
        value for the same key that would pass, and newest-wins must
        still hide it."""
        from pegasus_tpu.base.key_schema import check_key_hash, restore_key
        from pegasus_tpu.ops.predicates import host_match_filter
        from pegasus_tpu.storage.memtable import TOMBSTONE

        hft, hfp, sft, sfp = filter_key or (FT_NO_FILTER, b"",
                                            FT_NO_FILTER, b"")
        key_filtered = hft != FT_NO_FILTER or sft != FT_NO_FILTER

        def entry_of(key, value, ets):
            if key_filtered:
                hk, sk = restore_key(key)
                if not (host_match_filter(hk, hft, hfp)
                        and host_match_filter(sk, sft, sfp)):
                    return _OVERLAY_EXCLUDED  # fails the filter everywhere
            if value is TOMBSTONE:
                return None  # shadows the base
            if check_if_ts_expired(now, ets):
                self._abnormal_reads.increment()
                return None  # expired: hidden AND shadows the base
            if validate and not check_key_hash(key, self.pidx,
                                               self.partition_version):
                return None
            data = extract_user_data(self.data_version, value)
            if value_filter is not None and not host_match_filter(
                    data, value_filter[0], value_filter[1]):
                return None  # value-rejected: hidden, still shadows
            return data, ets

        mem = lsm.memtable
        mem_get = mem.get
        l0 = lsm.l0
        entries: dict = {}
        ranges: dict = {}
        windows = []
        walked = 0
        for _req, start_key, stop_key, _want, _plan, _geom, _nat, \
                frontier in req_plans:
            stop = stop_key or None
            if frontier is not None and (stop is None or frontier < stop):
                stop = frontier
            keys = ranges.get((start_key, stop))
            if keys is not None:
                windows.append(keys)
                continue
            in_range = mem.keys_in(start_key, stop)
            l0_rows: dict = {}
            for table in l0:  # newest first; first writer wins
                for key, value, ets in table.iterate(start_key, stop):
                    l0_rows.setdefault(key, (value, ets))
            if l0_rows:
                under = [k for k in l0_rows if mem_get(k) is None]
                if under:
                    in_range = sorted(in_range + under)
            keys = []
            for key in in_range:
                if key not in entries:
                    walked += 1
                    entries[key] = entry_of(
                        key, *(mem_get(key) or l0_rows[key]))
                if entries[key] is not _OVERLAY_EXCLUDED:
                    keys.append(key)
            ranges[(start_key, stop)] = keys
            windows.append(keys)
        _OVERLAY_ROWS_WALKED.increment(walked)
        return windows, entries

    def _eval_blocks_stacked(self, misses, filter_key, validate):
        """Evaluate MANY blocks' static predicates in as few device
        dispatches as possible via the shared stacker (scan_coordinator):
        blocks sharing (width, cap) become one [B*cap, W] program —
        records are independent, so block boundaries carry no meaning
        there."""
        from pegasus_tpu.server.scan_coordinator import stacked_block_eval

        blocks = [(ckey, dev, self.pidx) for ckey, dev in misses.items()]
        yield from stacked_block_eval(blocks, validate,
                                      self.partition_version,
                                      filter_key=filter_key)

    def _static_keep_window(self, window, validate: bool, filter_key,
                            fill=None) -> list:
        """Cached static keep masks for a window of blocks (solo-path
        form): filter match + partition-hash validation,
        `now`-independent. Window misses are evaluated in ONE stacked
        device wave — one round-trip per window instead of per block —
        and cached for every later scan to combine with TTL host-side.
        `window`: [(ckey, blk, lo, hi)]; returns masks aligned to it.

        `fill`: the range's blocks after the window (_blocks_after), or
        None where the read will not page on (one_page). A window that
        misses tops its misses up to a whole number of stacks with the
        next of those blocks whose mask is not cached: a stack is
        STACK_CHUNK blocks whatever it holds, so their masks cost the
        device nothing more, and the later pages find them as hits
        where they would each have paid a program of their own. Their
        masks are published, not returned, and are no look-ups."""
        from pegasus_tpu.server.scan_coordinator import (
            STACK_CHUNK,
            stacked_block_eval,
        )

        pv = self.partition_version
        keeps: list = [None] * len(window)
        misses = []
        with self._mask_lock:
            for j, (ckey, blk, _lo, _hi) in enumerate(window):
                mkey = (ckey, pv, validate, filter_key)
                cached = self._mask_cache.get(mkey)
                if cached is not None:
                    self._mask_cache.move_to_end(mkey)
                    keeps[j] = cached
                else:
                    misses.append((j, ckey, blk))
        _MASK_CACHE_HIT.increment(len(window) - len(misses))
        _MASK_CACHE_MISS.increment(len(misses))
        if not misses:
            return keeps
        blocks = [((j, ckey), self._device_cached_block(ckey, blk),
                   self.pidx) for j, ckey, blk in misses]
        if fill is not None and len(blocks) % STACK_CHUNK:
            filled = self._fill_blocks(fill, -len(blocks) % STACK_CHUNK,
                                       blocks[-1][1].keys.shape, validate,
                                       filter_key, pv)
            _MASK_FILL_BLOCKS.increment(len(filled))
            blocks += filled
        for (j, ckey), keep in stacked_block_eval(
                blocks, validate, pv, filter_key=filter_key):
            keep = np.asarray(keep)
            if j is not None:
                keeps[j] = keep
            self.store_mask_for(ckey, validate, filter_key, keep,
                                computed_pv=pv)
        return keeps

    def _fill_blocks(self, fill, need: int, shape, validate: bool,
                     filter_key, pv: int) -> list:
        """Stacker entries ((None, ckey), device block, pidx) for up to
        `need` of `fill`'s blocks whose mask for this flavor is not
        cached. Stops at the first block of another shape than
        `shape`: it would take a program of its own."""
        out = []
        for ckey, read in fill:
            if len(out) == need:
                break
            # GIL-atomic membership test: a racing store at worst
            # evaluates one mask twice
            if (ckey, pv, validate, filter_key) in self._mask_cache:
                continue
            dev = self._device_cached_block(ckey, read)
            if dev.keys.shape != shape:
                break
            out.append(((None, ckey), dev, self.pidx))
        return out

    def _device_cached_block(self, cache_key, blk):
        """The shared device-upload cache used by both scan paths.
        `blk`: the decoded block, or a function that reads it, called
        only when the block is not on the device yet."""
        import jax.numpy as jnp

        from pegasus_tpu.ops.record_block import RecordBlock, block_from_columns
        from pegasus_tpu.storage.sstable import BLOCK_CAPACITY

        with self._mask_lock:
            dev_block = self._device_block_cache.get(cache_key)
            if dev_block is not None:
                self._device_block_cache.move_to_end(cache_key)
                return dev_block
        if callable(blk):
            blk = blk()
        # upload outside the lock (serving and the prefresher may race
        # to a duplicate upload of the same block — harmless, last wins)
        n = blk.count
        cap = max(BLOCK_CAPACITY, n)
        nb = block_from_columns(blk.keys, blk.key_len, blk.expire_ts,
                                hash_lo=blk.hash_lo)
        pad = cap - n
        dev_block = RecordBlock(
            jnp.asarray(np.pad(nb.keys, ((0, pad), (0, 0)))),
            jnp.asarray(np.pad(nb.key_len, (0, pad))),
            jnp.asarray(np.pad(nb.hashkey_len, (0, pad))),
            jnp.asarray(np.pad(nb.expire_ts, (0, pad))),
            jnp.asarray(np.pad(nb.valid, (0, pad))),
            None if nb.hash_lo is None
            else jnp.asarray(np.pad(nb.hash_lo, (0, pad))))
        with self._mask_lock:
            self._device_block_cache[cache_key] = dev_block
            if len(self._device_block_cache) > self._device_block_cache_cap:
                self._device_block_cache.popitem(last=False)
        return dev_block

    # ---- maintenance --------------------------------------------------

    def flush(self) -> bool:
        with self._write_lock:
            return self.engine.flush()

    def checkpoint(self, dest_dir: str) -> int:
        """Frozen snapshot under the single-writer lock — checkpoint
        starts with a memtable flush and walks the run set, which must
        not interleave with the async env-compaction thread's publish
        (backup / learning / split all snapshot through here)."""
        with self._write_lock:
            return self.engine.checkpoint(dest_dir)

    def update_partition_count(self, new_count: int) -> None:
        """Partition-count flip after a split (parity: the group
        partition-count update in replica_split_manager.h:76-123): routing
        and the stale-key predicate switch to the new count; stale-half
        records are filtered from every scan immediately and physically
        dropped by the next manual compaction."""
        if new_count < self.partition_count:
            raise ValueError("partition count can only grow")
        self.partition_count = new_count
        self.partition_version = new_count - 1
        self.validate_partition_hash = (
            new_count > 1 and (new_count & (new_count - 1)) == 0)
        # cached masks were computed under the old partition_version; the
        # predicate takes pv dynamically so caches stay valid, but fused
        # prepared tensors embed nothing version-dependent either — keep.
        # The ROW/plan/point/live caches, by contrast, hold ROWS resolved
        # under the pre-flip routing: the hash gate keeps misrouted
        # requests off them, but half this partition's key range just
        # moved to the child — drop parent entries eagerly so no code
        # path (present or future) can observe a stale parent row, and
        # so dead-half rows stop occupying the node-shared byte cap.
        self._live_cache = {}
        self._plan_cache = None
        self._point_cache = None
        self._plan_expired_cache = (None, {})
        ROW_CACHE.invalidate_gid((self.app_id, self.pidx))

    def manual_compact(self, default_ttl: Optional[int] = None,
                       rules_filter=None,
                       now: Optional[int] = None) -> None:
        """Parity: pegasus_manual_compact_service (manual CompactRange).
        Defaults come from the table's app-envs (`default_ttl`,
        `user_specified_compaction`) unless overridden.

        `now` pins the filter timestamp (defaults to epoch_now() inside
        the engine). A table-wide trigger passes one shared timestamp
        so every sibling partition filters under IDENTICAL params —
        deterministic outputs, and the mesh-resident filter stage
        (parallel/mesh_resident.py) computes the whole table's drop
        masks in ONE dispatch that the siblings' compactions then read
        from cache.

        The writer critical section is NARROW: the overlay is frozen
        with one flush under _write_lock, the multi-second merge runs
        from that immutable snapshot with writes flowing, and
        _write_lock is retaken only for the publish cut-over (with
        lsm run-set revalidation inside _publish_l1) — so a write
        arriving mid-compaction no longer wedges transport dispatch
        and FD beacons for the whole merge. engine.compact_lock
        serializes compactions; the write path's auto-compaction
        skips its trigger while this runs (the manual run covers it).
        Cache eviction for the superseded runs happens through the
        store's publish hook (_on_store_publish)."""
        if default_ttl is None:
            default_ttl = self._default_ttl
        if rules_filter is None:
            rules_filter = self._compaction_rules
        with self.engine.compact_lock:
            with self._write_lock:
                # freeze the overlay: post-freeze writes land in the
                # fresh memtable / newer L0s, which the publish leaves
                # untouched (they keep shadowing the merged base)
                self.engine.flush()
            self.engine.manual_compact(
                default_ttl=default_ttl, pidx=self.pidx,
                partition_version=self.partition_version,
                validate_hash=self.validate_partition_hash,
                rules_filter=rules_filter, now=now,
                publish_lock=self._write_lock)
