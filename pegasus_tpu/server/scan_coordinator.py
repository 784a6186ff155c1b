"""Node-level cross-partition scan coordination.

The SURVEY §2.6 dispatch model realized: partitions are the batch
dimension of ONE device program. A node hosting many partitions of a
table receives one multi-partition scan message, plans each partition's
batch, stacks every uncached block ACROSS partitions (same key width →
one program over a stack of blocks, each with its partition index for
the stale-split check), evaluates once, and hands each partition its
masks back. Per-flush device dispatches drop from
O(partitions × blocks) to O(key-width buckets).

Two further batch axes cut what each dispatch costs (a fixed launch
plus the mask bytes that come back to the host):

- FLAVOR axis: requests carrying DIFFERENT filter patterns of the same
  filter type are planned as separate per-flavor groups, but their
  missing masks evaluate in ONE program ([K flavors × stacked records],
  ops/predicates.multi_static_block_predicate_submit) over the union of
  their blocks — each uploaded byte does K flavors of work, and every
  (flavor, block) pair in the union gets its mask cached (free sibling
  warming).
- PACKED masks: device programs return bit-packed uint8 masks (8x
  fewer bytes device→host); hosts unpack with numpy.

Masks are STATIC per (block, filter, partition_version): TTL expiry —
the only `now`-dependent predicate — is applied host-side from the
block's expire_ts column at assembly time (ops/predicates.py
static_block_predicate). A block therefore needs exactly one device
evaluation in its lifetime, and steady-state serving performs zero
device round-trips. Each row a read skips as expired counts in
`storage`/`scan_rows_expired`. Dropping expired rows from disk is the
manual compaction's: a pass with no user rules filters TTL on the
device where placement_verdict("ttl") says so, else on the host
(StorageEngine.manual_compact).
"""

from __future__ import annotations

import logging
import time
from collections import OrderedDict
from typing import Dict, List, Tuple

import numpy as np

from pegasus_tpu.ops.compaction import note_filter_program, note_mask_steps
from pegasus_tpu.ops.predicates import (
    FT_NO_FILTER,
    FilterSpec,
    multi_static_block_predicate_submit,
    stacked_multi_static_block_predicate_submit,
    stacked_static_block_predicate,
    static_block_predicate,
    unpack_masks,
)
from pegasus_tpu.ops.record_block import next_bucket
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.metrics import METRICS

_LOG = logging.getLogger("pegasus.scan")
_PREFRESH_ERRORS = METRICS.entity("storage", "node").counter(
    "mask_prefresh_error_count")


def scan_multi(servers_and_reqs: List[Tuple[object, list]],
               now: int) -> List[list]:
    """[(PartitionServer, [GetScannerRequest])] -> [[ScanResponse]].

    Requests are grouped per (validate, filter) flavor so a batch mixing
    filter patterns still rides the batched device path (one plan per
    flavor, one multi-flavor evaluation wave); partitions that cannot
    take the fast path (big overlay, gates, exotic filters) serve
    per-request.
    """
    # one scope for the whole flush: the partitions' stage points
    # (plan, decode, assemble, finish) and the overlay / wave scopes
    # take their intervals out of it; what is left is the grouping and
    # routing here
    with tracing.layer("coord.route"):
        return _scan_multi(servers_and_reqs, now)


def _scan_multi(servers_and_reqs, now: int) -> List[list]:
    from pegasus_tpu.server.partition_server import _normalize_filter_key

    states = []
    for server, reqs in servers_and_reqs:
        groups: "OrderedDict[tuple, list]" = OrderedDict()
        for i, r in enumerate(reqs):
            # pushdown identity joins the GROUP key (one request with a
            # different value filter or an aggregate must not knock the
            # whole flavor off the batched path) but not the plan
            # flavor — the device mask inputs are key-side only
            fl = (bool(r.validate_partition_hash
                       and server.validate_partition_hash),
                  _normalize_filter_key(r),
                  r.pushdown.key if r.pushdown is not None else None)
            groups.setdefault(fl, []).append(i)
        sub = []
        for fl, idxs in groups.items():
            state = server.plan_scan_batch([reqs[i] for i in idxs],
                                           now=now, flavor=fl[:2])
            sub.append((idxs, state))
        states.append((server, reqs, sub))

    # gather misses across partitions AND flavors; an eval group shares
    # (validate, partition_version, filter types, pattern pad widths) —
    # everything that must be static/uniform in one device program
    eval_groups: Dict[tuple, dict] = {}
    for server, reqs, sub in states:
        for _idxs, state in sub:
            if state is None or "precomputed" in state:
                continue
            misses = server.planned_misses(state)
            if not misses:
                continue
            hft, hfp, sft, sfp = state["filter_key"]
            gkey = (state["validate"], server.partition_version,
                    hft, sft, next_bucket(len(hfp)),
                    next_bucket(len(sfp)))
            grp = eval_groups.setdefault(gkey, {})
            flavor = grp.setdefault(state["filter_key"], [])
            for ckey, dev in misses.items():
                flavor.append((server, state, ckey, dev))

    for (validate, pv, _hft, _sft, _hw, _sw), flavors in \
            eval_groups.items():
        if len(flavors) == 1:
            (fkey, entries), = flavors.items()
            _eval_cross_partition(entries, validate, pv, fkey)
        else:
            _eval_cross_partition_multi(flavors, validate, pv)

    # cross-partition native assembly: concatenate every partition's
    # fast-path (overlay-free) requests and pack them with ONE native
    # call per flush (page.serve_batch -> pegasus_scan_serve_batch) —
    # per-partition batches are tiny (a 32-scan flush spread over 64
    # partitions), so amortizing the call setup across the whole flush
    # is what makes the C++ path pay
    from pegasus_tpu.server.page import serve_batch
    from pegasus_tpu.server.partition_server import (
        SCAN_BYTES_CAP,
        header_length,
    )

    fast_all: list = []
    fast_refs: list = []
    hdr_set = set()
    for server, reqs, sub in states:
        for _idxs, state in sub:
            if state is None or "precomputed" in state:
                continue
            fast = server.prepare_serve(state, state["cached_keep"])
            if not fast:
                continue
            hdr_set.add(header_length(server.data_version))
            fast_refs.append((state, len(fast)))
            fast_all.extend(fast)
    if fast_all and len(hdr_set) == 1:
        served_all = serve_batch(fast_all, None,
                                 SCAN_BYTES_CAP, hdr_set.pop())
        if served_all is not None:
            off = 0
            for state, n in fast_refs:
                state["_served"] = served_all[off:off + n]
                off += n
        # the one native call packed every fast request's page: that
        # interval is assembly, whichever partition's finish comes next
        tracing.mark("assemble")

    out = []
    for server, reqs, sub in states:
        resps = [None] * len(reqs)
        for idxs, state in sub:
            if state is None:
                rs = [server.on_get_scanner(reqs[i]) for i in idxs]
            elif "precomputed" in state:
                rs = state["precomputed"]
            else:
                rs = server.finish_scan_batch(
                    state, state["cached_keep"],
                    served=state.pop("_served", None))
            for i, r in zip(idxs, rs):
                resps[i] = r
        out.append(resps)
    return out


def stacked_block_eval(blocks, validate: bool, pv: int,
                       filter_key=None, perf_ctxs=()):
    """The ONE stacking implementation both the per-partition and the
    cross-partition paths use. `blocks`: [(tag, dev_block, pidx)] —
    yields (tag, static_keep).

    Two phases: SUBMIT every chunk's program to the device (async — XLA
    queues them all), then GATHER every result with the transfers
    started together. Each synchronous fetch of a fresh result pays a
    full host<->device round-trip, so starting all copies before the
    first wait overlaps compute and transfer across chunks instead of
    serializing round-trips. Masks come back bit-packed (8x fewer
    bytes to fetch) and unpack host-side.

    Being the one kernel dispatch site, this is also where the
    placement cost model is AUDITED: the wave's wall time is compared
    against ops/placement's prediction and fed to the workload
    profiler's cost-model drift gauge (server/workload.DRIFT), and the
    ambient PerfContext (when an op is being tracked) records the
    verdict + predicted/measured kernel ms.
    """
    blocks = list(blocks)
    if not blocks:
        return
    # resident mesh first: when the whole wave's blocks live in a
    # table's stacked device image and the cost model says one mesh
    # round beats the per-chunk host programs, ONE dispatch answers
    # everything (mesh_resident does its own drift audit under the
    # "mesh" class). Any decline — unattached, unresolved block, model
    # says host, watchdog trip — falls through unchanged.
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING

    if MESH_SERVING.enabled:
        served = MESH_SERVING.try_wave(blocks, validate, pv,
                                       filter_key=filter_key,
                                       perf_ctxs=perf_ctxs)
        if served is not None:
            yield from served
            return
    # from the call of the jitted predicate programs to their masks on
    # the host
    with tracing.layer("dispatch.wave"):
        clock = _WaveClock()
        submitted = list(stacked_block_submit(blocks, validate, pv,
                                              filter_key, clock))
        with tracing.layer("dispatch.fetch"):
            fetched = _fetch_wave([o[2] for o in submitted])
        clock.lap(FETCH)
    _audit_kernel_wave(blocks, filter_key, clock.close(), perf_ctxs)
    for (group, cap, _dev), packed in zip(submitted, fetched):
        keep_all = unpack_masks(packed, len(group) * cap)
        if len(group) == 1:
            yield group[0][0], keep_all
            continue
        for i, (tag, _d, _p) in enumerate(group):
            yield tag, keep_all[i * cap:(i + 1) * cap]


def _audit_kernel_wave(blocks, filter_key, measured_s: float,
                       perf_ctxs=()) -> None:
    """One drift sample per evaluated wave: predicted (cost model) vs
    measured (wall) kernel time, recorded process-wide and on the
    participating ops' PerfContexts (the ambient one, plus every
    coordinated state's context passed in `perf_ctxs` — the
    cross-partition path has no single ambient op). Filter-free masks
    are the compute-trivial "ttl" class; pattern-matching masks are
    "rules"."""
    from pegasus_tpu.ops.placement import (
        placement_verdict,
        predict_kernel_seconds,
    )
    from pegasus_tpu.server.workload import DRIFT
    from pegasus_tpu.utils import perf_context as perf

    cls = ("ttl" if filter_key is None
           or (filter_key[0] == FT_NO_FILTER
               and filter_key[2] == FT_NO_FILTER) else "rules")
    batch_bytes = sum(int(dev.keys.size) + 9 * int(dev.expire_ts.size)
                      for _t, dev, _p in blocks)
    predicted_s = predict_kernel_seconds(cls, batch_bytes)
    DRIFT.note(cls, predicted_s, measured_s)
    pcs = {id(pc): pc for pc in perf_ctxs if pc is not None}
    amb = perf.current()
    if amb is not None:
        pcs[id(amb)] = amb
    verdict = placement_verdict(cls) if pcs else ""
    for pc in pcs.values():
        # every participating op WAITED this wave, so each context
        # carries the wave's full wall time (not an apportioned share)
        pc.placement = verdict
        pc.predicted_kernel_ms += predicted_s * 1000.0
        pc.measured_kernel_ms += measured_s * 1000.0


def stacked_block_submit(blocks, validate: bool, pv: int,
                         filter_key=None, clock=None):
    """Phase 1: dispatch predicate programs WITHOUT waiting. Yields
    (group, cap, packed_keep_device_array). Buckets by (key width,
    capacity) so differently-capped tail blocks can never misalign mask
    slices; fixed STACK_CHUNK keeps exactly two compiled shapes per key
    width ([cap, W] and a stack of STACK_CHUNK) — variable stack sizes
    made every batch a fresh XLA compile. A stack is one jitted call
    that concatenates its blocks inside the program; one mixing hash_lo
    and non-hash_lo blocks drops the precomputed column (the kernel
    computes the hash on device instead). `clock`: the wave's
    _WaveClock, which times each chunk's stacking and launch."""
    hft, hfp, sft, sfp = filter_key or (FT_NO_FILTER, b"",
                                        FT_NO_FILTER, b"")
    hash_f = FilterSpec.make(hft, hfp)
    sort_f = FilterSpec.make(sft, sfp)
    for group, cap, stack, pidx in _stacked_chunks(blocks):
        if clock is not None:
            clock.lap(STACK)
        with tracing.layer("dispatch.launch"):
            stacked = len(stack) > 1
            if stacked:
                keep = stacked_static_block_predicate(
                    stack, pidx, hash_filter=hash_f, sort_filter=sort_f,
                    validate_hash=validate, partition_version=pv,
                    pack=True)
            else:
                keep = static_block_predicate(
                    stack[0], hash_filter=hash_f, sort_filter=sort_f,
                    validate_hash=validate, pidx=pidx,
                    partition_version=pv, pack=True)
            # key matrix + key_len, hashkey_len (4 B each) + valid, the
            # hash column where it is used, the packed mask back; a
            # stack also reads a pidx column, after its columns were
            # concatenated (read and written once: XLA writes the
            # concatenated copy inside the program)
            rows, width = len(stack) * cap, stack[0].keys.shape[1]
            columns = width + 9 + (
                4 if validate and all(b.hash_lo is not None
                                      for b in stack) else 0)
            note_filter_program(
                rows, rows * columns + rows // 8
                + (rows * (4 + 2 * (width + 17)) if stacked else 0),
                kind="mask", stacked=stacked)
        if clock is not None:
            clock.lap(LAUNCH)
        yield group, cap, keep


STACK, LAUNCH, FETCH = range(3)


class _WaveClock:
    """A wave of mask programs in its three steps on the host's clock:
    STACK (a chunk's blocks listed and its pidx vector built on the
    host), LAUNCH (the jitted call, which uploads a stack's pidx vector
    and queues the program that concatenates the stack) and FETCH (the
    wait for the wave's packed masks and their copy to the host). Each step boundary is one clock read that ends
    one step and starts the next, so the steps add up to the wave's
    wall; `close` counts them (`engine`/`mask_{stack,launch,fetch}_us`)
    and returns that wall, which the drift audit takes."""

    __slots__ = ("at", "ns")

    def __init__(self) -> None:
        self.ns = [0, 0, 0]
        self.at = time.perf_counter_ns()

    def lap(self, step: int) -> None:
        now = time.perf_counter_ns()
        self.ns[step] += now - self.at
        self.at = now

    def close(self) -> float:
        note_mask_steps(*self.ns)
        return sum(self.ns) / 1e9


STACK_CHUNK = 16

# flavor-axis sizes are bucketed to powers of two (list padded by
# repeating the last flavor) so K distinct patterns never compile more
# than log2(MULTI_FLAVOR_MAX) program shapes per (type, width) combo
MULTI_FLAVOR_MAX = 64


def _stacked_chunks(blocks):
    """Shared chunking: yields (group, cap, stack, pidx). A chunk of one
    block: stack [its block], pidx its scalar. A longer chunk: stack
    padded to STACK_CHUNK blocks by repeating its first, pidx their
    uint32[STACK_CHUNK] partition indices; the stacked programs
    concatenate the blocks themselves."""
    buckets: "OrderedDict[tuple, list]" = OrderedDict()
    for tag, dev, pidx in blocks:
        key = (int(dev.keys.shape[1]), int(dev.keys.shape[0]))
        buckets.setdefault(key, []).append((tag, dev, pidx))
    for (_w, cap), group in buckets.items():
        for off in range(0, len(group), STACK_CHUNK):
            chunk = group[off:off + STACK_CHUNK]
            if len(chunk) == 1:
                tag, dev, pidx = chunk[0]
                yield chunk, cap, [dev], pidx
                continue
            padded = chunk + [chunk[0]] * (STACK_CHUNK - len(chunk))
            with tracing.layer("dispatch.stack"):
                stack = [d for _t, d, _p in padded]
                pidx_vec = np.fromiter((p for _t, _d, p in padded),
                                       dtype=np.uint32, count=STACK_CHUNK)
            # the scope closes before the yield: the consumer's frames
            # are not the stack's children
            yield chunk, cap, stack, pidx_vec


def _fetch_wave(arrays: list) -> list:
    """Fetch a whole wave of device results in ONE transfer round.

    Every synchronous fetch round has a fixed cost regardless of size —
    fetching each chunk's mask separately multiplies it by the chunk
    count, so the wave gathers every submitted result with a single
    device_get."""
    if not arrays:
        return []
    import jax

    try:
        return jax.device_get(arrays)
    except Exception:  # noqa: BLE001 - fall back to per-array fetch
        return [np.asarray(a) for a in arrays]


def _eval_cross_partition(entries, validate: bool,
                          pv: int, filter_key=None) -> None:
    """Stack blocks from MANY partitions; each record carries its owning
    partition index so one program validates all. Every participating
    state's PerfContext gets the wave's placement/kernel audit."""
    blocks = [((server, state, ckey), dev, server.pidx)
              for server, state, ckey, dev in entries]
    pcs = _state_perf_ctxs(state for _srv, state, _ck, _d in entries)
    for (server, state, ckey), keep in stacked_block_eval(
            blocks, validate, pv, filter_key=filter_key,
            perf_ctxs=pcs):
        state["cached_keep"][ckey] = keep
        server.store_mask(state, ckey, keep)


def _state_perf_ctxs(states) -> list:
    """Distinct PerfContexts of the coordinated states (the prefresher
    passes placeholder states with no dict surface — skip those)."""
    out = {}
    for state in states:
        getter = getattr(state, "get", None)
        if getter is None:
            continue
        pc = getter("perf")
        if pc is not None:
            out[id(pc)] = pc
    return list(out.values())


def _flavor_specs(fkeys):
    """[(hash_FilterSpec, sort_FilterSpec)] for the flavor axis, padded
    to a power-of-two K by repeating the last flavor (bounded compile
    shapes)."""
    specs = [(FilterSpec.make(hft, hfp), FilterSpec.make(sft, sfp))
             for hft, hfp, sft, sfp in fkeys]
    k = 1
    while k < len(specs):
        k <<= 1
    specs = specs + [specs[-1]] * (k - len(specs))
    return specs


def _eval_cross_partition_multi(flavors: dict, validate: bool,
                                pv: int) -> None:
    """K filter flavors × the UNION of their missing blocks in one
    program per stack chunk. Every (flavor, block) mask that comes back
    is cached — pairs beyond the flavor's own miss set are free warm
    masks for the next scan with that pattern."""
    fkeys = list(flavors.keys())
    if len(fkeys) > MULTI_FLAVOR_MAX:
        # beyond the cap: evaluate in slabs
        items = list(flavors.items())
        mid = len(items) // 2
        _eval_cross_partition_multi(dict(items[:mid]), validate, pv)
        _eval_cross_partition_multi(dict(items[mid:]), validate, pv)
        return
    specs = _flavor_specs(fkeys)

    # union of blocks across flavors (a block may be missed by several)
    union: "OrderedDict[tuple, tuple]" = OrderedDict()
    wanted: Dict[tuple, list] = {}
    for fkey, entries in flavors.items():
        for server, state, ckey, dev in entries:
            ukey = (id(server), ckey)
            union.setdefault(ukey, (server, ckey, dev))
            wanted.setdefault((fkey, ukey), []).append(state)

    blocks = [((server, ckey), dev, server.pidx)
              for server, ckey, dev in union.values()]
    submitted = []
    with tracing.layer("dispatch.wave"):
        clock = _WaveClock()
        for group, cap, stack, pidx in _stacked_chunks(blocks):
            clock.lap(STACK)
            with tracing.layer("dispatch.launch"):
                if len(stack) > 1:
                    packed = stacked_multi_static_block_predicate_submit(
                        stack, specs, validate, pidx, pv)
                else:
                    packed = multi_static_block_predicate_submit(
                        stack[0], specs, validate, pidx, pv)
            clock.lap(LAUNCH)
            submitted.append((group, cap, packed))
        with tracing.layer("dispatch.fetch"):
            fetched = _fetch_wave([p for _g, _c, p in submitted])
        clock.lap(FETCH)
    # the multi-flavor wave audits like the single-flavor one: any
    # filtered flavor makes it the "rules" class (its compute bound)
    audit_fkey = next(
        (fk for fk in fkeys
         if fk[0] != FT_NO_FILTER or fk[2] != FT_NO_FILTER),
        fkeys[0])
    _audit_kernel_wave(
        blocks, audit_fkey, clock.close(),
        _state_perf_ctxs(st for states in wanted.values()
                         for st in states))
    for (group, cap, _p), packed in zip(submitted, fetched):
        masks = unpack_masks(packed, len(group) * cap)     # [K, S*cap]
        for ki, fkey in enumerate(fkeys):
            row = masks[ki]
            for i, ((server, ckey), _d, _p) in enumerate(group):
                keep = row[i * cap:(i + 1) * cap] if len(group) > 1 \
                    else row
                ukey = (id(server), ckey)
                states = wanted.get((fkey, ukey))
                # sibling (flavor, block) pairs beyond a flavor's own
                # miss set are cached only for WARM flavors — a flood of
                # one-shot patterns must not LRU-evict the long-lived
                # warm masks steady-state serving depends on (the same
                # guard _register_flavor applies to background warming)
                if states is None:
                    with server._mask_lock:
                        warm = (validate, fkey) in server._warm_flavors
                    if not warm:
                        continue
                server.store_mask_for(ckey, validate, fkey, keep,
                                      computed_pv=pv)
                for state in states or ():
                    state["cached_keep"][ckey] = np.asarray(keep)


class MaskPrefresher:
    """Background mask warmer — keeps first-touch device work off the
    serving path's critical latency.

    Static masks never expire (TTL is host-applied), so in steady state
    this thread has NOTHING to do: it only evaluates masks for blocks
    that recently appeared (flush/compaction rewrote the SSTs) or for a
    filter flavor seen for the first time, slightly ahead of the next
    scan. Serving that miss synchronously would put an upload, a
    dispatch and a mask fetch inside a client's scan.

    One per node (replica stub / bench cluster). Scans register their
    flavor (validate + filter) in PartitionServer.planned_misses (the
    `_warm_flavors` map); flavors age out after `horizon_s` without a
    scan. Daemon thread; safe to leave running.
    """

    def __init__(self, servers, horizon_s: float = 15.0,
                 poll_s: float = 0.2, device=None):
        import threading

        # `servers`: a list of PartitionServers, or a zero-arg callable
        # returning one (a replica stub's live set changes over time)
        self._servers = servers if callable(servers) \
            else (lambda s=list(servers): s)
        self.horizon_s = horizon_s
        self.poll_s = poll_s
        # jax.default_device is THREAD-local: a caller pinning a device
        # for serving must pin the warmer thread too or it computes on
        # the global default
        self.device = device
        self._stop = threading.Event()
        self._thread = None
        self.refreshed = 0  # masks warmed (for tests/metrics)
        self.errors = 0     # warm passes that raised

    @property
    def servers(self):
        return self._servers()

    def start(self) -> "MaskPrefresher":
        import threading

        if self._thread is None:
            self._thread = threading.Thread(target=self._run,
                                            name="mask-prefresher",
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _run(self) -> None:
        import contextlib

        ctx = contextlib.nullcontext()
        if self.device is not None:
            import jax

            ctx = jax.default_device(self.device)
        with ctx:
            while not self._stop.is_set():
                try:
                    self.refresh_once()
                except Exception:  # noqa: BLE001 - a failed pass only
                    # costs latency (serving recomputes), so the thread
                    # lives on — but counted, and logged with its cause
                    self.errors += 1
                    _PREFRESH_ERRORS.increment()
                    _LOG.exception("mask prefresher pass failed "
                                   "(%d so far)", self.errors)
                self._stop.wait(self.poll_s)

    def refresh_once(self, now: int = 0) -> int:
        """One warm pass over hot blocks missing their static mask;
        returns masks stored. Synchronous; tests call this directly.
        (`now` accepted for back-compat; static masks don't depend on
        it.) Flavors sharing filter types and pattern widths warm in
        one multi-flavor program per stack chunk."""
        wall = time.monotonic()
        warmed = 0
        groups: Dict[tuple, dict] = {}
        for srv in self.servers:
            for ckey, blk, validate, fkey in srv.hot_block_entries(
                    wall, self.horizon_s):
                dev = srv._device_cached_block(ckey, blk)
                hft, hfp, sft, sfp = fkey
                gkey = (validate, srv.partition_version, hft, sft,
                        next_bucket(len(hfp)), next_bucket(len(sfp)))
                grp = groups.setdefault(gkey, {})
                grp.setdefault(fkey, []).append((srv, ckey, dev))
        for (validate, pv, *_rest), flavors in groups.items():
            if len(flavors) == 1:
                (fkey, entries), = flavors.items()
                blocks = [((srv, ckey), dev, srv.pidx)
                          for srv, ckey, dev in entries]
                for (srv, ckey), keep in stacked_block_eval(
                        blocks, validate, pv, filter_key=fkey):
                    srv.store_mask_for(ckey, validate, fkey,
                                       keep, computed_pv=pv)
                    warmed += 1
            else:
                # no serving batch to hand masks back to: store-only
                _eval_cross_partition_multi(
                    {fkey: [(srv, _NO_STATE, ckey, dev)
                            for srv, ckey, dev in entries]
                     for fkey, entries in flavors.items()}, validate, pv)
                warmed += sum(len(e) for e in flavors.values())
        self.refreshed += warmed
        return warmed


class _NoStateType:
    """Placeholder state for prefresher-driven multi evals (no serving
    batch to hand masks back to) — swallows cached_keep writes."""

    def __getitem__(self, k):
        return {}


_NO_STATE = _NoStateType()
