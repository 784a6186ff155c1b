"""Node-level cross-partition point-read coordination.

The point-read twin of scan_coordinator: a node hosting many partitions
of a table receives one flush of concurrent get / ttl /
multi_get(sort_keys) / batch_get requests, plans each partition's batch
(per-generation point-location cache + vectorized block probes), then
serves the WHOLE flush's value gathers through one batched native path —
page.build_page concatenates every partition's (block, rows) chunks so
the flush pays one native gather call per unique touched block instead
of a Python key/value materialization loop per request.

Where the scan coordinator's win is device-dispatch amortization (stacked
mask programs), the point path's win is host-side: point predicates are
compute-trivial per byte (the "probe" workload class in ops/placement.py
— a crc compare and a TTL compare), so nothing here is worth a
host->device copy; what batching buys instead is

- ONE clock read, ONE gate/accounting pass, ONE slow-log observation per
  flush instead of per request;
- per-generation location caching: zipfian traffic re-probes the same
  hot keys, and a key's (block, row) is pure over the immutable run set;
- vectorized key-list bisects: each touched block answers every probe in
  the flush with one searchsorted over its sorted key matrix;
- one native gather per block for co-located keys (hot hash keys cluster
  in the same SST block) with per-second TTL masks read straight off the
  host-resident expire_ts column;
- batched sidecar pruning AND location: each partition's plan hashes
  its disk-bound residue ONCE (ops.predicates.bloom_key_hashes — the
  crc64 column every sidecar shares) and answers every (key x
  L0-table / L1-run) candidacy from the per-SSTable structures before
  any block is decoded. Indexed runs (storage/phash.py, the
  CompassDB-style perfect-hash index) answer candidacy and LOCATION
  in the same `pegasus_phash_probe_multi` cell: misses die with zero
  block touches and hits go straight to their (block, slot) row — no
  index bisect at all; filter-only runs keep the bloom+bisect path
  (storage/bloom.py), so mixed-format stores serve correctly. The
  plan's stage chain (plan/bloom/phash_probe/block_probe/decode/
  finish) shows which structure answered on slow logs and traces;
- the node row cache (server/row_cache.py): hot rows admitted by repeat
  traffic (or a hotkey-detection fast-admit) serve before the engine is
  touched at all, write-through-invalidated on the mutation apply path
  and wholesale on store publishes/generation bumps.

Used by the replica stub's client_read_batch handler (the rpc/transport
batch-dispatch hook delivers consecutive queued point reads as one
flush) and by both clients' point_read_multi.
"""

from __future__ import annotations

from typing import List, Tuple


def is_point_read(op: str, args) -> bool:
    """Ops the batched point path serves; everything else (ranged
    multi_get, scans, sortkey_count) keeps its own path. Defensive
    against malformed wire args — a shape this returns True for must
    never make plan_get_batch raise anything but ValueError."""
    if op in ("get", "ttl"):
        return isinstance(args, (bytes, bytearray))
    if op == "batch_get":
        return isinstance(getattr(args, "keys", None), (list, tuple))
    if op == "multi_get":
        return bool(getattr(args, "hash_key", b"")) \
            and bool(getattr(args, "sort_keys", ()))
    return False


def point_read_multi(servers_and_ops: List[Tuple[object, list]],
                     now=None, deadline=None, clock=None,
                     tenants=None) -> List[list]:
    """[(PartitionServer, [(op, args, partition_hash)])] -> [[result]].

    Results are byte-identical to the solo handlers (on_get / on_ttl /
    on_multi_get with sort keys / on_batch_get). One build_page call
    assembles every partition's L1 value gathers per value-header
    width (one native gather per unique block across the whole flush).

    `deadline`/`clock`: the flush's end-to-end deadline on the serving
    node's clock. Checked between the per-partition planning passes and
    again before the cross-partition gather — the two places a large
    flush spends real time — raising ERR_TIMEOUT instead of finishing
    work every requester already abandoned.

    `tenants`: optional per-pair QoS tenant names aligned with
    `servers_and_ops`. The finish pass (where the CU funnel fires) runs
    under that pair's ambient tenant, so a transport flush coalescing
    several tenants' reads still bills each tenant its own capacity
    units; None (or a None slot) leaves attribution to whatever tenant
    the caller already bound.
    """
    from pegasus_tpu.utils.tracing import layer

    # one scope for the whole flush: the partitions' stage points
    # (plan, index probes, decode, finish) take their intervals out of
    # it; what is left is the grouping and routing here
    with layer("coord.route"):
        return _point_read_multi(servers_and_ops, now, deadline, clock,
                                 tenants)


def _point_read_multi(servers_and_ops, now, deadline, clock,
                      tenants) -> List[list]:
    from pegasus_tpu.base.value_schema import epoch_now, header_length
    from pegasus_tpu.server.page import build_page

    def _check_deadline() -> None:
        if deadline is not None and clock is not None \
                and clock() > deadline:
            from pegasus_tpu.utils.errors import ErrorCode, PegasusError

            raise PegasusError(ErrorCode.ERR_TIMEOUT,
                               "point-read flush deadline exceeded")

    from pegasus_tpu.utils.tracing import annotate

    if now is None:
        now = epoch_now()
    states = []
    for server, ops in servers_and_ops:
        _check_deadline()
        states.append((server, server.plan_get_batch(ops, now=now)))
    _check_deadline()
    annotate("coord_plan")  # read-coordinator join point (active span)

    # cross-partition native assembly: group by value-header width (the
    # only per-partition parameter of the gather), concatenate chunks
    groups: dict = {}
    for server, state in states:
        chunks = server.point_chunks(state)
        if not chunks:
            state["_page"] = (None, 0)
            continue
        hdr = header_length(server.data_version)
        groups.setdefault(hdr, []).append((state, chunks))
    for hdr, grp in groups.items():
        all_chunks = []
        base = 0
        for state, chunks in grp:
            state["_page_base"] = base
            all_chunks.extend(chunks)
            base += state["chunk_rows"]
        page, _size, _last = build_page(all_chunks, hdr)
        for state, _chunks in grp:
            state["_page"] = (page, state.pop("_page_base"))

    annotate("coord_gather")

    out = []
    if tenants is None:
        tenants = [None] * len(states)
    from pegasus_tpu.server import tenancy

    for (server, state), tenant in zip(states, tenants):
        page, base = state.pop("_page", (None, 0))
        with tenancy.bind(tenant):
            out.append(server.finish_get_batch(state, page, base))
    annotate("coord_finish")
    return out
