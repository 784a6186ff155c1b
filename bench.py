#!/usr/bin/env python
"""YCSB-E-shaped scan benchmark for pegasus_tpu.

Workload (BASELINE.md config #2): a 64-partition table, zipfian-start range
scans of up to 100 records each, 95% scan / 5% insert, with the realistic
per-record read predicates Pegasus applies (TTL expiry on every record,
partition-hash validation) running on the accelerator. 10% of the loaded
records carry expired TTLs so expiry filtering does real work.

Prints ONE JSON line to stdout:
    {"metric": ..., "value": ops/sec, "unit": ..., "vs_baseline": ratio}
vs_baseline = accelerator throughput / XLA-CPU throughput for the same
workload in the same process (the CPU baseline the reference's scalar C++
loop competes with — see BASELINE.md "measure CPU baseline").

Secondary phases — YCSB-C point gets (BASELINE config #1; always on),
round-8 filtered reads (point_get_miss / point_get_hot: bloom pruning +
the node row cache vs the unfiltered baseline, byte-identity gated,
persisted to BENCH_r08.json),
manual-compaction GB/s (configs #3/#4), geo radius search (config #5)
— all ON by default (PEGBENCH_COMPACT=0 / PEGBENCH_GEO=0 to skip) — land
in BENCH_DETAILS.json
next to this script plus stderr; stdout stays one line.

This process owns the chip for the whole run. It exits non-zero before
any work when JAX's default device is not a TPU, when the native library
did not build, when the Pallas kernel does not compile, and when any
phase raises: a number measured on the host is never printed under a
per-chip name.

Env knobs: PEGBENCH_RECORDS (default 1_000_000), PEGBENCH_OPS (default
12_000), PEGBENCH_COMPACT_GB (default 1.0), PEGBENCH_EXPIRED (default 0.5),
PEGBENCH_PARTITIONS (default 64), PEGBENCH_SEED, PEGBENCH_COMPACT=0 /
PEGBENCH_GEO=0 (skip those phases),
PEGBENCH_SCAN_BATCH (default 32: scans coalesced per device dispatch —
the request-batching unit of SURVEY §2.6; 1 disables coalescing),
PEGBENCH_GET_BATCH (default 32: point gets coalesced per read-
coordinator flush in the point_get_batch phase),
PEGBENCH_WRITE_BATCH (default 32: puts coalesced per write_multi flush
in the write_put_batch phase),
PEGBENCH_MESH=0 (skip the mesh_scan phase) / PEGBENCH_MESH_RECORDS
(default 240_000) / PEGBENCH_MESH_PARTITIONS (default 8) — the
mesh_scan phase runs in this process over a mesh of jax.devices().
PEGBENCH_MESH_COMPACT=0 (skip the mesh_compact phase) /
PEGBENCH_MESH_COMPACT_RECORDS (default 192_000) — the compaction
FILTER-stage twin of mesh_scan, same in-process shape.
"""

import contextlib
import json
import os
import sys
import tempfile
import time


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pallas_smoke() -> None:
    """Compile and run the fused Pallas scan kernel on the default
    backend. A kernel Mosaic refuses raises: nothing here may fall back
    to interpret mode."""
    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.ops.record_block import build_record_block
    import pegasus_tpu.ops.pallas_scan as ps

    from pegasus_tpu.storage.sstable import BLOCK_CAPACITY as n

    # the shape the store has: one block of BLOCK_CAPACITY rows, width 32
    keys = [generate_key(b"hk%06d" % i, b"s%02d" % (i % 10))
            for i in range(n)]
    ets = [0 if i % 2 else 1 for i in range(n)]
    block = build_record_block(keys, ets, capacity=n, key_width=32)
    keep, _expired = ps.fused_scan_block(block, now=100)
    assert int(np.asarray(keep).sum()) == n // 2, \
        "fused kernel mask is wrong"


class BenchCluster:
    """Replicated-path bench target: a SimCluster onebox whose measured
    ops go client -> sim transport -> replica-stub gates -> storage app
    (VERDICT r1: the benched path must be the replicated path). Single
    replica per partition (BASELINE config #1 "onebox single-replica") so
    load cost stays in the storage engine, not the sim scheduler."""

    def __init__(self, tmpdir, n_partitions):
        from pegasus_tpu.tools.cluster import SimCluster

        self.cluster = SimCluster(tmpdir, n_nodes=1)
        self.app_id = self.cluster.create_table(
            "bench", partition_count=n_partitions, replica_count=1)
        self.client = self.cluster.client("bench")
        self.client.refresh_config()
        node = next(iter(self.cluster.stubs.values()))
        self.servers = [node.get_replica((self.app_id, pidx)).server
                        for pidx in range(n_partitions)]
        self.replicas = [node.get_replica((self.app_id, pidx))
                         for pidx in range(n_partitions)]

    def manual_compact_all(self, rules_filter=None, device=None):
        """Partitions overlap on a thread pool: each one's device-filter
        round-trip would otherwise serialize (64 x dispatch + fetch)."""
        from pegasus_tpu.client.table import compact_partitions_parallel

        compact_partitions_parallel(self.servers, device=device,
                                    rules_filter=rules_filter)

    def close(self):
        self.cluster.close()


def build_cluster(tmpdir, n_records, n_partitions, seed):
    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT

    rng = np.random.default_rng(seed)
    bc = BenchCluster(tmpdir, n_partitions)
    now = epoch_now()

    t0 = time.perf_counter()
    n_hashkeys = max(1, n_records // 10)
    # load through the REPLICA WRITE PATH (batched mutations: many puts
    # share one mutation, parity mutation.cpp:390) grouped per partition
    per_pidx_ops = {pidx: [] for pidx in range(n_partitions)}
    i = 0
    for h in range(n_hashkeys):
        hk = b"user%08d" % h
        ops = per_pidx_ops[key_hash_parts(hk) % n_partitions]
        for sk_i in range(10):
            if i >= n_records:
                break
            ets = 0 if rng.random() > 0.10 else max(1, now - 100)
            value = b"field0=%064d" % i
            key = generate_key(hk, b"s%02d" % sk_i)
            ops.append(WriteOp(OP_PUT, (key, value, ets)))
            i += 1
    for pidx, ops in per_pidx_ops.items():
        r = bc.replicas[pidx]
        for off in range(0, len(ops), 1000):
            r.client_write(ops[off:off + 1000])
        bc.cluster.loop.run_until_idle()
    load_s = time.perf_counter() - t0
    bc.load_write_qps = round(i / load_s, 1)  # replicated write path rate
    _log(f"loaded {i} records in {load_s:.1f}s "
         f"({bc.load_write_qps:.0f} writes/s through 2PC)")

    t0 = time.perf_counter()
    bc.manual_compact_all()
    _log(f"compacted in {time.perf_counter() - t0:.1f}s")
    return bc


def run_scans(bc, n_ops, n_partitions, n_hashkeys, seed, record_goal=100,
              insert_frac=0.05, scan_batch=None):
    """95% scans / 5% inserts THROUGH the cluster read/write gates;
    returns (ops, records, elapsed_s).

    Scans are coalesced into per-partition batches of up to
    `scan_batch` (PEGBENCH_SCAN_BATCH): the server evaluates each
    unique touched block ONCE per batch on the device — the request-
    batching dispatch model (SURVEY §2.6), which is what amortizes
    per-dispatch latency on a real accelerator."""
    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.types import GetScannerRequest

    if scan_batch is None:
        scan_batch = int(os.environ.get("PEGBENCH_SCAN_BATCH", 32))
    rng = np.random.default_rng(seed)
    client = bc.client
    # zipfian-ish partition popularity
    ranks = rng.permutation(n_partitions)
    weights = 1.0 / (1.0 + ranks.astype(float))
    weights /= weights.sum()
    # zipfian-ish start-key popularity within the loaded keyspace
    zipf_u = rng.random(n_ops) ** 2.0
    pidx_choices = rng.choice(n_partitions, size=n_ops, p=weights)
    insert_draw = rng.random(n_ops)
    # pre-drawn so the per-op stream is IDENTICAL whatever insert_frac
    # is: the warmup/pre-touch passes (insert_frac=0) must plan the
    # same scans as the measured pass or blocks go un-pre-touched
    scan_lens = rng.integers(1, record_goal + 1, size=n_ops)
    insert_hks = rng.integers(0, 1 << 30, size=n_ops)

    records = 0
    pending: dict = {}
    pending_n = 0

    def flush_pending():
        nonlocal records, pending_n
        if not pending:
            return
        results = client.scan_multi(dict(pending))
        for pidx, resps in results.items():
            for resp in resps:
                records += len(resp.kvs)
                if resp.context_id >= 0:  # defensive: one_page set
                    client._read("clear_scanner", resp.context_id, pidx)
        pending.clear()
        pending_n = 0

    t0 = time.perf_counter()
    for op in range(n_ops):
        if insert_draw[op] < insert_frac:
            flush_pending()  # writes serialize against in-flight scans
            hk = b"user%08d" % int(insert_hks[op])
            client.set(hk, b"s00", b"inserted")
            continue
        pidx = int(pidx_choices[op])
        start_hk = b"user%08d" % int(zipf_u[op] * n_hashkeys)
        scan_len = int(scan_lens[op])
        pending.setdefault(pidx, []).append(GetScannerRequest(
            start_key=generate_key(start_hk, b""),
            batch_size=scan_len,
            validate_partition_hash=True,
            one_page=True))
        pending_n += 1
        if pending_n >= scan_batch:
            flush_pending()
    flush_pending()
    elapsed = time.perf_counter() - t0
    return n_ops, records, elapsed


def _point_get_stream(n_ops, n_hashkeys, seed):
    """The YCSB-C op stream (zipfian-ish key popularity, BASELINE
    config #1) as (partition_hash, (hash_key, sort_key)) pairs — the
    ONE derivation every point-get flavor (solo, client-batched,
    server-side) measures against, so the cross-flavor ratios always
    compare identical workloads."""
    import numpy as np

    from pegasus_tpu.base.key_schema import key_hash_parts

    rng = np.random.default_rng(seed)
    zipf_u = rng.random(n_ops) ** 2.0
    sk_draw = rng.integers(0, 10, size=n_ops)
    return [(key_hash_parts(b"user%08d" % int(zipf_u[op] * n_hashkeys)),
             (b"user%08d" % int(zipf_u[op] * n_hashkeys),
              b"s%02d" % int(sk_draw[op])))
            for op in range(n_ops)]


def run_point_gets(bc, n_ops, n_hashkeys, seed):
    """YCSB-C: 100% single-request point gets through the cluster read
    gate (the round-5 baseline shape)."""
    stream = _point_get_stream(n_ops, n_hashkeys, seed)
    client = bc.client
    hits = 0
    t0 = time.perf_counter()
    for _ph, (hk, sk) in stream:
        err, _v = client.get(hk, sk)
        hits += err == 0
    return n_ops, hits, time.perf_counter() - t0


def run_point_gets_batched(bc, n_ops, n_hashkeys, seed, batch=32):
    """The same YCSB-C op stream coalesced through the cross-partition
    read coordinator (`batch` gets per flush, client.point_read_multi)
    — the request-batching dispatch model applied to point reads."""
    from pegasus_tpu.base.key_schema import generate_key

    stream = _point_get_stream(n_ops, n_hashkeys, seed)
    client = bc.client
    n_part = client.partition_count
    hits = 0
    pending: dict = {}
    pending_n = 0

    def flush():
        nonlocal hits, pending_n
        if not pending:
            return
        for _pidx, results in client.point_read_multi(
                dict(pending)).items():
            for err, _v in results:
                hits += err == 0
        pending.clear()
        pending_n = 0

    t0 = time.perf_counter()
    for ph, (hk, sk) in stream:
        pending.setdefault(ph % n_part, []).append(
            ("get", generate_key(hk, sk), ph))
        pending_n += 1
        if pending_n >= batch:
            flush()
    flush()
    return n_ops, hits, time.perf_counter() - t0


def run_point_gets_server_side(bc, n_ops, n_hashkeys, seed, batch=0):
    """Server-side only (no client/transport layer): batch=0 drives
    on_get per op — the round-5 single-request hot loop — batch=N
    drives coordinator flushes of N ops spread across partitions."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.read_coordinator import point_read_multi

    stream = [(ph % len(bc.servers), generate_key(hk, sk), ph)
              for ph, (hk, sk)
              in _point_get_stream(n_ops, n_hashkeys, seed)]
    servers = bc.servers
    hits = 0
    if batch <= 1:
        t0 = time.perf_counter()
        for pidx, key, ph in stream:
            err, _v = servers[pidx].on_get(key, partition_hash=ph)
            hits += err == 0
        return n_ops, hits, time.perf_counter() - t0
    t0 = time.perf_counter()
    for off in range(0, len(stream), batch):
        groups: dict = {}
        for pidx, key, ph in stream[off:off + batch]:
            groups.setdefault(pidx, []).append(("get", key, ph))
        for results in point_read_multi(
                [(servers[pidx], ops) for pidx, ops in groups.items()]):
            for err, _v in results:
                hits += err == 0
    return n_ops, hits, time.perf_counter() - t0


def _point_miss_stream(n_ops, n_hashkeys, seed):
    """Uniform LOADED hashkeys with half the sort-key space absent
    (s00-s09 loaded, s10-s19 never written) — the round-8 miss
    workload. Misses on existing hashkeys fall INSIDE every table's
    key fence (the realistic "existing user, missing field" shape), so
    only a membership structure can skip the block probes; uniform
    draws defeat the location cache (each key is effectively seen
    once)."""
    import numpy as np

    from pegasus_tpu.base.key_schema import key_hash_parts

    rng = np.random.default_rng(seed)
    hk_draw = rng.integers(0, n_hashkeys, size=n_ops)
    sk_draw = rng.integers(0, 20, size=n_ops)
    return [(key_hash_parts(b"user%08d" % int(hk_draw[op])),
             (b"user%08d" % int(hk_draw[op]),
              b"s%02d" % int(sk_draw[op])))
            for op in range(n_ops)]


def _point_hot_stream(n_ops, n_hashkeys, seed, hot_set=256, hot_frac=0.9):
    """Hotspot stream (YCSB-D-ish): `hot_frac` of ops over `hot_set`
    (hash, sort) pairs, the rest uniform — the shape the node row cache
    serves without entering the LSM."""
    import numpy as np

    from pegasus_tpu.base.key_schema import key_hash_parts

    rng = np.random.default_rng(seed)
    hot_hks = rng.integers(0, n_hashkeys, size=hot_set)
    hot_sks = rng.integers(0, 10, size=hot_set)
    pick = rng.integers(0, hot_set, size=n_ops)
    uni_hk = rng.integers(0, n_hashkeys, size=n_ops)
    uni_sk = rng.integers(0, 10, size=n_ops)
    hot_draw = rng.random(n_ops)
    out = []
    for op in range(n_ops):
        if hot_draw[op] < hot_frac:
            hk = b"user%08d" % int(hot_hks[pick[op]])
            sk = b"s%02d" % int(hot_sks[pick[op]])
        else:
            hk = b"user%08d" % int(uni_hk[op])
            sk = b"s%02d" % int(uni_sk[op])
        out.append((key_hash_parts(hk), (hk, sk)))
    return out


def deepen_l0(bc, n_hashkeys, seed, n_l0=4, rows_per_flush=500):
    """Round-8 store state: `n_l0` overlay flushes whose rows interleave
    across the loaded hashkey space (distinct sort keys, so the base
    dataset stays fully visible and identity gates are unaffected).
    Every L0 table's key fence then spans the whole probed range — each
    point get must consider every L0 table, exactly the deep-L0 shape
    the bloom layer answers with a bit probe instead of a block decode."""
    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT

    step = max(1, n_hashkeys // rows_per_flush)
    for g in range(n_l0):
        per_pidx: dict = {}
        for h in range(g, n_hashkeys, step):
            hk = b"user%08d" % h
            per_pidx.setdefault(
                key_hash_parts(hk) % len(bc.servers), []).append(
                WriteOp(OP_PUT,
                        (generate_key(hk, b"zz%02d" % g),
                         b"l0-%d" % g, 0)))
        for pidx, ops in per_pidx.items():
            bc.replicas[pidx].client_write(ops)
        bc.cluster.loop.run_until_idle()
        for s in bc.servers:
            s.flush()


def run_point_stream_server_side(bc, stream, batch=32):
    """Server-side batched point gets over a prebuilt (ph, (hk, sk))
    stream — the round-8 measurement loop, shared by the baseline and
    filtered passes so only the flag state differs."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.read_coordinator import point_read_multi

    resolved = [(ph % len(bc.servers), generate_key(hk, sk), ph)
                for ph, (hk, sk) in stream]
    servers = bc.servers
    hits = 0
    t0 = time.perf_counter()
    for off in range(0, len(resolved), batch):
        groups: dict = {}
        for pidx, key, ph in resolved[off:off + batch]:
            groups.setdefault(pidx, []).append(("get", key, ph))
        for results in point_read_multi(
                [(servers[pidx], ops) for pidx, ops in groups.items()]):
            for err, _v in results:
                hits += err == 0
    return len(resolved), hits, time.perf_counter() - t0


def collect_point_results(bc, stream, batch=32):
    """Per-op (err, value) tuples in stream order — the round-8
    byte-identity gate runs this once per flag mode and compares."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.read_coordinator import point_read_multi

    resolved = [(ph % len(bc.servers), generate_key(hk, sk), ph)
                for ph, (hk, sk) in stream]
    out = []
    for off in range(0, len(resolved), batch):
        groups: dict = {}
        order = []
        for pidx, key, ph in resolved[off:off + batch]:
            lst = groups.setdefault(pidx, [])
            order.append((pidx, len(lst)))
            lst.append(("get", key, ph))
        pidxs = list(groups)
        res = point_read_multi(
            [(bc.servers[p], groups[p]) for p in pidxs])
        by_pidx = dict(zip(pidxs, res))
        out.extend(tuple(by_pidx[p][i]) for p, i in order)
    return out


def _write_put_stream(n_ops, seed, tag=b"wb"):
    """Deterministic put stream over a dedicated keyspace (never
    collides with the loaded scan/get dataset): (partition_hash,
    (hash_key, sort_key), value) triples — the ONE derivation every
    write flavor (solo, client-batched, server-side) measures against."""
    import numpy as np

    from pegasus_tpu.base.key_schema import key_hash_parts

    rng = np.random.default_rng(seed)
    hk_draw = rng.integers(0, max(1, n_ops // 4), size=n_ops)
    out = []
    for op in range(n_ops):
        hk = tag + b"%08d" % int(hk_draw[op])
        sk = b"s%04d" % op
        out.append((key_hash_parts(hk, sk), (hk, sk),
                    b"wval-%06d" % op))
    return out


def run_puts(bc, n_ops, seed, tag=b"wb"):
    """Single-request puts through the full client write path (one
    client_write RPC + one 2PC round per op) — the write-side twin of
    run_point_gets."""
    stream = _write_put_stream(n_ops, seed, tag)
    client = bc.client
    errs = 0
    t0 = time.perf_counter()
    for _ph, (hk, sk), v in stream:
        errs += client.set(hk, sk, v) != 0
    return n_ops, errs, time.perf_counter() - t0


def run_puts_batched(bc, n_ops, seed, batch=32, tag=b"wb"):
    """The same put stream coalesced through write_multi (`batch` ops
    per flush): one client_write_batch RPC per node per flush, one
    mutation per touched partition, one group-commit window."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.rpc.codec import OP_PUT

    stream = _write_put_stream(n_ops, seed, tag)
    client = bc.client
    n_part = client.partition_count
    errs = 0
    pending: dict = {}
    pending_n = 0

    def flush():
        nonlocal errs, pending_n
        if not pending:
            return
        for _pidx, results in client.write_multi(dict(pending)).items():
            for err in results:
                errs += err != 0
        pending.clear()
        pending_n = 0

    t0 = time.perf_counter()
    for ph, (hk, sk), v in stream:
        pending.setdefault(ph % n_part, []).append(
            (OP_PUT, (generate_key(hk, sk), v, 0), ph))
        pending_n += 1
        if pending_n >= batch:
            flush()
    flush()
    return n_ops, errs, time.perf_counter() - t0


def run_puts_server_side(bc, n_ops, seed, batch=0, tag=b"wbs"):
    """Server-side only (no client/transport): batch=0 drives one
    replica.client_write (one mutation) per op; batch=N groups each
    window's ops per partition into ONE client_write — the mutation
    coalescing + vectorized-apply path in isolation."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT

    stream = [(ph % len(bc.replicas), generate_key(hk, sk), v)
              for ph, (hk, sk), v in _write_put_stream(n_ops, seed, tag)]
    replicas = bc.replicas
    pump = bc.cluster.loop.run_until_idle
    window = next(iter(bc.cluster.stubs.values())).write_window
    if batch <= 1:
        t0 = time.perf_counter()
        for pidx, key, v in stream:
            replicas[pidx].client_write([WriteOp(OP_PUT, (key, v, 0))])
            pump()
        return n_ops, time.perf_counter() - t0
    t0 = time.perf_counter()
    for off in range(0, len(stream), batch):
        groups: dict = {}
        for pidx, key, v in stream[off:off + batch]:
            groups.setdefault(pidx, []).append(
                WriteOp(OP_PUT, (key, v, 0)))
        # one group-commit window per flush — exactly what a
        # client_write_batch dispatch opens on a serving node
        with window:
            for pidx, ops in groups.items():
                replicas[pidx].client_write(ops)
        pump()
    return n_ops, time.perf_counter() - t0


def verify_write_batch_identity(bc, seed, n=256) -> bool:
    """Acceptance gate: the batched write path must produce the same
    per-op results as the solo handler AND leave identical user-visible
    state — asserted over twin keyspaces carrying the same payloads
    (hits, overwrites, and deletes alike)."""
    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.rpc.codec import OP_PUT, OP_REMOVE

    client = bc.client
    n_part = client.partition_count
    stream = _write_put_stream(n, seed, tag=b"id")
    solo_res = []
    for i, (_ph, (hk, sk), v) in enumerate(stream):
        solo_res.append(client.set(b"solo-" + hk, sk, v))
        if i % 5 == 0:  # overwrite mix
            solo_res.append(client.set(b"solo-" + hk, sk, v + b"!"))
        if i % 9 == 0:
            solo_res.append(client.delete(b"solo-" + hk, sk))
    groups: dict = {}
    order = []
    for i, (_ph, (hk, sk), v) in enumerate(stream):
        def add(op, hk=hk, sk=sk):
            ph = key_hash_parts(b"batch-" + hk, sk)
            pidx = ph % n_part
            lst = groups.setdefault(pidx, [])
            order.append((pidx, len(lst)))
            lst.append((op[0], op[1], ph))
        add((OP_PUT, (generate_key(b"batch-" + hk, sk), v, 0)))
        if i % 5 == 0:
            add((OP_PUT, (generate_key(b"batch-" + hk, sk), v + b"!", 0)))
        if i % 9 == 0:
            add((OP_REMOVE, (generate_key(b"batch-" + hk, sk),)))
    got = client.write_multi(groups)
    batch_res = [got[p][i] for p, i in order]
    if batch_res != solo_res:
        return False
    for _ph, (hk, sk), _v in stream:
        if client.get(b"solo-" + hk, sk) != client.get(b"batch-" + hk, sk):
            return False
    return True


def verify_point_batch_identity(bc, n_hashkeys, seed, n=512) -> bool:
    """Acceptance gate: batched results must be BYTE-identical to the
    single-request path over a sampled key set (hits, misses, and
    expired records alike)."""
    from pegasus_tpu.base.key_schema import generate_key

    stream = _point_get_stream(n, n_hashkeys, seed)
    client = bc.client
    n_part = client.partition_count
    groups: dict = {}
    expect: dict = {}
    for ph, (hk, sk) in stream:
        pidx = ph % n_part
        groups.setdefault(pidx, []).append(
            ("get", generate_key(hk, sk), ph))
        expect.setdefault(pidx, []).append(tuple(client.get(hk, sk)))
    got = client.point_read_multi(groups)
    return all(tuple(map(tuple, got[p])) == tuple(expect[p])
               for p in groups)


def measure_scan_phase(jax, device, bc, n_ops, n_partitions, n_hashkeys,
                      seed):
    """reset -> warmup (compile + device block caches) -> measure.

    A MaskPrefresher runs for the whole phase (as on a production node,
    node_main.py): the per-second mask refresh — the only device work in
    steady-state serving — happens in the background, so the measured
    path is the host assembly speed both backends share plus whatever
    device latency the prefresher FAILS to hide."""
    from pegasus_tpu.server.scan_coordinator import MaskPrefresher

    prefresher = MaskPrefresher(bc.servers, device=device).start()
    try:
        return _measure_scan_phase(jax, device, bc, n_ops, n_partitions,
                                   n_hashkeys, seed)
    finally:
        prefresher.stop()


def _measure_scan_phase(jax, device, bc, n_ops, n_partitions, n_hashkeys,
                        seed):
    with jax.default_device(device):
        bc.manual_compact_all(device=device)
        # warmup covers both compiled stack shapes AND the overlay path
        # (inserts) so the measured phase pays no first-touch compiles
        run_scans(bc, 120, n_partitions, n_hashkeys, seed, insert_frac=0)
        run_scans(bc, 60, n_partitions, n_hashkeys, seed + 1)
        bc.manual_compact_all(device=device)
        # steady-state pre-touch: the compact above rewrote the SSTs, so
        # without this pass the measured run pays one first-touch
        # host->device block upload per block — a load-time cost, not
        # scan throughput. Same seed + insert_frac=0 touches a superset
        # of the measured scans' blocks without mutating anything, so
        # BOTH phases measure with resident device block caches (on a
        # real chip: blocks already in HBM — the serving steady state).
        run_scans(bc, n_ops, n_partitions, n_hashkeys, seed,
                  insert_frac=0)
        # best-of-3: block masks are cached per wall-clock second (TTL
        # validity granularity), so a sub-second pass that happens to
        # straddle a second boundary recomputes part of its masks —
        # taking the best pass measures the steady state, not the luck
        # of the start instant, identically for both phases
        best = None
        for i in range(3):
            if i:
                # re-compact so every pass starts from the same server
                # state — pass 1's 5% inserts would otherwise push later
                # passes onto the overlay-merge path and 'best' would
                # just mean 'first'
                bc.manual_compact_all(device=device)
                run_scans(bc, n_ops, n_partitions, n_hashkeys, seed,
                          insert_frac=0)
            ops, recs, secs = run_scans(bc, n_ops, n_partitions,
                                        n_hashkeys, seed)
            if best is None or secs < best[2]:
                best = (ops, recs, secs)
        ops, recs, secs = best
    return ops, recs, secs


def data_bytes(bc) -> int:
    total = 0
    for srv in bc.servers:
        sst = os.path.join(srv.engine.data_dir, "sst")
        for name in os.listdir(sst):
            total += os.path.getsize(os.path.join(sst, name))
    return total


def _compact_rules_filter():
    """BASELINE config #4: hashkey-prefix delete + a sortkey-range
    delete (compaction_filter_rule.h:99,121,141) plus one
    MATCH_ANYWHERE hashkey pattern — the ruleset class whose per-byte
    matching work the accelerator's upload buys in one pass."""
    from pegasus_tpu.ops.compaction_rules import compile_rules

    return compile_rules([
        {"op": "delete_key",
         "rules": [{"type": "hashkey_pattern", "match": "prefix",
                    "pattern": "user000001"}]},
        {"op": "delete_key",
         "rules": [{"type": "hashkey_pattern", "match": "anywhere",
                    "pattern": "7777"},
                   {"type": "sortkey_pattern", "match": "prefix",
                    "pattern": "s0"}]},
    ])


def build_compact_store(data_dir: str, n_records: int,
                        expired_frac: float, n_parts: int, seed: int,
                        value_kind: str = "random"):
    """Build `n_parts` partition stores totalling n_records directly as
    columnar L1 runs — the bulk-load ingest shape (externally-built
    SSTs adopted whole, parity: bulk load OP_INGEST) — with
    `expired_frac` of records carrying expired TTLs (a TTL-retention
    sweep: the BASELINE config #3 workload at the scale where operators
    actually run manual compaction). Returns [StorageEngine]."""
    import numpy as np

    from pegasus_tpu.base.crc import crc64_batch
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.storage.engine import StorageEngine
    from pegasus_tpu.storage.lsm import L1_RUN_CAPACITY
    from pegasus_tpu.storage.sstable import SSTableWriter

    VALUE = 100
    BLOCK = 4096  # archival-table block size: 4x fewer per-block
    # host round-trips through the rewrite than the serving default
    now = epoch_now()
    per_part = n_records // n_parts
    engines = []
    for part in range(n_parts):
        rng = np.random.default_rng(seed + part)
        pdir = os.path.join(data_dir, f"p{part}")
        sst = os.path.join(pdir, "sst")
        os.makedirs(sst, exist_ok=True)
        names = []
        seq = 0
        writer = None
        in_run = 0
        meta = {"last_flushed_decree": 1, "data_version": 1}
        base0 = part * per_part
        for base in range(0, per_part, BLOCK):
            n = min(BLOCK, per_part - base)
            idx = np.arange(base0 + base, base0 + base + n)
            hks = idx // 10
            sks = idx % 10
            keys = np.zeros((n, 32), dtype=np.uint8)
            keys[:, 1] = 12  # BE u16 hashkey length
            keys[:, 2:14] = np.frombuffer(
                b"".join(b"user%08d" % h for h in hks),
                dtype=np.uint8).reshape(n, 12)
            keys[:, 14:17] = np.frombuffer(
                b"".join(b"s%02d" % s for s in sks),
                dtype=np.uint8).reshape(n, 3)
            key_len = np.full(n, 17, dtype=np.int32)
            ets = np.where(rng.random(n) < expired_frac,
                           np.uint32(max(1, now - 100)),
                           np.uint32(0)).astype(np.uint32)
            flags = np.zeros(n, dtype=np.uint8)
            offs = np.arange(n + 1, dtype=np.uint32) * VALUE
            if value_kind == "templated":
                # realistic structured payloads (field names + bounded
                # enumerations + a short random tail) — the workload
                # class where value compression actually pays, vs the
                # incompressible uniform-random default
                tails = rng.integers(97, 123, size=(n, 24),
                                     dtype=np.uint8)

                def _tv(j, i):
                    head = (b"ts=1700000000|city=%03d|tier=%d|"
                            b"status=active|score=%02d|"
                            % (i % 997, i % 5, i % 100)) \
                        + tails[j].tobytes()
                    return head + b"." * (VALUE - len(head))

                heap = b"".join(_tv(j, int(i))
                                for j, i in enumerate(idx))
            else:
                heap = rng.integers(32, 126, size=n * VALUE,
                                    dtype=np.uint8).tobytes()
            hash_lo = (crc64_batch(keys, np.full(n, 12, dtype=np.int64),
                                   start=2)
                       & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            if writer is None:
                writer = SSTableWriter(os.path.join(sst, f"l1-{seq}.sst"),
                                       meta=meta, async_io=True,
                                       block_capacity=BLOCK)
                seq += 1
            writer.add_block_columnar(keys, key_len, ets, hash_lo,
                                      flags, offs, heap)
            in_run += n
            if in_run >= L1_RUN_CAPACITY:
                writer.finish()
                names.append(os.path.basename(writer.path))
                writer = None
                in_run = 0
        if writer is not None:
            writer.finish()
            names.append(os.path.basename(writer.path))
        with open(os.path.join(sst, "MANIFEST.json"), "w") as f:
            json.dump({"seq": seq, "l1": names}, f)
        engines.append(StorageEngine(pdir))
    return engines


def _store_bytes(engines) -> int:
    total = 0
    for eng in engines:
        sst = os.path.join(eng.data_dir, "sst")
        for name in os.listdir(sst):
            if name.endswith(".sst"):
                total += os.path.getsize(os.path.join(sst, name))
    return total


def measure_compaction_scaled(jax, device, tmpdir, mode: str,
                              gb: float, expired_frac: float,
                              seed: int, n_parts: int = 8):
    """Manual compaction GB/s at BASELINE scale (config #3/#4).

    Builds a fresh deterministic table PER (mode, backend) — the
    measured pass must face identical drop work on both backends — then
    times ONE full bulk compaction of every partition on a thread pool
    (disk IO + native gathers overlap the device/XLA filter waves).
    Returns (input_gb_per_s, seconds, in_bytes, out_bytes)."""
    import shutil
    from concurrent.futures import ThreadPoolExecutor

    rules_filter = _compact_rules_filter() if mode == "rules" else None
    n_records = int(gb * 1e9 / 145)  # ~145 B/record on disk
    data_dir = os.path.join(tmpdir, f"compact-{mode}")
    if os.path.exists(data_dir):
        shutil.rmtree(data_dir)
    t0 = time.perf_counter()
    # n_parts + 1 IDENTICAL partitions: partition 0 is the untimed
    # compile/warm pass. Same record count -> the same chunk row-bucket
    # sequence -> every XLA program shape the timed partitions will use
    # compiles on this backend BEFORE the clock starts (a tiny warm
    # store missed the 256k-row bucket, so the first measured pass paid
    # device compiles inside the timing — observed as a consistent
    # first-slot deficit on identical backends).
    per_part = n_records // n_parts
    engines = build_compact_store(
        data_dir, per_part * (n_parts + 1),
        expired_frac if mode == "ttl" else 0.05, n_parts + 1, seed)
    _log(f"compact[{mode}] fixture: {per_part * n_parts} records + "
         f"1 warm partition built in {time.perf_counter() - t0:.1f}s")
    warm_engine = engines[0]
    engines = engines[1:]
    with jax.default_device(device):
        warm_engine.manual_compact(rules_filter=rules_filter)
    warm_engine.close()

    # settle the fixture's dirty pages before timing: the measured pass
    # must compete with its OWN writeback, not the builder's
    os.sync()

    size_before = _store_bytes(engines)
    with jax.default_device(device):
        t0 = time.perf_counter()

        def one(eng):
            with jax.default_device(device):
                eng.manual_compact(rules_filter=rules_filter)

        with ThreadPoolExecutor(max_workers=min(8, n_parts)) as ex:
            for f in [ex.submit(one, e) for e in engines]:
                f.result()
        secs = time.perf_counter() - t0
    size_after = _store_bytes(engines)
    for eng in engines:
        eng.close()
    shutil.rmtree(data_dir, ignore_errors=True)
    return size_before / max(secs, 1e-9) / 1e9, secs, size_before, \
        size_after


def _compact_sample_digest(engines, seed, per_part=3000):
    """Deterministic record-level digest of post-compaction contents:
    a bounded iterate() prefix plus scattered point gets per partition
    — the identity gate between the compressed and uncompressed
    same-run stores."""
    import hashlib
    import itertools

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key

    h = hashlib.sha256()
    rng = np.random.default_rng(seed)
    for eng in engines:
        for key, value, ets in itertools.islice(eng.iterate(),
                                                per_part):
            h.update(key)
            h.update(value)
            h.update(b"%d" % ets)
        for _ in range(64):
            k = generate_key(b"user%08d" % int(rng.integers(0, 1 << 24)),
                             b"s%02d" % int(rng.integers(0, 10)))
            h.update(repr(eng.get(k)).encode())
    return h.hexdigest()


def measure_compressed_compact(jax, device, tmpdir, gb: float,
                               expired_frac: float, seed: int,
                               n_parts: int = 8):
    """compact_compressed phase (round-11): the SAME logical dataset is
    built twice — block_codec=none and =dcz — and one full bulk
    compaction of every partition is timed on each. Reported per codec:
    wall seconds, on-disk input/output bytes, disk GB/s, and EFFECTIVE
    input GB/s (logical uncompressed bytes / seconds — the number that
    can pass the raw-disk ceiling when compressed output shrinks the
    write side). Identity-gated record-for-record between the two
    stores."""
    import shutil
    from concurrent.futures import ThreadPoolExecutor

    from pegasus_tpu.utils.flags import FLAGS

    n_records = int(gb * 1e9 / 145)  # ~145 B/record in the raw format
    per_part = n_records // n_parts
    out = {}
    logical_in = None
    old_codec = FLAGS.get("pegasus.storage", "block_codec")
    try:
        for codec in ("none", "dcz"):
            FLAGS.set("pegasus.storage", "block_codec", codec)
            data_dir = os.path.join(tmpdir, f"ccompact-{codec}")
            if os.path.exists(data_dir):
                shutil.rmtree(data_dir)
            t0 = time.perf_counter()
            engines = build_compact_store(
                data_dir, per_part * (n_parts + 1), expired_frac,
                n_parts + 1, seed, value_kind="templated")
            _log(f"compact_compressed[{codec}] fixture: "
                 f"{per_part * n_parts} records in "
                 f"{time.perf_counter() - t0:.1f}s")
            warm = engines[0]
            engines = engines[1:]
            with jax.default_device(device):
                warm.manual_compact()
            warm.close()
            os.sync()
            size_before = _store_bytes(engines)
            if codec == "none":
                logical_in = size_before
            with jax.default_device(device):
                t0 = time.perf_counter()

                def one(eng):
                    with jax.default_device(device):
                        eng.manual_compact()

                # pool sized to the machine, not the partition count:
                # dcz compaction is CPU-dense (GIL-released native
                # deflate), and 8 workers on a 2-core box thrash both
                # codecs while taxing dcz hardest (measured 0.83x vs
                # 1.04x at workers=cpu_count on the same fixture)
                with ThreadPoolExecutor(
                        max_workers=min(os.cpu_count() or 4,
                                        n_parts)) as ex:
                    for f in [ex.submit(one, e) for e in engines]:
                        f.result()
                secs = time.perf_counter() - t0
            size_after = _store_bytes(engines)
            ratios = [t.codec_stats for e in engines
                      for t in e.lsm.l1_runs if t.codec_stats]
            raw_b = sum(r["raw_bytes"] for r in ratios)
            stored_b = sum(r["stored_bytes"] for r in ratios)
            digest = _compact_sample_digest(engines, seed + 1)
            for eng in engines:
                eng.close()
            shutil.rmtree(data_dir, ignore_errors=True)
            out[codec] = {
                "seconds": round(secs, 3),
                "in_bytes": size_before,
                "out_bytes": size_after,
                "disk_gb_per_s": round(size_before / secs / 1e9, 4),
                "effective_input_gb_per_s": round(
                    (logical_in or size_before) / secs / 1e9, 4),
                "output_compression_ratio": (
                    round(stored_b / raw_b, 4) if raw_b else None),
                "sample_digest": digest,
            }
            _log(f"compact_compressed[{codec}]: {secs:.1f}s, "
                 f"disk {out[codec]['disk_gb_per_s']:.3f} GB/s, "
                 f"effective {out[codec]['effective_input_gb_per_s']:.3f}"
                 f" GB/s")
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old_codec)
    out["identity_ok"] = (out["none"]["sample_digest"]
                          == out["dcz"]["sample_digest"])
    out["effective_speedup"] = round(
        out["dcz"]["effective_input_gb_per_s"]
        / max(out["none"]["effective_input_gb_per_s"], 1e-9), 3)
    return out


def measure_pipelined_compact(jax, device, tmpdir, gb: float,
                              expired_frac: float, seed: int,
                              n_parts: int = 8):
    """compact_pipelined phase (round-12): the SAME logical dataset is
    built twice and one full bulk compaction of every partition is
    timed with the staged pipeline OFF (serial windowed path) and ON
    (read/filter/write threads + bounded queues). Identity-gated
    record-for-record; also records the placement cost model's
    offload-pays verdict for the phase's filter batches."""
    import shutil

    from pegasus_tpu.ops.placement import offload_breakdown
    from pegasus_tpu.storage.compact_pipeline import pipeline_window
    from pegasus_tpu.utils.flags import FLAGS

    n_records = int(gb * 1e9 / 145)
    per_part = n_records // n_parts
    out = {}
    old = FLAGS.get("pegasus.storage", "compact_pipeline")
    try:
        for mode in ("serial", "pipelined"):
            FLAGS.set("pegasus.storage", "compact_pipeline",
                      mode == "pipelined")
            data_dir = os.path.join(tmpdir, f"pcompact-{mode}")
            if os.path.exists(data_dir):
                shutil.rmtree(data_dir)
            t0 = time.perf_counter()
            engines = build_compact_store(
                data_dir, per_part * (n_parts + 1), expired_frac,
                n_parts + 1, seed, value_kind="templated")
            _log(f"compact_pipelined[{mode}] fixture: "
                 f"{per_part * n_parts} records in "
                 f"{time.perf_counter() - t0:.1f}s")
            warm = engines[0]
            engines = engines[1:]
            with jax.default_device(device):
                warm.manual_compact()
            warm.close()
            os.sync()
            size_before = _store_bytes(engines)
            with jax.default_device(device):
                t0 = time.perf_counter()
                # ONE compaction at a time — the cluster scheduler's
                # staggered shape (the coordinator grants one node's
                # heavy compaction at a time, and intra-compaction
                # overlap is exactly what this phase isolates; the
                # pool-parallel shape is compact_compressed's)
                for eng in engines:
                    eng.manual_compact()
                secs = time.perf_counter() - t0
            size_after = _store_bytes(engines)
            digest = _compact_sample_digest(engines, seed + 1)
            for eng in engines:
                eng.close()
            shutil.rmtree(data_dir, ignore_errors=True)
            out[mode] = {
                "seconds": round(secs, 3),
                "in_bytes": size_before,
                "out_bytes": size_after,
                "input_gb_per_s": round(size_before / secs / 1e9, 4),
                "sample_digest": digest,
            }
            _log(f"compact_pipelined[{mode}]: {secs:.1f}s, "
                 f"{out[mode]['input_gb_per_s']:.3f} GB/s input")
    finally:
        FLAGS.set("pegasus.storage", "compact_pipeline", old)
    out["identity_ok"] = (out["serial"]["sample_digest"]
                          == out["pipelined"]["sample_digest"])
    out["speedup"] = round(out["pipelined"]["input_gb_per_s"]
                           / max(out["serial"]["input_gb_per_s"],
                                 1e-9), 3)
    # offload-pays breakdown for this phase's filter batches: one
    # pipeline window of ~145B records (the TTL workload class) and
    # the rules class at the same size — PERF round-12's table
    window_bytes = pipeline_window() * 4096 * 36  # keys+cols/record
    out["offload_breakdown"] = {
        w: offload_breakdown(w, window_bytes) for w in ("ttl", "rules")}
    return out


def measure_health_overhead(tmpdir, seed: int):
    """Flight-recorder overhead phase (round 16): the SAME batched
    point-get and write_multi streams through a SimCluster with the
    recorder + health rules OFF vs ON at the default cadence —
    same-run, identity-gated (per-mode result digests must match).
    The acceptance gate: recorder-on within 2% of recorder-off on both
    the read and the write phase (median of 3 reps); the ring-memory
    byte cost is recorded alongside."""
    import hashlib
    import shutil

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import expire_ts_from_ttl
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.flags import FLAGS

    n_keys = int(os.environ.get("PEGBENCH_HEALTH_KEYS", 512))
    n_rounds = int(os.environ.get("PEGBENCH_HEALTH_ROUNDS", 40))
    reps = 3
    batch = 32
    cdir = os.path.join(tmpdir, "health_overhead")
    cluster = SimCluster(cdir, n_nodes=3, seed=seed)
    try:
        cluster.create_table("ho", partition_count=4, replica_count=3)
        client = cluster.client("ho")
        keys = [(b"hk%05d" % i, b"s") for i in range(n_keys)]
        for start in range(0, n_keys, batch):
            groups = {}
            for hk, sk in keys[start:start + batch]:
                ph = key_hash_parts(hk, sk)
                groups.setdefault(ph % 4, []).append(
                    (OP_PUT, (generate_key(hk, sk), b"v" * 64,
                              expire_ts_from_ttl(0)), ph))
            client.write_multi(groups)

        # ONE fixed op order for every pass: the warm-up drives the
        # store to this order's write fixed point, so every measured
        # pass reads identical state
        order = np.random.default_rng(seed + 1).integers(
            0, n_keys, size=n_rounds * batch)

        def one_pass(digest):
            # the timer round fires on the SAME fixed schedule in both
            # modes (every 8 op rounds); sim time compresses ~1000x, so
            # this schedule ticks the recorder FAR above its deployed
            # cadence — the A/B bounds the always-on hook cost, and the
            # per-tick cost is measured separately below and normalized
            # to the default cadence
            t0 = time.perf_counter()
            for r in range(n_rounds):
                groups = {}
                for j in order[r * batch:(r + 1) * batch]:
                    hk, sk = keys[int(j)]
                    ph = key_hash_parts(hk, sk)
                    groups.setdefault(ph % 4, []).append(
                        ("get", generate_key(hk, sk), ph))
                res = client.point_read_multi(groups)
                for pidx in sorted(res):
                    for st, val in res[pidx]:
                        digest.update(b"%d" % st)
                        digest.update(val)
                if r % 8 == 7:
                    cluster.step()
            t_read = time.perf_counter() - t0
            t0 = time.perf_counter()
            for r in range(n_rounds):
                groups = {}
                for j in order[r * batch:(r + 1) * batch]:
                    hk, sk = keys[int(j)]
                    ph = key_hash_parts(hk, sk)
                    groups.setdefault(ph % 4, []).append(
                        (OP_PUT, (generate_key(hk, sk),
                                  b"w%d" % r, expire_ts_from_ttl(0)),
                         ph))
                res = client.write_multi(groups)
                for pidx in sorted(res):
                    for st in res[pidx]:
                        digest.update(b"%d" % st)
                if r % 8 == 7:
                    cluster.step()
            t_write = time.perf_counter() - t0
            return t_read, t_write

        FLAGS.set("pegasus.health", "recorder_enabled", False)
        one_pass(hashlib.sha256())  # unmeasured warm-up
        modes = [("recorder_off", False), ("recorder_on", True)]
        out = {"keys": n_keys,
               "ops_per_mode": n_rounds * batch * 2 * reps}
        ops_n = n_rounds * batch
        times = {name: ([], []) for name, _e in modes}
        hashes = {name: hashlib.sha256() for name, _e in modes}
        # modes interleave across reps so slow drift hits both equally
        for _rep in range(reps):
            for name, enabled in modes:
                FLAGS.set("pegasus.health", "recorder_enabled", enabled)
                tr, tw = one_pass(hashes[name])
                times[name][0].append(tr)
                times[name][1].append(tw)
        digests = {}
        for name, _e in modes:
            reads, writes = times[name]
            digests[name] = hashes[name].hexdigest()
            out[name] = {
                "read_qps": round(ops_n * reps / sum(reads), 1),
                "write_qps": round(ops_n * reps / sum(writes), 1),
                "read_s_median": round(sorted(reads)[1], 4),
                "write_s_median": round(sorted(writes)[1], 4),
            }
        FLAGS.set("pegasus.health", "recorder_enabled", True)
        base, on = out["recorder_off"], out["recorder_on"]
        out["read_overhead"] = round(
            on["read_s_median"] / base["read_s_median"] - 1.0, 4)
        out["write_overhead"] = round(
            on["write_s_median"] / base["write_s_median"] - 1.0, 4)
        out["identity_ok"] = len(set(digests.values())) == 1
        # per-tick cost, normalized to the DEFAULT cadence: in a real
        # deployment the recorder fires once per interval of WALL time,
        # so its steady-state cost fraction is tick_seconds / interval
        # (the sim A/B above over-ticks by the time-compression factor)
        interval = FLAGS.get("pegasus.health", "recorder_interval_s")
        n_ticks = 30
        tick_s_total = 0.0
        for t in range(n_ticks):
            # touch the store between ticks so the timed tick pays the
            # LOADED cost — percentile windows re-sort, counters append
            # — not the idle fast path (version caches + zero slides)
            groups = {}
            for j in order[(t * 16) % (n_keys - 16):][:16]:
                hk, sk = keys[int(j)]
                ph = key_hash_parts(hk, sk)
                groups.setdefault(ph % 4, []).append(
                    (OP_PUT, (generate_key(hk, sk), b"t%d" % t,
                              expire_ts_from_ttl(0)), ph))
            client.write_multi(groups)
            cluster.loop.run_for(interval)  # advance sim time only
            t0 = time.perf_counter()
            for stub in cluster.stubs.values():
                stub.recorder.tick(force=True)
                stub.health.evaluate()
            tick_s_total += time.perf_counter() - t0
        tick_s = tick_s_total / n_ticks / len(cluster.stubs)
        out["tick_ms"] = round(tick_s * 1000.0, 3)
        out["cadence_overhead"] = round(tick_s / interval, 4)
        # the ring-memory cost of the on-mode rings, per node
        out["ring_bytes"] = {
            name: stub.recorder.nbytes()
            for name, stub in sorted(cluster.stubs.items())}
        out["ring_bytes_total"] = sum(out["ring_bytes"].values())
        out["events_fired"] = sum(
            stub.health.events_total
            for stub in cluster.stubs.values())
        # the bench gate: at the DEFAULT cadence the recorder+rules
        # tick must cost <=2% of a core — cadence_overhead is exactly
        # that fraction; the same-run A/B above is reported for the
        # record but over-ticks by the sim's time-compression factor
        # (~1000x the deployed cadence), so its raw ratio re-measures
        # tick cost at an unrealistic rate and does not gate. Results
        # must be identical and a steady healthy run must fire zero
        # events.
        out["gate_ok"] = bool(
            out["identity_ok"]
            and out["cadence_overhead"] <= 0.02
            and out["events_fired"] == 0)
        return out
    finally:
        FLAGS.set("pegasus.health", "recorder_enabled", True)
        cluster.close()
        shutil.rmtree(cdir, ignore_errors=True)


def measure_perfctx_overhead(tmpdir, seed: int):
    """PerfContext overhead phase (round 18): the SAME batched
    point-get and ranged multi_get streams through a SimCluster with
    per-op cost-vector collection hard-OFF vs ON — same-run,
    identity-gated (per-mode result digests must match). The gate:
    contexts-enabled read AND scan paths within 2% of hard-off (median
    of 3 reps, modes interleaved), per the health_overhead
    convention."""
    import hashlib
    import shutil

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import expire_ts_from_ttl
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.flags import FLAGS

    n_hks = int(os.environ.get("PEGBENCH_PERFCTX_KEYS", 256))
    n_sks = 8  # sort keys per hashkey: the ranged leg reads real pages
    # enough rounds that each leg's median is hundreds of ms — 30-round
    # legs measured ~16 ms and the A/B was pure scheduler noise (±5%)
    n_rounds = int(os.environ.get("PEGBENCH_PERFCTX_ROUNDS", 240))
    reps = 3
    batch = 32
    cdir = os.path.join(tmpdir, "perfctx_overhead")
    cluster = SimCluster(cdir, n_nodes=3, seed=seed)
    try:
        cluster.create_table("pc", partition_count=4, replica_count=3)
        client = cluster.client("pc")
        hks = [b"phk%05d" % i for i in range(n_hks)]
        for start in range(0, n_hks, batch):
            groups = {}
            for hk in hks[start:start + batch]:
                ph = key_hash_parts(hk, b"")
                for j in range(n_sks):
                    groups.setdefault(ph % 4, []).append(
                        (OP_PUT, (generate_key(hk, b"s%02d" % j),
                                  b"v" * 64, expire_ts_from_ttl(0)),
                         ph))
            client.write_multi(groups)
        # compact so the ranged leg rides the columnar scan path (the
        # instrumented mask/kernel pipeline, not the overlay merge)
        for stub in cluster.stubs.values():
            for r in stub.replicas.values():
                r.server.engine.flush()
                r.server.engine.manual_compact()

        # ONE fixed op order for every pass (write fixed point: the
        # data is read-only here, so every pass reads identical state)
        order = np.random.default_rng(seed + 1).integers(
            0, n_hks, size=n_rounds * batch)

        def one_pass(digest):
            t0 = time.perf_counter()
            for r in range(n_rounds):
                groups = {}
                for j in order[r * batch:(r + 1) * batch]:
                    hk = hks[int(j)]
                    ph = key_hash_parts(hk, b"")
                    groups.setdefault(ph % 4, []).append(
                        ("get", generate_key(hk, b"s00"), ph))
                res = client.point_read_multi(groups)
                for pidx in sorted(res):
                    for st, val in res[pidx]:
                        digest.update(b"%d" % st)
                        digest.update(val)
            t_read = time.perf_counter() - t0
            t0 = time.perf_counter()
            for r in range(n_rounds):
                for j in order[r * batch:(r + 1) * batch:4]:
                    hk = hks[int(j)]
                    err, kvs = client.multi_get(hk)
                    digest.update(b"%d%d" % (err, len(kvs)))
                    for sk in sorted(kvs):
                        digest.update(sk)
                        digest.update(kvs[sk])
            t_scan = time.perf_counter() - t0
            return t_read, t_scan

        FLAGS.set("pegasus.perfctx", "enabled", False)
        one_pass(hashlib.sha256())  # unmeasured warm-up
        modes = [("perfctx_off", False), ("perfctx_on", True)]
        ops_read = n_rounds * batch
        ops_scan = n_rounds * (batch // 4)
        out = {"hashkeys": n_hks, "sortkeys_per_hk": n_sks,
               "ops_per_mode": (ops_read + ops_scan) * reps}
        times = {name: ([], []) for name, _e in modes}
        hashes = {name: hashlib.sha256() for name, _e in modes}
        # modes interleave across reps so slow drift hits both equally
        for _rep in range(reps):
            for name, enabled in modes:
                FLAGS.set("pegasus.perfctx", "enabled", enabled)
                tr, ts = one_pass(hashes[name])
                times[name][0].append(tr)
                times[name][1].append(ts)
        digests = {}
        for name, _e in modes:
            reads, scans = times[name]
            digests[name] = hashes[name].hexdigest()
            out[name] = {
                "read_qps": round(ops_read * reps / sum(reads), 1),
                "scan_qps": round(ops_scan * reps / sum(scans), 1),
                "read_s_median": round(sorted(reads)[1], 4),
                "scan_s_median": round(sorted(scans)[1], 4),
            }
        base, on = out["perfctx_off"], out["perfctx_on"]
        out["read_overhead"] = round(
            on["read_s_median"] / base["read_s_median"] - 1.0, 4)
        out["scan_overhead"] = round(
            on["scan_s_median"] / base["scan_s_median"] - 1.0, 4)
        out["identity_ok"] = len(set(digests.values())) == 1
        out["gate_ok"] = bool(
            out["identity_ok"]
            and out["read_overhead"] <= 0.02
            and out["scan_overhead"] <= 0.02)
        return out
    finally:
        FLAGS.set("pegasus.perfctx", "enabled", True)
        cluster.close()
        shutil.rmtree(cdir, ignore_errors=True)


def measure_qos_isolation(tmpdir, seed: int):
    """Multi-tenant QoS phase (round 20), two same-run A/Bs.

    Admission overhead: ONE tenant runs the batched point-get and
    ranged multi_get streams over compacted read-only state with
    budget enforcement hard-OFF vs ON. The tenant's configured budget
    sits far above the workload, so the ON mode pays the real
    per-request resolve + bucket checks without ever gating —
    identity-gated, modes interleaved, median of 3 reps; the gate: ON
    within 2% of OFF on both legs (the perfctx convention; reads and
    scans are the shed-eligible admission classes — writes are
    shed-exempt and their funnel is exercised by the isolation arm
    below). Tenant classification and CU charging run in BOTH modes
    (unconditional data-plane accounting); the A/B isolates what the
    enforce flag adds.

    Isolation: a compliant tenant's batched point-get rounds, timed
    per round, with an abusive tenant absent vs flooding oversized
    writes into a tiny CU budget before every round. Per-tenant
    budgets (not client courtesy) are the mechanism: the gates are
    that the compliant tenant's result digest is IDENTICAL in both
    modes, the abuser actually went over budget, and the compliant
    per-round p99 stays within the gated bound (<=1.5x its solo p99).
    """
    import hashlib
    import shutil

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import expire_ts_from_ttl
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.server.tenancy import TENANTS
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.flags import FLAGS

    n_keys = int(os.environ.get("PEGBENCH_QOS_KEYS", 512))
    n_rounds = int(os.environ.get("PEGBENCH_QOS_ROUNDS", 240))
    iso_rounds = int(os.environ.get("PEGBENCH_QOS_ISO_ROUNDS", 160))
    reps = 3
    batch = 32
    out = {}

    def pct(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * q))]

    # ---- A/B 1: single-tenant admission-path overhead ---------------
    cdir = os.path.join(tmpdir, "qos_admission")
    cluster = SimCluster(cdir, n_nodes=3, seed=seed)
    try:
        cluster.create_table(
            "qa", partition_count=4, replica_count=3,
            envs={"qos.tenants": "bench:8:100000000",
                  "qos.default_tenant": "bench"})
        client = cluster.client("qa")  # adopts qos.default_tenant
        n_sks = 4  # sort keys per hashkey: the ranged leg reads pages
        hks = [b"qak%05d" % i for i in range(n_keys)]
        for start in range(0, n_keys, batch):
            groups = {}
            for hk in hks[start:start + batch]:
                ph = key_hash_parts(hk, b"")
                for j in range(n_sks):
                    groups.setdefault(ph % 4, []).append(
                        (OP_PUT, (generate_key(hk, b"s%02d" % j),
                                  b"v" * 64, expire_ts_from_ttl(0)),
                         ph))
            client.write_multi(groups)
        # compact so every measured pass reads the SAME frozen state —
        # a mutating leg would make the A/B measure store drift, not
        # admission cost
        for stub in cluster.stubs.values():
            for r in stub.replicas.values():
                r.server.engine.flush()
                r.server.engine.manual_compact()

        order = np.random.default_rng(seed + 1).integers(
            0, n_keys, size=n_rounds * batch)

        def one_pass(digest):
            t0 = time.perf_counter()
            for r in range(n_rounds):
                groups = {}
                for j in order[r * batch:(r + 1) * batch]:
                    hk = hks[int(j)]
                    ph = key_hash_parts(hk, b"")
                    groups.setdefault(ph % 4, []).append(
                        ("get", generate_key(hk, b"s00"), ph))
                res = client.point_read_multi(groups)
                for pidx in sorted(res):
                    for st, val in res[pidx]:
                        digest.update(b"%d" % st)
                        digest.update(val)
            t_read = time.perf_counter() - t0
            t0 = time.perf_counter()
            for r in range(n_rounds):
                for j in order[r * batch:(r + 1) * batch:4]:
                    hk = hks[int(j)]
                    err, kvs = client.multi_get(hk)
                    digest.update(b"%d%d" % (err, len(kvs)))
                    for sk in sorted(kvs):
                        digest.update(sk)
                        digest.update(kvs[sk])
            t_scan = time.perf_counter() - t0
            return t_read, t_scan

        FLAGS.set("pegasus.qos", "tenant_enforce", False)
        one_pass(hashlib.sha256())  # unmeasured warm-up
        modes = [("enforce_off", False), ("enforce_on", True)]
        # min-of-reps needs several shots per mode to land on a quiet
        # slice of a loaded box (observed pass spread up to ±40% wall)
        admit_reps = int(os.environ.get("PEGBENCH_QOS_REPS", 7))
        ops_n = n_rounds * batch
        out["hashkeys"] = n_keys
        out["admission_ops_per_mode"] = (ops_n + ops_n // 4) * admit_reps
        times = {name: ([], []) for name, _e in modes}
        hashes = {name: hashlib.sha256() for name, _e in modes}
        # modes interleave across reps AND alternate order per rep:
        # whatever warms within a rep (page cache, allocator) benefits
        # the second slot, so a fixed order would bias one mode
        for rep in range(admit_reps):
            for name, enabled in (modes if rep % 2 == 0
                                  else modes[::-1]):
                FLAGS.set("pegasus.qos", "tenant_enforce", enabled)
                tr, ts = one_pass(hashes[name])
                times[name][0].append(tr)
                times[name][1].append(ts)
        digests = {}
        for name, _e in modes:
            reads, scans = times[name]
            digests[name] = hashes[name].hexdigest()
            out[name] = {
                "read_s_median": round(sorted(reads)[admit_reps // 2],
                                       4),
                "scan_s_median": round(sorted(scans)[admit_reps // 2],
                                       4),
                "read_s_min": round(min(reads), 4),
                "scan_s_min": round(min(scans), 4),
            }
        # the overhead estimator is the per-mode MIN over reps (timeit
        # discipline): the pass replays deterministically, so host
        # scheduler/GC noise is strictly additive and the fastest pass
        # sits closest to the true path cost — per-pass wall noise on
        # a loaded box (±5-10%) would drown a 2% gate computed from
        # medians; the medians ride along for the record
        base, on = out["enforce_off"], out["enforce_on"]
        out["admission_read_overhead"] = round(
            on["read_s_min"] / base["read_s_min"] - 1.0, 4)
        out["admission_scan_overhead"] = round(
            on["scan_s_min"] / base["scan_s_min"] - 1.0, 4)
        out["admission_identity_ok"] = len(set(digests.values())) == 1
    finally:
        FLAGS.set("pegasus.qos", "tenant_enforce", True)
        cluster.close()
        shutil.rmtree(cdir, ignore_errors=True)
        TENANTS.reset()  # process singleton: drop the sim-pinned clock

    # ---- A/B 2: abuser on/off isolation -----------------------------
    cdir = os.path.join(tmpdir, "qos_isolation")
    cluster = SimCluster(cdir, n_nodes=3, seed=seed + 9)
    try:
        # weight 8:1 and a ~200 CU/s abuser budget vs 16KB (5 CU)
        # writes: the abuser outruns its refill every round and lives
        # in jittered-backoff retry, the compliant tenant never gates
        cluster.create_table(
            "qi", partition_count=4, replica_count=3,
            envs={"qos.tenants": "abuser:1:200,compliant:8:100000000",
                  "qos.default_tenant": "compliant"})
        compliant = cluster.client("qi", name="bench-qi-compliant",
                                   tenant="compliant")
        abuser = cluster.client("qi", name="bench-qi-abuser",
                                tenant="abuser")
        keys = [(b"qik%05d" % i, b"s") for i in range(n_keys)]
        for start in range(0, n_keys, batch):
            groups = {}
            for hk, sk in keys[start:start + batch]:
                ph = key_hash_parts(hk, sk)
                groups.setdefault(ph % 4, []).append(
                    (OP_PUT, (generate_key(hk, sk), b"v" * 64,
                              expire_ts_from_ttl(0)), ph))
            compliant.write_multi(groups)

        order = np.random.default_rng(seed + 2).integers(
            0, n_keys, size=iso_rounds * batch)
        big = b"A" * 16384  # ~5 CU per write against the 200 CU/s budget

        def iso_pass(with_abuser, digest, round_times):
            # untimed priming round: the inter-pass run_until_idle
            # leaves due periodic work (health ticks, lease renewals)
            # for the next request to pump, and with a few hundred
            # samples the p99 is the top handful of rounds — one
            # scheduling artifact must not own it
            groups = {}
            for j in order[:batch]:
                hk, sk = keys[int(j)]
                ph = key_hash_parts(hk, sk)
                groups.setdefault(ph % 4, []).append(
                    ("get", generate_key(hk, sk), ph))
            compliant.point_read_multi(groups)
            for r in range(iso_rounds):
                if with_abuser:
                    for i in range(3):
                        # a FIXED 97-key abuser working set, disjoint
                        # from the compliant keys and overwritten with
                        # a constant value: the compliant digest stays
                        # mode-independent and the store reaches an
                        # overwrite fixed point instead of growing
                        abuser.set(b"abk%04d" % ((r * 3 + i) % 97),
                                   b"s", big)
                groups = {}
                for j in order[r * batch:(r + 1) * batch]:
                    hk, sk = keys[int(j)]
                    ph = key_hash_parts(hk, sk)
                    groups.setdefault(ph % 4, []).append(
                        ("get", generate_key(hk, sk), ph))
                t0 = time.perf_counter()
                res = compliant.point_read_multi(groups)
                round_times.append(time.perf_counter() - t0)
                for pidx in sorted(res):
                    for st, val in res[pidx]:
                        digest.update(b"%d" % st)
                        digest.update(val)

        # warm up WITH the abuser (populates its working set, settles
        # flush debt), then compact to the steady state every measured
        # pass starts from — without this, monotonic store growth makes
        # later modes slower and the solo/abuse ratio measures drift
        iso_pass(True, hashlib.sha256(), [])
        for stub in cluster.stubs.values():
            for r in stub.replicas.values():
                r.server.engine.flush()
                r.server.engine.manual_compact()
        cluster.loop.run_until_idle()
        # (mode, enforce, abuser present): the unprotected arm shows
        # what the same abuse does with budget enforcement off
        iso_modes = [("abuser_off", True, False),
                     ("abuser_on", True, True),
                     ("abuser_unprotected", False, True)]
        iso_times = {name: [] for name, _e, _w in iso_modes}
        iso_hashes = {name: hashlib.sha256() for name, _e, _w in
                      iso_modes}
        for _rep in range(reps):
            for name, enforce, with_abuser in iso_modes:
                # the unprotected arm charges CU without gating, so it
                # leaves a bucket deficit no continuously-enforced
                # system ever accrues (post-debit deficit is bounded
                # by ONE op there) — restart the abuser's bucket so
                # every arm starts from the same burst allowance
                TENANTS.ensure("abuser", 1.0, 0.0)
                TENANTS.ensure("abuser", 1.0, 200.0)
                FLAGS.set("pegasus.qos", "tenant_enforce", enforce)
                iso_pass(with_abuser, iso_hashes[name],
                         iso_times[name])
                # drain in-flight replication so one mode's leftovers
                # never land inside the next mode's timed rounds
                cluster.loop.run_until_idle()
        FLAGS.set("pegasus.qos", "tenant_enforce", True)
        snap = TENANTS.snapshot()
        for name, _e, _w in iso_modes:
            ts = iso_times[name]
            out[name] = {
                "compliant_p99_ms": round(pct(ts, 0.99) * 1000, 3),
                "compliant_median_ms": round(pct(ts, 0.5) * 1000, 3),
                "rounds": len(ts),
            }
        out["abuser_on"].update({
            "abuser_overbudget": snap.get("abuser", {}).get(
                "overbudget", 0),
            "abuser_shed": snap.get("abuser", {}).get("shed", 0),
            "abuser_cu_total": snap.get("abuser", {}).get("cu_total", 0),
            "compliant_overbudget": snap.get("compliant", {}).get(
                "overbudget", 0),
        })
        out["compliant_p99_ratio"] = round(
            out["abuser_on"]["compliant_p99_ms"]
            / out["abuser_off"]["compliant_p99_ms"], 3)
        # enforcement's value under identical abuse (reported, not
        # gated: a sequential sim understates unprotected queueing)
        out["unprotected_median_ratio"] = round(
            out["abuser_unprotected"]["compliant_median_ms"]
            / out["abuser_on"]["compliant_median_ms"], 3)
        out["identity_ok"] = len(
            {h.hexdigest() for h in iso_hashes.values()}) == 1
        out["gate_ok"] = bool(
            out["admission_identity_ok"]
            and out["admission_read_overhead"] <= 0.02
            and out["admission_scan_overhead"] <= 0.02
            and out["identity_ok"]
            and out["compliant_p99_ratio"] <= 1.5
            and out["abuser_on"]["abuser_overbudget"] > 0
            and out["abuser_on"]["compliant_overbudget"] == 0)
        return out
    finally:
        cluster.close()
        shutil.rmtree(cdir, ignore_errors=True)
        TENANTS.reset()


def measure_follower_read(tmpdir, seed: int):
    """Follower-read capacity phase (round 17): the SAME batched
    point-get stream through a 3-replica SimCluster at linearizable
    (primary-only) vs bounded_stale (round-robin across all three
    replicas under the read lease) — same-run, identity-gated on the
    returned bytes, modes interleaved across 3 reps.

    The table is ONE partition on purpose: a hot partition is the unit
    whose serving capacity the follower fan-out multiplies (per-table
    aggregates just sum partitions). The sim runs every replica on one
    host thread, so wall q/s cannot show the fan-out — the aggregate
    is modeled the way capacity planning does it: the busiest replica
    is the bottleneck, so
        aggregate_read_qps = wall_qps * total_ops / max_per_replica_ops
    (primary-only: one replica serves 100% -> factor 1; follower
    reads: three replicas serve ~1/3 each -> factor ~3). The gate:
    >= 2x aggregate q/s with byte-identical results and ZERO stale
    bounces (every serve was a real lease-checked, watermark-checked
    follower answer, not a bounce-and-retry at the primary)."""
    import hashlib
    import shutil
    from collections import Counter as _Counter

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import expire_ts_from_ttl
    from pegasus_tpu.client.cluster_client import bounded_stale
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.tools.cluster import SimCluster

    n_hks = int(os.environ.get("PEGBENCH_FOLLOWER_KEYS", 256))
    n_rounds = int(os.environ.get("PEGBENCH_FOLLOWER_ROUNDS", 120))
    reps = 3
    batch = 32
    cdir = os.path.join(tmpdir, "follower_read")
    cluster = SimCluster(cdir, n_nodes=3, seed=seed)
    try:
        cluster.create_table("fr", partition_count=1, replica_count=3)
        client = cluster.client("fr")
        hks = [b"fhk%05d" % i for i in range(n_hks)]
        for start in range(0, n_hks, batch):
            groups = {0: []}
            for hk in hks[start:start + batch]:
                ph = key_hash_parts(hk, b"")
                groups[0].append(
                    (OP_PUT, (generate_key(hk, b"s"), b"v" * 64,
                              expire_ts_from_ttl(0)), ph))
            client.write_multi(groups)
        for stub in cluster.stubs.values():
            for r in stub.replicas.values():
                r.server.engine.flush()
                r.server.engine.manual_compact()
        # settle: secondaries commit everything and stamp freshness
        cluster.step(rounds=2)

        # per-replica serve tally, read off the wire the client sends
        served = _Counter()
        orig_send = client._send_request

        def counted_send(dst, method, payload, **kw):
            if method == "client_read_batch":
                served[dst] += sum(len(ops)
                                   for _gpid, ops in payload["groups"])
            return orig_send(dst, method, payload, **kw)

        client._send_request = counted_send

        order = np.random.default_rng(seed + 3).integers(
            0, n_hks, size=n_rounds * batch)
        cons = bounded_stale(
            float(os.environ.get("PEGBENCH_FOLLOWER_LAG_MS", 60_000)))

        def one_pass(digest, consistency):
            t0 = time.perf_counter()
            for r in range(n_rounds):
                groups = {0: []}
                for j in order[r * batch:(r + 1) * batch]:
                    hk = hks[int(j)]
                    groups[0].append(
                        ("get", generate_key(hk, b"s"),
                         key_hash_parts(hk, b"")))
                res = client.point_read_multi(groups,
                                              consistency=consistency)
                for st, val in res[0]:
                    digest.update(b"%d" % st)
                    digest.update(val)
            return time.perf_counter() - t0

        one_pass(hashlib.sha256(), None)  # unmeasured warm-up
        served.clear()
        modes = [("linearizable", None), ("follower", cons)]
        ops_pass = n_rounds * batch
        out = {"hashkeys": n_hks, "ops_per_mode": ops_pass * reps,
               "replica_count": 3}
        times = {name: [] for name, _c in modes}
        hashes = {name: hashlib.sha256() for name, _c in modes}
        tallies = {name: _Counter() for name, _c in modes}
        # modes interleave across reps so slow drift hits both equally
        for _rep in range(reps):
            for name, consistency in modes:
                served.clear()
                times[name].append(one_pass(hashes[name], consistency))
                tallies[name] += served
        bounces = sum(stub._stale_bounces.value()
                      for stub in cluster.stubs.values())
        digests = {}
        for name, _c in modes:
            tally = tallies[name]
            total = sum(tally.values())
            # the busiest replica bounds the group's capacity
            fanout = total / max(tally.values())
            wall_qps = ops_pass * reps / sum(times[name])
            digests[name] = hashes[name].hexdigest()
            out[name] = {
                "wall_qps": round(wall_qps, 1),
                "serving_replicas": len(tally),
                "max_replica_share": round(max(tally.values()) / total,
                                           4),
                "fanout": round(fanout, 3),
                "aggregate_read_qps": round(wall_qps * fanout, 1),
                "pass_s_median": round(sorted(times[name])[1], 4),
            }
        base = out["linearizable"]["aggregate_read_qps"]
        # top-level twin of the follower-mode aggregate: the round's
        # headline metric (bench_report scans a phase's top level)
        out["aggregate_read_qps"] = out["follower"]["aggregate_read_qps"]
        out["speedup"] = round(
            out["follower"]["aggregate_read_qps"] / base, 3)
        out["stale_bounces"] = bounces
        out["identity_ok"] = len(set(digests.values())) == 1
        out["gate_ok"] = bool(out["identity_ok"] and bounces == 0
                              and out["speedup"] >= 2.0)
        return out
    finally:
        cluster.close()
        shutil.rmtree(cdir, ignore_errors=True)


def measure_dup_catchup(tmpdir, seed: int):
    """Geo-replication catch-up phase (round 14): batched+compressed
    dup_apply_batch envelope shipping vs the legacy solo-mutation
    client_write shipping, catching a follower cluster up over a
    DELAYED inter-cluster link — same-run, identity-gated on the
    follower table digest. Each mode runs a FRESH two-SimCluster
    topology from the same seed (identical schedules); with every
    inter-cluster hop paying the link delay, catch-up sim-time is
    round-trip-dominated, i.e. it measures shipping efficiency, not
    host speed. A third pass re-runs batched mode under synthetic
    follower pressure (every envelope delivery grows the follower's
    shed counter): the governor's AIMD backoff must ENGAGE
    (backoff_count grows, throttle floors) while catch-up still
    completes — the forward-progress floor."""
    import hashlib
    import shutil

    from pegasus_tpu.runtime.sim import SimLoop, SimNetwork
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.flags import FLAGS
    from pegasus_tpu.utils.metrics import METRICS

    n_records = int(os.environ.get("PEGBENCH_DUP_RECORDS", 400))
    delay_s = 0.03
    flag_keys = ["ship_batch_mutations", "ship_batch_bytes",
                 "ship_governor"]
    import pegasus_tpu.replica.dup_governor  # noqa: F401 - flags
    import pegasus_tpu.replica.duplication_cluster  # noqa: F401

    saved = {k: FLAGS.get("pegasus.dup", k) for k in flag_keys}

    def dup_counters():
        shipped = raw = backoff = 0
        for ent in METRICS.snapshot("duplication"):
            m = ent.get("metrics", {})
            shipped += m.get("dup_shipped_bytes", {}).get("value", 0)
            raw += m.get("dup_shipped_raw_bytes", {}).get("value", 0)
            backoff += m.get("dup_backoff_count", {}).get("value", 0)
        return shipped, raw, backoff

    def one_mode(name, batch, pressure):
        mode_dir = os.path.join(tmpdir, f"dupcatch_{name}")
        loop = SimLoop(seed=seed)
        net = SimNetwork(loop)
        a = SimCluster(os.path.join(mode_dir, "A"), n_nodes=2,
                       name_prefix="a-", loop=loop, net=net,
                       cluster_id=1)
        b = SimCluster(os.path.join(mode_dir, "B"), n_nodes=2,
                       name_prefix="b-", loop=loop, net=net,
                       cluster_id=2)
        try:
            FLAGS.set("pegasus.dup", "ship_batch_mutations", batch)

            def step_both(r=1):
                for _ in range(r):
                    a.step()
                    b.step(advance=False)

            def pump(sim_seconds):
                """Advance shared sim time in 1s slices with timers
                interleaved: a LONG shipping chain spans many sim
                seconds of link delay, and beacons must keep flowing
                through it or the follower's FD lease lapses mid-
                catch-up (a step-quantized-beacon artifact — real
                nodes beacon on wall-clock timers)."""
                for _ in range(int(sim_seconds)):
                    for cl in (a, b):
                        for stub in cl.stubs.values():
                            stub.send_beacon()
                            stub.config_sync()
                            stub.dup_tick()
                    loop.run_for(1.0)
                    for cl in (a, b):
                        for m in cl.metas:
                            m.tick()

            step_both(2)
            a.create_table("t", partition_count=2, replica_count=2)
            b.create_table("t", partition_count=2, replica_count=2)
            ca = a.client("t")
            for i in range(n_records):
                assert ca.set(b"ck%06d" % i, b"s",
                              b"geo-payload-%06d|" % i * 4) == 0
            for s in list(a.stubs) + [m.name for m in a.metas]:
                for d in list(b.stubs) + [m.name for m in b.metas]:
                    net.set_delay(delay_s, src=s, dst=d)
                    net.set_delay(delay_s, src=d, dst=s)
            if pressure:
                # synthetic follower pressure: every envelope delivery
                # grows the shed counter the ack carries back
                shed = METRICS.entity("rpc", "dispatch", {}).counter(
                    "read_shed_count")
                for bn in list(b.stubs):
                    orig = net._handlers[bn]

                    def wrapped(src, mt, pl, orig=orig):
                        if mt == "dup_apply_batch":
                            shed.increment(5)
                        orig(src, mt, pl)

                    net._handlers[bn] = wrapped
            s0, r0, b0 = dup_counters()
            t0_sim, t0 = loop.now, time.perf_counter()
            a.meta.duplication.add_duplication("t", "b-meta", "t")
            drained = False
            for _ in range(600):
                pump(1)
                sessions = [sess for stub in a.stubs.values()
                            for sess in stub._dup_sessions.values()]
                if sessions and all(
                        sess.confirmed_decree > 0
                        and sess._inflight_decree is None
                        and sess.stats()["lag_decrees"] == 0
                        for sess in sessions):
                    drained = True
                    break
            sim_s = loop.now - t0_sim
            wall_s = time.perf_counter() - t0
            s1, r1, b1 = dup_counters()
            cb = b.client("t")
            digest = hashlib.sha256()
            for i in range(n_records):
                st, val = cb.get(b"ck%06d" % i, b"s")
                digest.update(b"%d" % st)
                digest.update(val or b"")
            return {
                "drained": drained,
                "catchup_sim_s": round(sim_s, 2),
                "catchup_wall_s": round(wall_s, 2),
                "shipped_wire_bytes": s1 - s0,
                "shipped_raw_bytes": r1 - r0,
                "compression_ratio": round((s1 - s0) / (r1 - r0), 4)
                if r1 > r0 else None,
                "governor_backoffs": b1 - b0,
                "digest": digest.hexdigest(),
            }
        finally:
            a.close()
            b.close()
            shutil.rmtree(mode_dir, ignore_errors=True)

    try:
        out = {"records": n_records, "link_delay_s": delay_s}
        out["solo"] = one_mode("solo", 1, False)
        out["batched"] = one_mode("batched", 32, False)
        out["governed"] = one_mode("governed", 32, True)
        out["speedup_sim"] = round(
            out["solo"]["catchup_sim_s"]
            / out["batched"]["catchup_sim_s"], 2) \
            if out["batched"]["catchup_sim_s"] else None
        out["identity_ok"] = (
            out["solo"]["digest"] == out["batched"]["digest"]
            == out["governed"]["digest"])
        # the gate: batched+compressed beats solo on the delayed link,
        # byte-identical content, and the governor both ENGAGES under
        # follower pressure and never stalls catch-up (forward floor)
        out["gate_ok"] = bool(
            out["identity_ok"]
            and out["solo"]["drained"] and out["batched"]["drained"]
            and out["governed"]["drained"]
            and (out["speedup_sim"] or 0) > 1.0
            and out["governed"]["governor_backoffs"] > 0)
        return out
    finally:
        for k, v in saved.items():
            FLAGS.set("pegasus.dup", k, v)


def measure_mixed_load(jax, device, tmpdir, seed: int,
                       n_parts: int = 4, fg_seconds: float = 20.0):
    """Mixed-load phase (round-12): foreground point reads against one
    store while background compactions churn `n_parts` sibling stores,
    with the governor's pressure feedback OFF then ON. The foreground
    loop stamps the SAME deadline-violation counter the rpc dispatcher
    stamps (a get exceeding the deadline budget ticks it), so the
    feedback signal is the real one: foreground latency violations
    drive the AIMD backoff. Reported per mode: foreground p50/p99,
    deadline violations, background bytes compacted (forward-progress
    proof), and the governor's backoff count."""
    import shutil
    import threading as _threading

    import numpy as np

    from pegasus_tpu.storage.compact_governor import GOVERNOR
    from pegasus_tpu.utils.flags import FLAGS
    from pegasus_tpu.utils.metrics import METRICS

    from pegasus_tpu.base.key_schema import generate_key

    deadline_ms = float(os.environ.get("PEGBENCH_MIXED_DEADLINE_MS",
                                       "20"))
    # the forward-progress floor must be able to BIND on this fixture
    # (the governor paces on-disk bytes; each bg store is ~25 MB
    # compressed and the CPU-bound natural rate is ~70 MB/s, so the
    # default 32 MB/s floor would never constrain anything): the
    # phase runs with an 8 MB/s floor and records it
    floor_mbps = float(os.environ.get("PEGBENCH_MIXED_FLOOR_MBPS",
                                      "8"))
    old_floor = FLAGS.get("pegasus.storage", "compact_min_mbps")
    FLAGS.set("pegasus.storage", "compact_min_mbps", floor_mbps)
    per_part = int(0.12e9 / 145)
    viol_counter = METRICS.entity("rpc", "dispatch", {}).counter(
        "deadline_expired_count")
    out = {"deadline_ms": deadline_ms, "floor_mbps": floor_mbps}
    for mode in ("sched_off", "sched_on"):
        data_dir = os.path.join(tmpdir, f"mixed-{mode}")
        if os.path.exists(data_dir):
            shutil.rmtree(data_dir)
        engines = build_compact_store(
            data_dir, per_part * (n_parts + 1), 0.4, n_parts + 1,
            seed, value_kind="templated")
        fg_eng, bg_engines = engines[0], engines[1:]
        os.sync()
        bg_bytes = _store_bytes(bg_engines)
        # reset governor adaptation state between modes
        GOVERNOR._pressure_last = None
        GOVERNOR._throttle_mbps = 0.0
        GOVERNOR._engaged_at_mbps = 0.0
        backoff0 = GOVERNOR._c_backoff.value()
        viol0 = viol_counter.value()
        stop = _threading.Event()
        compacted = []

        def bg_run():
            # cycle the background compactions for the WHOLE foreground
            # window (after the first cycle the stores are pure L1 with
            # nothing to drop, so later cycles are verbatim-copy
            # rewrites — still the full read+write disk churn): the
            # foreground p99 must face sustained background IO, not a
            # 2-second burst diluted over the window
            with jax.default_device(device):
                while not stop.is_set():
                    for eng in bg_engines:
                        if stop.is_set():
                            return
                        eng.manual_compact()
                        compacted.append(eng)

        lat = []
        rng = np.random.default_rng(seed + 5)
        t_bg = _threading.Thread(target=bg_run, daemon=True)
        t_bg.start()
        t_end = time.perf_counter() + fg_seconds
        while time.perf_counter() < t_end:
            hk = b"user%08d" % int(rng.integers(0, per_part // 10))
            sk = b"s%02d" % int(rng.integers(0, 10))
            k = generate_key(hk, sk)
            t0 = time.perf_counter()
            fg_eng.get(k)
            dt = (time.perf_counter() - t0) * 1000.0
            lat.append(dt)
            if mode == "sched_on" and dt > deadline_ms:
                # the dispatcher's signal, stamped by the foreground:
                # a read blowing its deadline budget is exactly what
                # the shed/deadline machinery counts
                viol_counter.increment()
        fg_done = time.perf_counter()
        stop.set()
        t_bg.join(timeout=120)
        bg_secs = time.perf_counter() - fg_done
        lat.sort()
        n = len(lat)
        out[mode] = {
            "fg_gets": n,
            "fg_p50_ms": round(lat[n // 2], 3) if n else None,
            "fg_p99_ms": round(lat[int(n * 0.99)], 3) if n else None,
            "fg_deadline_violations": viol_counter.value() - viol0,
            "bg_parts_compacted": len(compacted),
            "bg_bytes": bg_bytes,
            "bg_extra_seconds_after_fg": round(bg_secs, 2),
            "governor_backoffs": GOVERNOR._c_backoff.value() - backoff0,
            "throttle_mbps_final": GOVERNOR.status()["throttle_mbps"],
        }
        for eng in engines:
            eng.close()
        shutil.rmtree(data_dir, ignore_errors=True)
        _log(f"mixed[{mode}]: p99 {out[mode]['fg_p99_ms']}ms over "
             f"{n} gets, {len(compacted)}/{n_parts} bg compactions, "
             f"{out[mode]['governor_backoffs']} backoffs")
    GOVERNOR._pressure_last = None
    GOVERNOR._throttle_mbps = 0.0
    GOVERNOR._engaged_at_mbps = 0.0
    FLAGS.set("pegasus.storage", "compact_min_mbps", old_floor)
    if out["sched_off"]["fg_p99_ms"] and out["sched_on"]["fg_p99_ms"]:
        out["p99_ratio_on_vs_off"] = round(
            out["sched_on"]["fg_p99_ms"]
            / out["sched_off"]["fg_p99_ms"], 3)
    out["forward_progress_ok"] = \
        out["sched_on"]["bg_parts_compacted"] > 0
    return out


def _scan_identity_digest(bc, n_partitions, n_hashkeys, seed, n=96):
    """sha256 over a deterministic scan sample's key/value bytes."""
    import hashlib

    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.types import GetScannerRequest

    rng = np.random.default_rng(seed)
    h = hashlib.sha256()
    for _ in range(n):
        pidx = int(rng.integers(0, n_partitions))
        start = b"user%08d" % int(rng.integers(0, n_hashkeys))
        res = bc.client.scan_multi({pidx: [GetScannerRequest(
            start_key=generate_key(start, b""), batch_size=40,
            validate_partition_hash=True, one_page=True)]})
        for resp in res[pidx]:
            for kv in resp.kvs:
                h.update(kv.key)
                h.update(b"\x00")
                h.update(kv.value)
                h.update(b"\x01")
    return h.hexdigest()


def measure_compressed_scan(jax, device, tmpdir, n_records: int,
                            n_partitions: int, n_ops: int, seed: int):
    """scan_compressed phase (round-11): the warm YCSB-E scan measured
    over a compressed store vs an uncompressed same-run twin. Direct
    compute means the steady state decodes nothing (masks from the
    encoded probe, blocks resident in the byte-capped cache), so the
    compressed number must sit within noise of the raw one — that IS
    the acceptance gate, alongside a byte-identity scan sample."""
    from pegasus_tpu.utils.flags import FLAGS

    n_hashkeys = max(1, n_records // 10)
    out = {}
    old_codec = FLAGS.get("pegasus.storage", "block_codec")
    try:
        for codec in ("none", "dcz"):
            FLAGS.set("pegasus.storage", "block_codec", codec)
            bdir = os.path.join(tmpdir, f"cscan-{codec}")
            bc = build_cluster(bdir, n_records, n_partitions, seed)
            try:
                ops, recs, secs = _measure_scan_phase(
                    jax, device, bc, n_ops, n_partitions, n_hashkeys,
                    seed)
                digest = _scan_identity_digest(bc, n_partitions,
                                               n_hashkeys, seed + 7)
                out[codec] = {
                    "ops_per_s": round(ops / secs, 1),
                    "records_per_s": round(recs / secs, 1),
                    "seconds": round(secs, 3),
                    "disk_bytes": data_bytes(bc),
                    "sample_digest": digest,
                }
                _log(f"scan_compressed[{codec}]: "
                     f"{out[codec]['ops_per_s']:.0f} ops/s, "
                     f"{out[codec]['records_per_s']:.0f} records/s")
            finally:
                bc.close()
    finally:
        FLAGS.set("pegasus.storage", "block_codec", old_codec)
    out["identity_ok"] = (out["none"]["sample_digest"]
                          == out["dcz"]["sample_digest"])
    out["ops_ratio_dcz_vs_none"] = round(
        out["dcz"]["ops_per_s"] / max(out["none"]["ops_per_s"], 1e-9),
        4)
    out["disk_ratio"] = round(
        out["dcz"]["disk_bytes"] / max(out["none"]["disk_bytes"], 1),
        4)
    return out


def _pushdown_drain(bc, pidx, req):
    """Drive one partition's scan to exhaustion through the cluster
    read path; returns (rows, shipped_wire_bytes, final agg partial)."""
    from pegasus_tpu.server.types import SCAN_CONTEXT_ID_COMPLETED

    rows, shipped = [], 0
    resp = bc.client.scan_multi({pidx: [req]})[pidx][0]
    while True:
        assert resp.error == 0, f"scan error {resp.error}"
        shipped += resp.wire_bytes()
        rows.extend((kv.key, kv.value) for kv in resp.kvs)
        if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
            return rows, shipped, resp.agg
        resp = bc.client.scan_page(pidx, resp.context_id)


def measure_scan_pushdown(jax, device, tmpdir, n_records: int,
                          n_partitions: int, seed: int):
    """scan_pushdown phase: the SAME full-table value-filtered count,
    measured twice — client-side (plain scans ship every row, the
    client filters and counts) vs pushdown (the server's vectorized
    value-filter kernel prunes pages; aggregate mode ships one tiny
    partial per partition). Swept at ~0.9 / ~0.1 / ~0.01 selectivity;
    the row sets must be byte-identical (that IS the gate) and the
    aggregate wire cost must stay O(partitions), asserted off the
    responses' shipped-bytes accounting."""
    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.ops.predicates import FT_MATCH_ANYWHERE, host_match_filter
    from pegasus_tpu.ops.pushdown import PushdownSpec
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.server.types import GetScannerRequest

    rng = np.random.default_rng(seed)
    bdir = os.path.join(tmpdir, "pushdown")
    bc = BenchCluster(bdir, n_partitions)
    try:
        # token-embedded values: each marker lands independently at its
        # selectivity, so one load serves all three sweep points
        per_pidx = {p: [] for p in range(n_partitions)}
        n_hashkeys = max(1, n_records // 10)
        i = 0
        for h in range(n_hashkeys):
            hk = b"user%08d" % h
            ops = per_pidx[key_hash_parts(hk) % n_partitions]
            for sk_i in range(10):
                if i >= n_records:
                    break
                toks = b"".join(
                    tok for tok, p in ((b" m90", 0.9), (b" m10", 0.1),
                                       (b" m01", 0.01))
                    if rng.random() < p)
                ops.append(WriteOp(OP_PUT, (
                    generate_key(hk, b"s%02d" % sk_i),
                    b"field0=%032d%s" % (i, toks), 0)))
                i += 1
        for pidx, ops in per_pidx.items():
            r = bc.replicas[pidx]
            for off in range(0, len(ops), 1000):
                r.client_write(ops[off:off + 1000])
            bc.cluster.loop.run_until_idle()
        with jax.default_device(device):
            bc.manual_compact_all(device=device)

            out = {"records": i, "partitions": n_partitions}
            plain = GetScannerRequest(batch_size=1000, full_scan=True,
                                      validate_partition_hash=True)
            for name, pat in (("0.9", b"m90"), ("0.1", b"m10"),
                              ("0.01", b"m01")):
                spec = PushdownSpec(value_filter_type=FT_MATCH_ANYWHERE,
                                    value_filter_pattern=pat)
                pushed = GetScannerRequest(
                    batch_size=1000, full_scan=True,
                    validate_partition_hash=True, pushdown=spec)

                def client_arm():
                    rows, shipped = [], 0
                    for pidx in range(n_partitions):
                        r, s, _a = _pushdown_drain(bc, pidx, plain)
                        shipped += s
                        rows.extend(
                            (k, v) for k, v in r
                            if host_match_filter(v, FT_MATCH_ANYWHERE,
                                                 pat))
                    return rows, shipped

                def pushdown_arm():
                    rows, shipped = [], 0
                    for pidx in range(n_partitions):
                        r, s, _a = _pushdown_drain(bc, pidx, pushed)
                        shipped += s
                        rows.extend(r)
                    return rows, shipped

                # warm both arms (block caches, mask caches, compiles),
                # then best-of-3 — same steady-state rule as the other
                # scan phases
                client_arm()
                pushdown_arm()
                c_best = p_best = None
                for _ in range(3):
                    t0 = time.perf_counter()
                    c_rows, c_ship = client_arm()
                    c_s = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    p_rows, p_ship = pushdown_arm()
                    p_s = time.perf_counter() - t0
                    c_best = c_s if c_best is None else min(c_best, c_s)
                    p_best = p_s if p_best is None else min(p_best, p_s)
                identical = sorted(c_rows) == sorted(p_rows)

                # aggregate count: one partial per partition on the wire
                agg_req = GetScannerRequest(
                    batch_size=1000, full_scan=True,
                    validate_partition_hash=True,
                    pushdown=PushdownSpec(
                        value_filter_type=FT_MATCH_ANYWHERE,
                        value_filter_pattern=pat, aggregate="count"))
                agg_shipped, agg_count = 0, 0
                t0 = time.perf_counter()
                for pidx in range(n_partitions):
                    r, s, agg = _pushdown_drain(bc, pidx, agg_req)
                    assert not r, "aggregate reply must carry no rows"
                    agg_shipped += s
                    agg_count += int(agg["count"])
                agg_s = time.perf_counter() - t0
                wire_ok = agg_shipped <= 256 * n_partitions
                assert agg_count == len(c_rows), \
                    f"agg count {agg_count} != {len(c_rows)}"

                out[f"sel_{name}"] = {
                    "matching_rows": len(c_rows),
                    "client_seconds": round(c_best, 4),
                    "pushdown_seconds": round(p_best, 4),
                    "pushdown_speedup": round(c_best / max(p_best, 1e-9),
                                              3),
                    "client_shipped_bytes": c_ship,
                    "pushdown_shipped_bytes": p_ship,
                    "agg_seconds": round(agg_s, 4),
                    "agg_shipped_bytes": agg_shipped,
                    "agg_wire_o_partitions": wire_ok,
                    "identity_ok": identical,
                }
                _log(f"scan_pushdown[sel={name}]: client {c_best:.3f}s "
                     f"vs pushdown {p_best:.3f}s "
                     f"({c_best / max(p_best, 1e-9):.2f}x), agg wire "
                     f"{agg_shipped}B/{n_partitions} parts, "
                     f"identical={identical}")
            out["identity_ok"] = all(
                out[k]["identity_ok"] for k in
                ("sel_0.9", "sel_0.1", "sel_0.01"))
            out["agg_wire_o_partitions"] = all(
                out[k]["agg_wire_o_partitions"] for k in
                ("sel_0.9", "sel_0.1", "sel_0.01"))
            # the ISSUE gate: >=2x at selectivity <= 0.1, identity held
            out["pushdown_speedup"] = out["sel_0.1"]["pushdown_speedup"] \
                if out["identity_ok"] else 0.0
        return out
    finally:
        import shutil

        bc.close()
        shutil.rmtree(bdir, ignore_errors=True)


@contextlib.contextmanager
def _mesh_phase_isolation():
    """The mesh phases set process-wide state (storage flags, a frozen
    engine clock, the MESH_SERVING attachment). They used to run in a
    child process; in this one, put it all back on exit."""
    import pegasus_tpu.storage.engine as engine_mod
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.utils.flags import FLAGS

    saved = [(sec, name, FLAGS.get(sec, name)) for sec, name in (
        ("pegasus.storage", "block_codec"),
        ("pegasus.server", "rocksdb_max_iteration_count"))]
    saved_clock = engine_mod.epoch_now
    try:
        yield
    finally:
        MESH_SERVING.reset()
        engine_mod.epoch_now = saved_clock
        for sec, name, value in saved:
            FLAGS.set(sec, name, value)


def measure_mesh_scan() -> dict:
    """mesh_scan phase: the resident device-mesh SPMD serving arm vs the
    per-chunk kernel wave, same run, byte-identity gated, over a mesh of
    this process's jax.devices().

    Measures the node-level cross-partition wave (scan_multi's shape:
    every partition's uncached blocks in ONE stacked_block_eval call)
    with the mesh DETACHED (per-chunk programs) vs ATTACHED (one
    resident SPMD dispatch answers all partitions), under the REAL
    placement gate — no pinning. Then the whole-range aggregate fold at
    the same selectivity, then the watchdog leg: every dispatch forced
    to overrun its deadline must trip the watchdog and degrade to the
    per-partition kernels with identical rows and zero hung scans. Sets
    process-wide flags: call under _mesh_phase_isolation()."""
    import numpy as np

    from pegasus_tpu.client.client import PegasusClient
    from pegasus_tpu.client.table import Table
    from pegasus_tpu.ops.predicates import FT_NO_FILTER, FT_MATCH_ANYWHERE
    from pegasus_tpu.ops.pushdown import PushdownSpec
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.server.scan_coordinator import stacked_block_eval
    from pegasus_tpu.server.types import (
        GetScannerRequest,
        SCAN_CONTEXT_ID_COMPLETED,
    )
    from pegasus_tpu.utils.flags import FLAGS
    import jax

    n_records = int(os.environ.get("PEGBENCH_MESH_RECORDS", 240_000))
    n_partitions = int(os.environ.get("PEGBENCH_MESH_PARTITIONS", 8))
    seed = int(os.environ.get("PEGBENCH_SEED", 7))
    fkey = (FT_NO_FILTER, b"", FT_NO_FILTER, b"")
    rng = np.random.default_rng(seed)

    tmpdir = tempfile.mkdtemp(prefix="pegbench_mesh")
    # codec none: compressed blocks answer their static probes in the
    # encoded domain host-side and never reach the wave path
    FLAGS.set("pegasus.storage", "block_codec", "none")
    FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", 0)
    table = Table(tmpdir, partition_count=n_partitions)
    client = PegasusClient(table)
    t0 = time.perf_counter()
    for i in range(n_records):
        tok = b" m10" if rng.random() < 0.1 else b""  # selectivity 0.1
        assert client.set(b"user%06d" % (i // 10), b"s%02d" % (i % 10),
                          b"f=%024d%s" % (i, tok)) == 0
    _log(f"loaded {n_records} records in {time.perf_counter() - t0:.1f}s")
    for s in table.partitions.values():
        s.engine.flush()
        s.engine.manual_compact()  # wave serving is over pure sorted runs

    blocks = []
    for p, s in sorted(table.partitions.items()):
        for run in s.engine.lsm.sorted_runs():
            for bm, blk in run.iter_blocks(b"", None):
                ckey = (run.path, bm.offset)
                blocks.append(((p, ckey), s._device_cached_block(ckey, blk),
                               s.pidx, int(blk.count)))
    pv = table.partitions[0].partition_version

    def wave_once():
        masks = {}
        t0 = time.perf_counter()
        for tag, keep in stacked_block_eval(
                [(t, d, p) for t, d, p, _n in blocks], True, pv,
                filter_key=fkey):
            masks[tag] = np.asarray(keep)
        return time.perf_counter() - t0, masks

    def drain_all():
        rows = {}
        for p, s in sorted(table.partitions.items()):
            pd = PushdownSpec(value_filter_type=FT_MATCH_ANYWHERE,
                              value_filter_pattern=b"m10")
            resp = s.on_get_scanner(GetScannerRequest(batch_size=1000,
                                                      pushdown=pd))
            got = []
            while True:
                assert resp.error == 0
                got.extend((kv.key, kv.value) for kv in resp.kvs)
                if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
                    break
                resp = s.on_scan(resp.context_id)
            rows[p] = got
        return rows

    def drain_all_multi():
        """Node-level coordinated drain: every partition's first wave of
        planned misses evaluates in ONE cross-partition scan_multi call
        — the shape whose program count and byte volume clear the real
        mesh placement gate (solo drains wave in LOOKAHEAD windows and
        stay on host kernels honestly)."""
        def fresh_req():
            return GetScannerRequest(
                batch_size=1000,
                pushdown=PushdownSpec(value_filter_type=FT_MATCH_ANYWHERE,
                                      value_filter_pattern=b"m10"))
        first = client.scan_multi({p: [fresh_req()]
                                   for p in sorted(table.partitions)})
        rows = {}
        for p in sorted(table.partitions):
            s = table.partitions[p]
            resp = first[p][0]
            got = []
            while True:
                assert resp.error == 0
                got.extend((kv.key, kv.value) for kv in resp.kvs)
                if resp.context_id == SCAN_CONTEXT_ID_COMPLETED:
                    break
                resp = s.on_scan(resp.context_id)
            rows[p] = got
        return rows

    def agg_all():
        out = {}
        t0 = time.perf_counter()
        for p, s in sorted(table.partitions.items()):
            pd = PushdownSpec(value_filter_type=FT_MATCH_ANYWHERE,
                              value_filter_pattern=b"m10",
                              aggregate="count")
            resp = s.on_get_scanner(GetScannerRequest(batch_size=1000,
                                                      pushdown=pd))
            while resp.context_id != SCAN_CONTEXT_ID_COMPLETED:
                assert resp.error == 0
                resp = s.on_scan(resp.context_id)
            out[p] = resp.agg
        return time.perf_counter() - t0, out

    def clear_masks():
        for s in table.partitions.values():
            with s._mask_lock:
                s._mask_cache.clear()

    # host arm: mesh detached — the chunked host kernel wave
    MESH_SERVING.reset()
    wave_once()  # warm compiles + block device cache
    host_wave = min(wave_once()[0] for _ in range(3))
    host_masks = wave_once()[1]
    host_rows = drain_all()
    agg_all()
    host_agg_s = min(agg_all()[0] for _ in range(3))
    host_agg = agg_all()[1]

    # mesh arm: attach every partition; the REAL placement gate routes
    for s in table.partitions.values():
        MESH_SERVING.attach(s)
    w0 = MESH_SERVING.wave_dispatches
    wave_once()  # warm: builds the resident image + mesh program
    mesh_served = MESH_SERVING.wave_dispatches > w0
    mesh_wave = min(wave_once()[0] for _ in range(3))
    mesh_masks = wave_once()[1]
    clear_masks()
    w1 = MESH_SERVING.wave_dispatches
    mesh_rows = drain_all_multi()
    mesh_drain_served = MESH_SERVING.wave_dispatches > w1
    a0 = MESH_SERVING.agg_dispatches
    agg_all()
    mesh_agg_served = MESH_SERVING.agg_dispatches > a0
    mesh_agg_s = min(agg_all()[0] for _ in range(3))
    mesh_agg = agg_all()[1]

    wave_identity = all(
        np.array_equal(host_masks[t][:n], mesh_masks[t][:n])
        for t, _d, _p, n in blocks)
    rows_identity = host_rows == mesh_rows
    agg_identity = host_agg == mesh_agg

    # watchdog leg: wedge every dispatch; coordinated serving must
    # degrade to the host kernels (identical rows, bounded wall, zero
    # hung scans). Two overrunning dispatches trip the watchdog, the
    # third drain serves with mesh serving disabled.
    MESH_SERVING.watchdog.deadline_s = 1e-9
    t0 = time.perf_counter()
    clear_masks()
    drain_all_multi()  # dispatch 1 overruns -> host fallback
    clear_masks()
    drain_all_multi()  # dispatch 2 overruns -> consecutive-failure trip
    clear_masks()
    wedged_rows = drain_all_multi()  # tripped: per-partition kernels
    wedged_wall = time.perf_counter() - t0
    wd = {
        "fallback_identity_ok": wedged_rows == host_rows,
        "wall_s": round(wedged_wall, 3),
        "trips": MESH_SERVING.watchdog.trips,
        "wedged": bool(MESH_SERVING.status()["dispatch_wedged"]),
        "fallbacks": MESH_SERVING.status()["mesh_fallback_count"],
    }
    table.close()
    import shutil

    shutil.rmtree(tmpdir, ignore_errors=True)

    speedup = host_wave / max(mesh_wave, 1e-9)
    agg_speedup = host_agg_s / max(mesh_agg_s, 1e-9)
    identity_ok = wave_identity and rows_identity and agg_identity
    out = {
        "records": n_records, "partitions": n_partitions,
        "devices": len(jax.devices()), "blocks": len(blocks),
        "selectivity": 0.1,
        "host_wave_ms": round(host_wave * 1e3, 2),
        "mesh_wave_ms": round(mesh_wave * 1e3, 2),
        "mesh_speedup": round(speedup, 3) if identity_ok else 0.0,
        "agg_host_ms": round(host_agg_s * 1e3, 2),
        "agg_mesh_ms": round(mesh_agg_s * 1e3, 2),
        "agg_speedup": round(agg_speedup, 3),
        "mesh_served": mesh_served,
        "mesh_drain_served": mesh_drain_served,
        "mesh_agg_served": mesh_agg_served,
        "wave_identity_ok": wave_identity,
        "rows_identity_ok": rows_identity,
        "agg_identity_ok": agg_identity,
        "watchdog": wd,
        "gate_ok": bool(identity_ok and mesh_served and mesh_drain_served
                        and speedup >= 1.5
                        and wd["trips"] >= 1
                        and wd["fallback_identity_ok"] and wd["wedged"]),
    }
    return out


def measure_mesh_compact() -> dict:
    """mesh_compact phase: the compaction FILTER stage off the resident
    device-mesh image vs the per-window kernels, same run,
    identity-digest-gated, over a mesh of this process's jax.devices().

    Measures the bulk-compaction FILTER stage over >=8 partitions with
    the mesh DETACHED (per-partition submit/drain programs) vs
    ATTACHED (ONE whole-table SPMD dispatch + sibling cache serves),
    under the REAL mesh_compact_pays gate — no pinning. Then three
    full-compaction arms over copies of the same store — host-pipelined,
    mesh-filtered, and wedged-watchdog — must publish byte-identical
    SST files, and the mesh arm's publishes must refresh residency by
    survivor-gather (reuse counter, zero slab builds). Sets process-wide
    state: call under _mesh_phase_isolation()."""
    import hashlib
    import shutil

    import numpy as np

    import pegasus_tpu.storage.engine as engine_mod
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.client.client import PegasusClient
    from pegasus_tpu.client.table import Table
    from pegasus_tpu.ops.compaction import (
        compaction_eval_drain,
        compaction_eval_submit,
    )
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.storage.compact_pipeline import window_count
    from pegasus_tpu.utils.flags import FLAGS
    import jax

    n_records = int(os.environ.get("PEGBENCH_MESH_COMPACT_RECORDS",
                                   192_000))
    n_partitions = int(os.environ.get("PEGBENCH_MESH_PARTITIONS", 8))
    seed = int(os.environ.get("PEGBENCH_SEED", 7))
    rng = np.random.default_rng(seed)

    tmpdir = tempfile.mkdtemp(prefix="pegbench_meshcompact")
    base = os.path.join(tmpdir, "base")
    FLAGS.set("pegasus.storage", "block_codec", "none")
    table = Table(base, partition_count=n_partitions)
    client = PegasusClient(table)
    t0 = time.perf_counter()
    for i in range(n_records):
        # ~30% of rows carry TTLs that will be expired at the arms'
        # shared filter timestamp (BASELINE config #3's retention sweep)
        ttl = 60 if rng.random() < 0.3 else 0
        assert client.set(b"user%06d" % (i // 10), b"s%02d" % (i % 10),
                          b"f=%024d" % i, ttl_seconds=ttl) == 0
    _log(f"loaded {n_records} records in {time.perf_counter() - t0:.1f}s")
    for s in table.partitions.values():
        s.engine.flush()
        s.engine.manual_compact()  # bulk filtering is over pure L1
    fixed_now = epoch_now() + 3600
    # the finish-time stamp lands in the SST index; freeze it so arms
    # can't straddle a second boundary and diverge on non-filter bytes
    engine_mod.epoch_now = lambda: fixed_now
    entries_per = {p: s.engine.lsm.bulk_compact_entries()
                   for p, s in sorted(table.partitions.items())}
    n_blocks = sum(len(e) for e in entries_per.values())
    host_windows = sum(window_count(len(e))
                       for e in entries_per.values())

    def host_filter_once():
        t0 = time.perf_counter()
        masks = {}
        for p, s in sorted(table.partitions.items()):
            blocks = [((run, i), run.read_block(i), p)
                      for run, i, _bm in entries_per[p]]
            pend = compaction_eval_submit(
                blocks, fixed_now, 0, s.partition_version, False,
                operations=None, eval_device=None, want_ets=False)
            for tag, drop, _e in compaction_eval_drain(
                    pend, want_ets=False):
                masks[(p,) + tag] = np.asarray(drop, bool)
        return time.perf_counter() - t0, masks

    def mesh_filter_once():
        MESH_SERVING._compact_cache.clear()
        t0 = time.perf_counter()
        masks = {}
        for p, s in sorted(table.partitions.items()):
            got = MESH_SERVING.try_compact_masks(
                s.engine.lsm, entries_per[p], fixed_now, 0, p,
                s.partition_version, False, None, want_ets=False,
                n_windows=window_count(len(entries_per[p])))
            if got is None:
                return time.perf_counter() - t0, None
            for (run, i), (drop, _e) in got.items():
                masks[(p, run, i)] = np.asarray(drop, bool)
        return time.perf_counter() - t0, masks

    # host arm first: mesh detached, per-partition window programs
    MESH_SERVING.reset()
    host_filter_once()  # warm compiles + OS page cache
    host_filter_s = min(host_filter_once()[0] for _ in range(3))
    host_masks = host_filter_once()[1]

    # mesh arm: attach every partition; the REAL gate routes
    for s in table.partitions.values():
        MESH_SERVING.attach(s)
    mesh_filter_once()  # warm: resident image + program compile
    mesh_filter_s = min(mesh_filter_once()[0] for _ in range(3))
    _t, mesh_masks = mesh_filter_once()
    mesh_served = mesh_masks is not None
    mask_identity = bool(
        mesh_served and host_masks.keys() == mesh_masks.keys()
        and all(np.array_equal(host_masks[k], mesh_masks[k])
                for k in host_masks))
    dispatches = MESH_SERVING.compact_dispatches
    serves = MESH_SERVING.compact_mask_serves
    MESH_SERVING.reset()
    table.close()

    def digest(d):
        out = []
        for root, _dirs, files in os.walk(d):
            for f in sorted(files):
                if f.endswith(".sst"):
                    p = os.path.join(root, f)
                    with open(p, "rb") as fh:
                        out.append((os.path.relpath(p, d),
                                    hashlib.sha256(
                                        fh.read()).hexdigest()))
        return sorted(out)

    def compact_arm(name, mesh=False, wedge=False):
        d = os.path.join(tmpdir, name)
        shutil.copytree(base, d)
        MESH_SERVING.reset()
        t = Table(d, partition_count=n_partitions)
        try:
            if mesh:
                for s in t.partitions.values():
                    MESH_SERVING.attach(s)
                assert MESH_SERVING.ensure_current()
            if wedge:
                MESH_SERVING.watchdog.deadline_s = 1e-9
            builds0 = MESH_SERVING.slab_builds
            t0 = time.perf_counter()
            for s in t.partitions.values():
                s.manual_compact(now=fixed_now)
            wall = time.perf_counter() - t0
            if mesh and not wedge:
                MESH_SERVING.ensure_current()  # publish-side refresh
            st = MESH_SERVING.status()
            st["slab_builds_during"] = MESH_SERVING.slab_builds - builds0
            return digest(d), wall, st
        finally:
            t.close()
            MESH_SERVING.reset()

    host_dig, host_wall, _ = compact_arm("host")
    mesh_dig, mesh_wall, mesh_st = compact_arm("mesh", mesh=True)
    wedge_dig, wedge_wall, wedge_st = compact_arm("wedged", mesh=True,
                                                  wedge=True)
    shutil.rmtree(tmpdir, ignore_errors=True)

    filter_speedup = host_filter_s / max(mesh_filter_s, 1e-9)
    digest_ok = host_dig == mesh_dig
    wedged_ok = host_dig == wedge_dig
    out = {
        "records": n_records, "partitions": n_partitions,
        "devices": len(jax.devices()), "blocks": n_blocks,
        "host_windows": host_windows,
        "host_filter_ms": round(host_filter_s * 1e3, 2),
        "mesh_filter_ms": round(mesh_filter_s * 1e3, 2),
        "filter_speedup": (round(filter_speedup, 3)
                           if mask_identity else 0.0),
        "mesh_served": mesh_served,
        "mask_identity_ok": mask_identity,
        "compact_host_s": round(host_wall, 3),
        "compact_mesh_s": round(mesh_wall, 3),
        "compact_wedged_s": round(wedge_wall, 3),
        "digest_identity_ok": digest_ok,
        "wedged_digest_ok": wedged_ok,
        "dispatches": dispatches,
        "mask_serves": serves,
        "arm_dispatches": mesh_st["compact_dispatches"],
        "refresh_reuses": mesh_st["refresh_reuses"],
        "refresh_rebuilds": mesh_st["refresh_rebuilds"],
        "refresh_slab_builds": mesh_st["slab_builds_during"],
        "wedged_fallbacks": wedge_st["compact_mesh_fallback_count"],
        "wedged_trips": wedge_st["watchdog"]["trips"],
        "gate_ok": bool(mask_identity and digest_ok and wedged_ok
                        and mesh_served and dispatches >= 1
                        and filter_speedup >= 1.5
                        and mesh_st["refresh_reuses"] >= n_partitions
                        and mesh_st["slab_builds_during"] == 0
                        and wedge_st["watchdog"]["trips"] >= 1),
    }
    return out


def measure_geo(jax, device, n_points=20_000, n_searches=150, seed=11):
    """Geo radius-search ops/sec (BASELINE config #5): cell-cover prefix
    scans + one batched device distance predicate per search."""
    import numpy as np

    from pegasus_tpu.client import PegasusClient, Table
    from pegasus_tpu.geo import GeoClient

    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory(prefix="peggeo") as tmp:
        raw = Table(os.path.join(tmp, "raw"), app_id=1, partition_count=8)
        idx = Table(os.path.join(tmp, "idx"), app_id=2, partition_count=8)
        geo = GeoClient(PegasusClient(raw), PegasusClient(idx))
        # ~20km x 20km urban box around (40, -74)
        lats = 40.0 + (rng.random(n_points) - 0.5) * 0.18
        lngs = -74.0 + (rng.random(n_points) - 0.5) * 0.24
        for i in range(n_points):
            geo.set(b"poi%06d" % i, b"s",
                    b"%f|%f|poi-%d" % (lats[i], lngs[i], i))
        raw.flush_all()
        idx.flush_all()
        with jax.default_device(device):
            # L0 -> L1 so the cell scans ride the batched device path
            idx.manual_compact_all(device=device)
        centers = rng.integers(0, n_points, size=n_searches)
        with jax.default_device(device):
            # warmup: full pass so compiles + first-touch block caches
            # are paid before measurement (both backends get the same
            # treatment when the caller measures accel and cpu in turn)
            for ci in centers:
                geo.search_radial(float(lats[ci]), float(lngs[ci]), 500)
            hits = 0
            t0 = time.perf_counter()
            for ci in centers:
                hits += len(geo.search_radial(float(lats[ci]),
                                              float(lngs[ci]), 500))
            secs = time.perf_counter() - t0
        raw.close()
        idx.close()
        return n_searches / secs, hits


def main() -> None:
    n_records = int(os.environ.get("PEGBENCH_RECORDS", 1_000_000))
    n_ops = int(os.environ.get("PEGBENCH_OPS", 12_000))
    n_partitions = int(os.environ.get("PEGBENCH_PARTITIONS", 64))
    seed = int(os.environ.get("PEGBENCH_SEED", 7))
    # all BASELINE.md phases run by default so the recorded details
    # cover every target row; =0 disables one for quick iteration
    do_compact = os.environ.get("PEGBENCH_COMPACT", "1") != "0"
    do_compressed = os.environ.get("PEGBENCH_COMPRESSED", "1") != "0"
    do_pushdown = os.environ.get("PEGBENCH_PUSHDOWN", "1") != "0"
    do_pipeline = os.environ.get("PEGBENCH_PIPELINE", "1") != "0"
    do_mixed = os.environ.get("PEGBENCH_MIXED", "1") != "0"
    do_geo = os.environ.get("PEGBENCH_GEO", "1") != "0"
    do_dup = os.environ.get("PEGBENCH_DUP", "1") != "0"
    do_health = os.environ.get("PEGBENCH_HEALTH", "1") != "0"
    do_perfctx = os.environ.get("PEGBENCH_PERFCTX", "1") != "0"
    do_follower = os.environ.get("PEGBENCH_FOLLOWER_READ", "1") != "0"
    do_qos = os.environ.get("PEGBENCH_QOS", "1") != "0"
    do_mesh = os.environ.get("PEGBENCH_MESH", "1") != "0"
    do_mesh_compact = os.environ.get("PEGBENCH_MESH_COMPACT", "1") != "0"

    details = {"phases": {}}
    here = os.path.dirname(os.path.abspath(__file__))

    def save_details():
        """Crash-durable phase results: every completed phase lands in
        BENCH_DETAILS.json IMMEDIATELY — a later phase that fails must
        not discard numbers already measured."""
        with open(os.path.join(here, "BENCH_DETAILS.json"), "w") as f:
            json.dump(details, f, indent=1)

    from pegasus_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    # the accel/cpu ratio needs the host backend beside the chip
    current = jax.config.jax_platforms or ""
    if current and "cpu" not in current.split(","):
        jax.config.update("jax_platforms", current + ",cpu")

    accel = jax.devices()[0]
    if accel.platform != "tpu":
        sys.exit(f"bench.py measures a TPU and found platform "
                 f"{accel.platform!r} ({accel.device_kind}): nothing "
                 f"was run")
    from pegasus_tpu import native

    if not native.available():
        sys.exit("bench.py needs the native library (pegasus_tpu/native) "
                 "and it did not build: nothing was run")
    cpu = jax.local_devices(backend="cpu")[0]
    _log(f"accelerator: {accel} ({accel.device_kind}, "
         f"{len(jax.devices())} devices), baseline: {cpu}")

    pallas_smoke()  # raises if Mosaic refuses the kernel
    _log(f"pallas fused-kernel smoke on {accel.platform}: ok")
    details["accel_platform"] = accel.platform
    details["device_kind"] = accel.device_kind
    details["n_devices"] = len(jax.devices())

    with tempfile.TemporaryDirectory(prefix="pegbench") as tmpdir:
        bc = build_cluster(tmpdir, n_records, n_partitions, seed)
        n_hashkeys = max(1, n_records // 10)
        try:
            ops, recs, accel_s = measure_scan_phase(
                jax, accel, bc, n_ops, n_partitions, n_hashkeys, seed + 2)
            accel_qps = ops / accel_s
            _log(f"accel: {ops} ops / {recs} records in {accel_s:.2f}s "
                 f"-> {accel_qps:.1f} ops/s, {recs / accel_s:.0f} rec/s")

            ops_c, recs_c, cpu_s = measure_scan_phase(
                jax, cpu, bc, n_ops, n_partitions, n_hashkeys, seed + 2)
            cpu_qps = ops_c / cpu_s
            _log(f"cpu:   {ops_c} ops / {recs_c} records in {cpu_s:.2f}s "
                 f"-> {cpu_qps:.1f} ops/s")
            details["phases"]["load_write"] = {
                "write_qps_2pc": bc.load_write_qps,
                "records": n_records,
            }
            details["phases"]["scan"] = {
                "accel_qps": round(accel_qps, 2),
                "cpu_qps": round(cpu_qps, 2),
                "accel_records_per_s": round(recs / accel_s, 1),
                "ops": n_ops, "records_loaded": n_records,
                "scan_batch": int(os.environ.get("PEGBENCH_SCAN_BATCH",
                                                 32)),
            }
            save_details()

            # a later phase that raises is recorded next to the numbers
            # already saved, and the run then exits non-zero without a
            # headline line
            try:
                # YCSB-C point gets (host-dominated: measures the full
                # client->gate->engine path; the accel/cpu ratio shows the
                # device path does not tax point reads)
                g_ops = max(2000, n_ops)
                # warm once for BOTH phases: the engine builds per-block
                # key lists lazily on first bisect — whichever phase runs
                # first would otherwise pay that construction and read slow
                run_point_gets(bc, g_ops, n_hashkeys, seed + 3)
                with jax.default_device(accel):
                    ops_g, hits_g, accel_g = run_point_gets(
                        bc, g_ops, n_hashkeys, seed + 3)
                with jax.default_device(cpu):
                    _o, _h, cpu_g = run_point_gets(bc, g_ops, n_hashkeys,
                                                   seed + 3)
                details["phases"]["point_get"] = {
                    "accel_qps": round(ops_g / accel_g, 2),
                    "cpu_qps": round(ops_g / cpu_g, 2),
                    "hit_rate": round(hits_g / ops_g, 4),
                }
                save_details()

                # batched point reads (the read-coordinator tentpole):
                # the SAME op stream coalesced 32 per flush through
                # point_read_multi, vs the single-request numbers above
                # — plus the server-side pair (no client/transport) and
                # the byte-identity acceptance gate
                pg_batch = int(os.environ.get("PEGBENCH_GET_BATCH", 32))
                identical = verify_point_batch_identity(
                    bc, n_hashkeys, seed + 3)
                with jax.default_device(accel):
                    run_point_gets_batched(bc, g_ops, n_hashkeys,
                                           seed + 3, batch=pg_batch)
                    ops_b, hits_b, accel_b = run_point_gets_batched(
                        bc, g_ops, n_hashkeys, seed + 3, batch=pg_batch)
                with jax.default_device(cpu):
                    run_point_gets_batched(bc, g_ops, n_hashkeys,
                                           seed + 3, batch=pg_batch)
                    _o, _h, cpu_b = run_point_gets_batched(
                        bc, g_ops, n_hashkeys, seed + 3, batch=pg_batch)
                # server-side: the r5 single-request hot loop vs the
                # coordinator, same stream, both warm (pass 1 warms)
                run_point_gets_server_side(bc, g_ops, n_hashkeys,
                                           seed + 3, batch=0)
                _o, _h, sv_solo = run_point_gets_server_side(
                    bc, g_ops, n_hashkeys, seed + 3, batch=0)
                run_point_gets_server_side(bc, g_ops, n_hashkeys,
                                           seed + 3, batch=pg_batch)
                _o, _h, sv_b = run_point_gets_server_side(
                    bc, g_ops, n_hashkeys, seed + 3, batch=pg_batch)
                details["phases"]["point_get_batch"] = {
                    "batch": pg_batch,
                    "accel_qps": round(ops_b / accel_b, 2),
                    "cpu_qps": round(ops_b / cpu_b, 2),
                    "hit_rate": round(hits_b / ops_b, 4),
                    "vs_single_request": round(
                        (ops_b / accel_b) / (ops_g / accel_g), 3),
                    "server_side_solo_qps": round(g_ops / sv_solo, 2),
                    f"server_side_batch{pg_batch}_qps": round(
                        g_ops / sv_b, 2),
                    "server_side_speedup": round(sv_solo / sv_b, 3),
                    "identical_to_unbatched": identical,
                }
                save_details()
                _log(f"point-get-batch({pg_batch}): "
                     f"{ops_b / accel_b:.0f} q/s client-batched "
                     f"({(ops_b / accel_b) / (ops_g / accel_g):.2f}x "
                     f"single-request); server-side "
                     f"{g_ops / sv_solo:.0f} -> {g_ops / sv_b:.0f} q/s "
                     f"({sv_solo / sv_b:.2f}x); "
                     f"identical={identical}")

                # batching-margin sweep: the same scan workload with
                # coalescing DISABLED (batch=1) on both backends — the
                # accel/cpu margin should GROW with the batch size,
                # since batching is what amortizes device dispatch
                m_ops = max(1500, n_ops // 8)
                with jax.default_device(accel):
                    run_scans(bc, m_ops, n_partitions, n_hashkeys,
                              seed + 5, insert_frac=0, scan_batch=1)
                    o1, _r1, a1 = run_scans(bc, m_ops, n_partitions,
                                            n_hashkeys, seed + 5,
                                            scan_batch=1)
                with jax.default_device(cpu):
                    run_scans(bc, m_ops, n_partitions, n_hashkeys,
                              seed + 5, insert_frac=0, scan_batch=1)
                    _o, _r, c1 = run_scans(bc, m_ops, n_partitions,
                                           n_hashkeys, seed + 5,
                                           scan_batch=1)
                ratio_b1 = (o1 / a1) / (o1 / c1) if a1 and c1 else 0
                base_batch = details["phases"]["scan"]["scan_batch"]
                ratio_bn = (details["phases"]["scan"]["accel_qps"]
                            / max(details["phases"]["scan"]["cpu_qps"],
                                  1e-9))
                details["phases"]["scan_batch_margin"] = {
                    "batch1_accel_qps": round(o1 / a1, 2),
                    "batch1_cpu_qps": round(o1 / c1, 2),
                    "batch1_vs_baseline": round(ratio_b1, 3),
                    "baseline_batch": base_batch,
                    f"batch{base_batch}_vs_baseline": round(ratio_bn, 3),
                }
                save_details()
                _log(f"scan margin: batch=1 ratio {ratio_b1:.3f}, "
                     f"batch={base_batch} ratio {ratio_bn:.3f}")
                _log(f"point-get: accel {ops_g / accel_g:.0f} q/s, "
                     f"cpu {ops_g / cpu_g:.0f} q/s, hits {hits_g}/{ops_g}")

                # batched write hot path (the round-7 write-side
                # tentpole): the same put workload single-request vs
                # coalesced `wb` per flush through write_multi (one
                # client_write_batch RPC per node per flush, one
                # mutation per touched partition, one group-commit
                # window), plus the server-side pair and the
                # results/state identity acceptance gate
                w_ops = max(2000, n_ops // 4)
                wb = int(os.environ.get("PEGBENCH_WRITE_BATCH", 32))
                w_identical = verify_write_batch_identity(bc, seed + 11)
                assert w_identical, \
                    "batched write results/state diverged from solo"
                run_puts(bc, 500, seed + 12, tag=b"wwarm")  # warm path
                ops_ws, errs_ws, solo_w = run_puts(bc, w_ops, seed + 13)
                ops_wb, errs_wb, batch_w = run_puts_batched(
                    bc, w_ops, seed + 14, batch=wb)
                sv_n, sv_solo_s = run_puts_server_side(
                    bc, w_ops, seed + 15, batch=0)
                _svn, sv_b_s = run_puts_server_side(
                    bc, w_ops, seed + 16, batch=wb)
                # short fsync-mode segment: the group-commit window's
                # shared fsync measured against op count, then the
                # default sync mode restored
                from pegasus_tpu.utils.flags import FLAGS as _FLAGS
                from pegasus_tpu.utils.metrics import METRICS as _MET

                fs_counter = _MET.entity("write", "node0").counter(
                    "plog_fsync_count")
                _FLAGS.set("pegasus.replica", "plog_sync_mode", "fsync")
                try:
                    fs0 = fs_counter.value()
                    run_puts_batched(bc, 1024, seed + 17, batch=wb,
                                     tag=b"wfs")
                    w_fsyncs = fs_counter.value() - fs0
                finally:
                    _FLAGS.set("pegasus.replica", "plog_sync_mode",
                               "flush")
                w_ratio = (ops_wb / batch_w) / (ops_ws / solo_w)
                details["phases"]["write_put_batch"] = {
                    "batch": wb,
                    "solo_qps": round(ops_ws / solo_w, 2),
                    "batched_qps": round(ops_wb / batch_w, 2),
                    "vs_single_request": round(w_ratio, 3),
                    "server_side_solo_qps": round(sv_n / sv_solo_s, 2),
                    f"server_side_batch{wb}_qps": round(
                        sv_n / sv_b_s, 2),
                    "server_side_speedup": round(sv_solo_s / sv_b_s, 3),
                    "errors": errs_ws + errs_wb,
                    "identical_to_solo": w_identical,
                    "meets_1_8x": w_ratio >= 1.8,
                    "fsync_mode_segment": {
                        "ops": 1024, "plog_fsyncs": w_fsyncs,
                        "fsyncs_per_op": round(w_fsyncs / 1024, 4)},
                }
                save_details()
                _log(f"write-put-batch({wb}): "
                     f"{ops_ws / solo_w:.0f} -> {ops_wb / batch_w:.0f} "
                     f"w/s client path ({w_ratio:.2f}x); server-side "
                     f"{sv_n / sv_solo_s:.0f} -> {sv_n / sv_b_s:.0f} w/s "
                     f"({sv_solo_s / sv_b_s:.2f}x); "
                     f"identical={w_identical}; fsync-mode segment: "
                     f"{w_fsyncs} fsyncs / 1024 ops")

                # round-8 filtered reads: bloom probe pruning + the
                # node row cache, measured against the UNfiltered
                # baseline IN THE SAME RUN over a deep-L0 store, with
                # byte-identity gates on both workloads (the filters'
                # whole contract is "faster, bit-for-bit the same")
                from pegasus_tpu.utils.flags import FLAGS as _F8

                f_ops = max(3000, n_ops // 2)
                fb = int(os.environ.get("PEGBENCH_FILTER_BATCH", 128))
                # deep-L0 state: 16 overlay tables — the bulk-load /
                # ingest-heavy shape (`rocksdb.usage_scenario =
                # bulk_load` turns auto-compaction OFF, so the overlay
                # grows unboundedly until the load finishes), with rows
                # interleaved across the probed keyspace
                deepen_l0(bc, n_hashkeys, seed + 21, n_l0=16,
                          rows_per_flush=min(2 * n_hashkeys, 50_000))
                miss_stream = _point_miss_stream(f_ops, n_hashkeys,
                                                 seed + 22)
                hot_stream = _point_hot_stream(f_ops, n_hashkeys,
                                               seed + 23)
                id_miss, id_hot = miss_stream[:512], hot_stream[:512]

                def _mode(bloom: bool, rc_bytes: int,
                          phash: bool = False) -> None:
                    _F8.set("pegasus.server", "bloom_probe", bloom)
                    _F8.set("pegasus.server", "phash_probe", phash)
                    _F8.set("pegasus.server", "row_cache_bytes",
                            rc_bytes)
                    for s in bc.servers:
                        s._point_cache = None  # re-plan under this mode

                def _measure(stream, reps=3, fresh_loc=False):
                    """Median-of-reps elapsed (the onebox shares the
                    host with the jax runtime; single runs jitter).
                    `fresh_loc` resets the per-generation location
                    cache before each rep: a uniform miss stream never
                    repeats a key in production, so letting rep 1's
                    locations serve reps 2-3 would measure PR 1's
                    cache, not the probe path — block caches and key
                    lists (state that IS warm in production) keep."""
                    import statistics as _stats

                    run_point_stream_server_side(bc, stream, fb)  # warm
                    out = []
                    for _ in range(reps):
                        if fresh_loc:
                            for s in bc.servers:
                                s._point_cache = None
                        _o, hits, el = run_point_stream_server_side(
                            bc, stream, fb)
                        out.append((el, hits))
                    return (_stats.median(e for e, _h in out),
                            out[0][1])

                _mode(False, 0)  # unfiltered, uncached baseline
                base_miss_id = collect_point_results(bc, id_miss, fb)
                base_hot_id = collect_point_results(bc, id_hot, fb)
                base_miss_s, m_hits = _measure(miss_stream,
                                               fresh_loc=True)
                base_hot_s, h_hits = _measure(hot_stream)
                _mode(True, 0)   # the filter layer alone (miss gate)
                miss_ident = collect_point_results(
                    bc, id_miss, fb) == base_miss_id
                flt_miss_s, m_hits_f = _measure(miss_stream,
                                                fresh_loc=True)
                _mode(True, 33_554_432)  # PR-8 production: bloom + rc
                hot_ident = collect_point_results(
                    bc, id_hot, fb) == base_hot_id
                flt_hot_s, h_hits_f = _measure(hot_stream)

                # round-15: the perfect-hash index against the PR 4
                # bloom+bisect pair — SAME run, same store, same
                # streams, byte-identity gated against the same
                # unfiltered baseline results. Indexed runs answer
                # candidacy AND location in one hash pass: misses die
                # with zero block touches, hits skip both bisects.
                _mode(True, 0, phash=True)
                ph_miss_ident = collect_point_results(
                    bc, id_miss, fb) == base_miss_id
                ph_miss_s, m_hits_p = _measure(miss_stream,
                                               fresh_loc=True)
                _mode(True, 33_554_432, phash=True)  # new production
                ph_hot_ident = collect_point_results(
                    bc, id_hot, fb) == base_hot_id
                ph_hot_s, h_hits_p = _measure(hot_stream)

                # resident index memory, same-store: what the bloom
                # bits cost vs what the phash costs, per key (the
                # bisect path ALSO lazily materializes ~key_width+64
                # bytes/row of key lists / probe tables on hot blocks
                # — memory the phash never allocates; not counted
                # here, so the phash column is its worst case)
                total_keys = bloom_b = phash_b = 0
                runs_all = runs_ph = 0
                for s in bc.servers:
                    _lsm = s.engine.lsm
                    for t in list(_lsm.l0) + list(_lsm.l1_runs):
                        total_keys += t.total_count
                        im = t.index_memory()
                        bloom_b += im["bloom"]
                        phash_b += im["phash"]
                        runs_all += 1
                        runs_ph += t.phash is not None
                index_memory = {
                    "total_keys": total_keys, "runs": runs_all,
                    "runs_with_phash": runs_ph,
                    "bloom_bytes": bloom_b, "phash_bytes": phash_b,
                    "bloom_bytes_per_key": round(
                        bloom_b / max(1, total_keys), 3),
                    "phash_bytes_per_key": round(
                        phash_b / max(1, total_keys), 3),
                }
                details["phases"]["index_memory"] = index_memory

                miss_x = base_miss_s / flt_miss_s
                hot_x = base_hot_s / flt_hot_s
                ph_miss_x = base_miss_s / ph_miss_s
                ph_hot_x = base_hot_s / ph_hot_s
                details["phases"]["point_get_miss"] = {
                    "ops": f_ops, "batch": fb,
                    "hit_rate": round(m_hits_f / f_ops, 4),
                    "unfiltered_qps": round(f_ops / base_miss_s, 2),
                    "filtered_qps": round(f_ops / flt_miss_s, 2),
                    "phash_qps": round(f_ops / ph_miss_s, 2),
                    "speedup": round(miss_x, 3),
                    "phash_speedup": round(ph_miss_x, 3),
                    "phash_vs_bloom": round(flt_miss_s / ph_miss_s, 3),
                    "meets_2x": miss_x >= 2.0,
                    "beats_bloom": ph_miss_x > miss_x,
                    "identical_to_unfiltered": bool(
                        miss_ident and m_hits == m_hits_f),
                    "phash_identical": bool(
                        ph_miss_ident and m_hits == m_hits_p),
                }
                details["phases"]["point_get_hot"] = {
                    "ops": f_ops, "batch": fb,
                    "hit_rate": round(h_hits_f / f_ops, 4),
                    "unfiltered_qps": round(f_ops / base_hot_s, 2),
                    "row_cache_qps": round(f_ops / flt_hot_s, 2),
                    "phash_qps": round(f_ops / ph_hot_s, 2),
                    "speedup": round(hot_x, 3),
                    "phash_speedup": round(ph_hot_x, 3),
                    "phash_vs_bloom": round(flt_hot_s / ph_hot_s, 3),
                    "meets_1_5x": hot_x >= 1.5,
                    "beats_bloom": ph_hot_x > hot_x,
                    "identical_to_uncached": bool(
                        hot_ident and h_hits == h_hits_f),
                    "phash_identical": bool(
                        ph_hot_ident and h_hits == h_hits_p),
                }
                save_details()
                with open(os.path.join(here, "BENCH_r08.json"), "w") as f:
                    json.dump({"phases": {
                        "point_get_miss":
                            details["phases"]["point_get_miss"],
                        "point_get_hot":
                            details["phases"]["point_get_hot"],
                    }, "accel_platform": accel.platform}, f, indent=1)
                with open(os.path.join(here, "BENCH_r15.json"), "w") as f:
                    json.dump({"phases": {
                        "point_get_miss":
                            details["phases"]["point_get_miss"],
                        "point_get_hot":
                            details["phases"]["point_get_hot"],
                        "index_memory": index_memory,
                    }, "accel_platform": accel.platform}, f, indent=1)
                _log(f"point-get-miss: {f_ops / base_miss_s:.0f} -> "
                     f"{f_ops / flt_miss_s:.0f} (bloom, {miss_x:.2f}x)"
                     f" -> {f_ops / ph_miss_s:.0f} q/s (phash, "
                     f"{ph_miss_x:.2f}x, identical={ph_miss_ident}); "
                     f"point-get-hot: {f_ops / base_hot_s:.0f} -> "
                     f"{f_ops / flt_hot_s:.0f} (bloom+rc, {hot_x:.2f}x)"
                     f" -> {f_ops / ph_hot_s:.0f} q/s (phash+rc, "
                     f"{ph_hot_x:.2f}x, identical={ph_hot_ident}); "
                     f"index_memory: bloom "
                     f"{index_memory['bloom_bytes_per_key']} B/key vs "
                     f"phash {index_memory['phash_bytes_per_key']} "
                     f"B/key over {total_keys} keys")

                if do_compact:
                    gb = float(os.environ.get("PEGBENCH_COMPACT_GB", "1.0"))
                    exp_frac = float(os.environ.get("PEGBENCH_EXPIRED",
                                                    "0.5"))
                    for mode in ("ttl", "rules"):
                        a_g, a_s, a_in, a_out = measure_compaction_scaled(
                            jax, accel, tmpdir, mode, gb, exp_frac, seed)
                        _log(f"compact[{mode}]: accel {a_g:.3f} GB/s "
                             f"({a_s:.1f}s, {a_in / 1e9:.2f} GB -> "
                             f"{a_out / 1e9:.2f} GB)")
                        c_g, c_s, _c_in, _c_out = measure_compaction_scaled(
                            jax, cpu, tmpdir, mode, gb, exp_frac, seed)
                        _log(f"compact[{mode}]: cpu   {c_g:.3f} GB/s "
                             f"({c_s:.1f}s)")
                        details["phases"][f"compact_{mode}"] = {
                            "accel_gbps": round(a_g, 4),
                            "cpu_gbps": round(c_g, 4),
                            "vs_baseline": round(a_g / c_g, 3) if c_g else 0,
                            "input_gb": round(a_in / 1e9, 3),
                            "output_gb": round(a_out / 1e9, 3),
                            "expired_frac": exp_frac if mode == "ttl"
                            else 0.05,
                            "accel_seconds": round(a_s, 2),
                            "cpu_seconds": round(c_s, 2),
                        }
                        save_details()

                if do_compressed:
                    # round-11: compressed SST output + direct compute.
                    # Single-backend phases (the codec work is host-side
                    # by design — deflate/inflate and the encoded probes
                    # never touch the device), so each runs once on the
                    # serving backend and compares codec none vs dcz
                    # same-run.
                    gb = float(os.environ.get(
                        "PEGBENCH_COMPRESSED_GB", "1.0"))
                    exp_frac = float(os.environ.get("PEGBENCH_EXPIRED",
                                                    "0.5"))
                    cc = measure_compressed_compact(
                        jax, accel, tmpdir, gb, exp_frac, seed)
                    details["phases"]["compact_compressed"] = cc
                    save_details()
                    _log(f"compact_compressed: effective "
                         f"{cc['dcz']['effective_input_gb_per_s']:.3f} "
                         f"GB/s vs {cc['none']['effective_input_gb_per_s']:.3f}"
                         f" uncompressed ({cc['effective_speedup']:.2f}x,"
                         f" ratio {cc['dcz']['output_compression_ratio']}"
                         f", identical={cc['identity_ok']})")
                    cs = measure_compressed_scan(
                        jax, accel, tmpdir,
                        min(n_records, 200_000), n_partitions,
                        n_ops, seed)
                    details["phases"]["scan_compressed"] = cs
                    save_details()
                    _log(f"scan_compressed: dcz "
                         f"{cs['dcz']['ops_per_s']:.0f} vs none "
                         f"{cs['none']['ops_per_s']:.0f} ops/s "
                         f"({cs['ops_ratio_dcz_vs_none']:.3f}x, disk "
                         f"{cs['disk_ratio']:.3f}, "
                         f"identical={cs['identity_ok']})")

                if do_pushdown:
                    # scan pushdown: server-side value filter +
                    # aggregates vs the same work client-side, swept
                    # across selectivities (host-side kernels — one
                    # serving backend, same-run comparison)
                    sp = measure_scan_pushdown(
                        jax, accel, tmpdir,
                        min(n_records, 100_000), n_partitions, seed)
                    details["phases"]["scan_pushdown"] = sp
                    save_details()
                    _log(f"scan_pushdown: {sp['pushdown_speedup']:.2f}x "
                         f"at sel 0.1 (0.9: "
                         f"{sp['sel_0.9']['pushdown_speedup']:.2f}x, "
                         f"0.01: "
                         f"{sp['sel_0.01']['pushdown_speedup']:.2f}x), "
                         f"identical={sp['identity_ok']}, agg wire "
                         f"O(parts)={sp['agg_wire_o_partitions']}")

                if do_pipeline:
                    # round-12: staged compaction pipeline, serial vs
                    # pipelined same-run (single backend — the overlap
                    # is host-side disk/CPU/filter; the device leg is
                    # inside the filter stage either way)
                    gb = float(os.environ.get(
                        "PEGBENCH_PIPELINE_GB", "1.0"))
                    exp_frac = float(os.environ.get("PEGBENCH_EXPIRED",
                                                    "0.5"))
                    pc = measure_pipelined_compact(
                        jax, accel, tmpdir, gb, exp_frac, seed)
                    details["phases"]["compact_pipelined"] = pc
                    save_details()
                    _log(f"compact_pipelined: "
                         f"{pc['pipelined']['input_gb_per_s']:.3f} vs "
                         f"{pc['serial']['input_gb_per_s']:.3f} GB/s "
                         f"serial ({pc['speedup']:.2f}x, "
                         f"identical={pc['identity_ok']})")

                if do_mixed:
                    ml = measure_mixed_load(jax, accel, tmpdir, seed)
                    details["phases"]["mixed_load"] = ml
                    save_details()
                    _log(f"mixed_load: p99 on/off "
                         f"{ml.get('p99_ratio_on_vs_off')}; forward "
                         f"progress={ml['forward_progress_ok']}")

                if do_health:
                    ho = measure_health_overhead(tmpdir, seed)
                    details["phases"]["health_overhead"] = ho
                    save_details()
                    _log(f"health_overhead: tick {ho['tick_ms']}ms -> "
                         f"{ho['cadence_overhead']:.2%} of a core at "
                         f"the default cadence (sim A/B read "
                         f"{ho['read_overhead']:+.2%} / write "
                         f"{ho['write_overhead']:+.2%} at ~1000x "
                         f"cadence, rings {ho['ring_bytes_total']}B, "
                         f"events={ho['events_fired']}, gate<=2%: "
                         f"{ho['gate_ok']}, "
                         f"identical={ho['identity_ok']})")

                if do_perfctx:
                    po = measure_perfctx_overhead(tmpdir, seed)
                    details["phases"]["perfctx_overhead"] = po
                    save_details()
                    _log(f"perfctx_overhead: contexts-on read "
                         f"{po['read_overhead']:+.2%} / scan "
                         f"{po['scan_overhead']:+.2%} vs hard-off "
                         f"(gate<=2%: {po['gate_ok']}, "
                         f"identical={po['identity_ok']})")

                if do_qos:
                    qi = measure_qos_isolation(tmpdir, seed)
                    details["phases"]["qos_isolation"] = qi
                    save_details()
                    with open(os.path.join(here, "BENCH_r20.json"),
                              "w") as f:
                        json.dump({"phases": {"qos_isolation": qi},
                                   "accel_platform": accel.platform},
                                  f, indent=1)
                    _log(f"qos_isolation: admission read "
                         f"{qi['admission_read_overhead']:+.2%} / scan "
                         f"{qi['admission_scan_overhead']:+.2%} "
                         f"enforce-on vs off; compliant p99 "
                         f"{qi['abuser_off']['compliant_p99_ms']}ms solo"
                         f" -> {qi['abuser_on']['compliant_p99_ms']}ms "
                         f"under abuse ({qi['compliant_p99_ratio']}x, "
                         f"abuser overbudget="
                         f"{qi['abuser_on']['abuser_overbudget']}, "
                         f"identical={qi['identity_ok']}, "
                         f"gate: {qi['gate_ok']})")

                if do_follower:
                    fr = measure_follower_read(tmpdir, seed)
                    details["phases"]["follower_read"] = fr
                    save_details()
                    with open(os.path.join(here, "BENCH_r17.json"),
                              "w") as f:
                        json.dump({"phases": {"follower_read": fr},
                                   "accel_platform": accel.platform},
                                  f, indent=1)
                    _log(f"follower_read: aggregate "
                         f"{fr['linearizable']['aggregate_read_qps']} "
                         f"-> {fr['follower']['aggregate_read_qps']} "
                         f"q/s ({fr['speedup']}x, "
                         f"{fr['follower']['serving_replicas']} serving"
                         f" replicas, bounces={fr['stale_bounces']}, "
                         f"identical={fr['identity_ok']}, "
                         f"gate>=2x: {fr['gate_ok']})")

                if do_dup:
                    dc = measure_dup_catchup(tmpdir, seed)
                    details["phases"]["dup_catchup"] = dc
                    save_details()
                    _log(f"dup_catchup: batched+compressed "
                         f"{dc['batched']['catchup_sim_s']}s vs solo "
                         f"{dc['solo']['catchup_sim_s']}s sim "
                         f"({dc['speedup_sim']}x, wire ratio "
                         f"{dc['batched']['compression_ratio']}, "
                         f"governed backoffs "
                         f"{dc['governed']['governor_backoffs']}, "
                         f"identical={dc['identity_ok']}, "
                         f"gate={dc['gate_ok']})")

                if do_mesh:
                    with _mesh_phase_isolation():
                        ms = measure_mesh_scan()
                    details["phases"]["mesh_scan"] = ms
                    save_details()
                    with open(os.path.join(here, "BENCH_r18.json"),
                              "w") as f:
                        json.dump({"phases": {"mesh_scan": ms},
                                   "accel_platform": accel.platform},
                                  f, indent=1)
                    _log(f"mesh_scan: wave {ms['host_wave_ms']}ms host "
                         f"-> {ms['mesh_wave_ms']}ms mesh "
                         f"({ms['mesh_speedup']}x, agg "
                         f"{ms['agg_speedup']}x) over "
                         f"{ms['partitions']} partitions / "
                         f"{ms['devices']} devices, identical="
                         f"{ms['rows_identity_ok']}, watchdog fallback "
                         f"identical={ms['watchdog']['fallback_identity_ok']}"
                         f", gate>=1.5x: {ms['gate_ok']}")

                if do_mesh_compact:
                    with _mesh_phase_isolation():
                        mc = measure_mesh_compact()
                    details["phases"]["mesh_compact"] = mc
                    save_details()
                    with open(os.path.join(here, "BENCH_r19.json"),
                              "w") as f:
                        json.dump({"phases": {"mesh_compact": mc},
                                   "accel_platform": accel.platform},
                                  f, indent=1)
                    _log(f"mesh_compact: filter "
                         f"{mc['host_filter_ms']}ms host -> "
                         f"{mc['mesh_filter_ms']}ms mesh "
                         f"({mc['filter_speedup']}x over "
                         f"{mc['partitions']} partitions, "
                         f"{mc['host_windows']} windows -> "
                         f"{mc['dispatches']} dispatch), digests "
                         f"identical={mc['digest_identity_ok']}, wedged "
                         f"identical={mc['wedged_digest_ok']}, refresh "
                         f"reuses={mc['refresh_reuses']}, gate>=1.5x: "
                         f"{mc['gate_ok']}")

                if do_geo:
                    g_accel, g_hits = measure_geo(jax, accel)
                    g_cpu, _ = measure_geo(jax, cpu)
                    details["phases"]["geo_radius_search"] = {
                        "accel_qps": round(g_accel, 2),
                        "cpu_qps": round(g_cpu, 2),
                        "vs_baseline": round(g_accel / g_cpu, 3) if g_cpu
                        else 0,
                        "hits": g_hits,
                    }
                    save_details()
                    _log(f"geo: accel {g_accel:.1f} q/s, cpu {g_cpu:.1f} q/s")

            except Exception as e:
                details["error_phase"] = f"{type(e).__name__}: {e}"[:300]
                save_details()
                raise

            print(json.dumps({
                "metric": "YCSB-E scan ops/sec/chip (64-partition, "
                          "TTL+hash-validated)",
                "value": round(accel_qps, 2),
                "unit": "ops/s",
                "vs_baseline": round(accel_qps / cpu_qps, 3)
                if cpu_qps else 0,
                "platform": accel.platform,
                "device_kind": accel.device_kind,
                "n_devices": len(jax.devices()),
            }))
        finally:
            bc.close()


if __name__ == "__main__":
    main()
