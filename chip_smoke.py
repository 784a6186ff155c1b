#!/usr/bin/env python
"""Standing check that the served path runs on the local TPU.

    python chip_smoke.py

The parent never imports JAX. It starts three children, one after the
other, each of which exits (and so frees the chip) before the next
starts:

  probe    - asks JAX what it found; anything but a TPU ends the run
             here. Rebuilds the native libraries from the committed
             sources.
  stage B  - the README quick-start path: `onebox_cluster.start` with one
             meta process (CPU) and one replica node process that owns
             the chip, a table loaded and read over TCP, the node's
             `placement` verdict fetched at the end. The child itself
             (launcher + client) stays on the CPU backend.
  stage A  - the path the benchmark's cells measure (BENCHMARK.json): an
             in-process 3-node SimCluster, BASELINE.json config #2
             (1,000,000 records, 64 partitions, 3 replicas), served
             through ClusterClient; compactions with the filter stage on
             the device; the resident mesh image; the device kernels one
             by one.

Every answer is compared with a plain model kept in this file (Model).
There is no CPU mode: a stage that is meant to hold the chip fails when
`jax.devices()[0].platform != "tpu"`. The stage functions take their
size as arguments so that tests/test_chip_smoke.py can call them tiny on
the CPU backend.

Stdout carries two lines, each one JSON object: the summary (also
written to chip_smoke_out/summary.json), then, last,
`{"ok": true, "device": {"platform", "kind", "count"}}` with exactly
those keys. On failure nothing is printed there and the exit code is not
0. The numbers in the summary are set-up facts (seconds of load,
compile, serve; link rates), not performance results.
"""

from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import hashlib
import json
import os
import signal
import struct
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")

SIZE_A = dict(n_records=1_000_000, n_partitions=64, n_nodes=3,
              n_scans=2048, n_gets=2048, n_sets=3000)
SIZE_B = dict(n_records=100_000, n_partitions=8, n_scans=200, n_gets=1000)

# BASELINE.json config #4's shape: a hashkey-prefix delete
# plus a hashkey-pattern + sortkey-prefix delete
RULES_BASELINE = [
    {"op": "delete_key",
     "rules": [{"type": "hashkey_pattern", "match": "prefix",
                "pattern": "user000001"}]},
    {"op": "delete_key",
     "rules": [{"type": "hashkey_pattern", "match": "anywhere",
                "pattern": "7777"},
               {"type": "sortkey_pattern", "match": "prefix",
                "pattern": "s0"}]},
]
# the mesh-filtered compaction's own rule, so its effect can be told
# apart from the one above
RULES_MESH = [
    {"op": "delete_key",
     "rules": [{"type": "hashkey_pattern", "match": "prefix",
                "pattern": "user000002"}]},
]


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- the plain model --------------------------------------------------------

def model_key(hk: bytes, sk: bytes) -> bytes:
    """The store's key order: big-endian u16 hashkey length, hashkey,
    sortkey, compared as bytes."""
    return struct.pack(">H", len(hk)) + hk + sk


def rule_matches(rules, hk: bytes, sk: bytes) -> bool:
    """Does a row match a delete ruleset? Operations are OR-ed, the
    rules inside one operation AND-ed."""
    for op in rules:
        ok = True
        for r in op["rules"]:
            data = hk if r["type"] == "hashkey_pattern" else sk
            pat = r["pattern"].encode()
            ok &= {"prefix": data.startswith(pat),
                   "postfix": data.endswith(pat),
                   "anywhere": pat in data}[r["match"]]
        if ok:
            return True
    return False


class Model:
    """Sorted (hashkey, sortkey) -> (value, expire_ts), per partition.
    Imports nothing from pegasus_tpu.ops, server or storage; the
    partition of a hashkey is the repo's golden-vector-tested crc64."""

    def __init__(self, n_partitions: int):
        from pegasus_tpu.base.key_schema import key_hash_parts

        self.n_partitions = n_partitions
        self._hash = key_hash_parts
        self.rows = [dict() for _ in range(n_partitions)]  # key -> row
        self._order = [None] * n_partitions                # sorted keys

    def partition_of(self, hk: bytes) -> int:
        return self._hash(hk) % self.n_partitions

    def put(self, hk: bytes, sk: bytes, value: bytes, ets: int) -> None:
        p = self.partition_of(hk)
        self.rows[p][model_key(hk, sk)] = (hk, sk, value, ets)
        self._order[p] = None

    @staticmethod
    def expired(ets: int, now: int) -> bool:
        return 0 < ets <= now

    def get(self, hk: bytes, sk: bytes, now: int):
        row = self.rows[self.partition_of(hk)].get(model_key(hk, sk))
        if row is None or self.expired(row[3], now):
            return None
        return row[2]

    def scan(self, pidx: int, start_key: bytes, inclusive: bool, n: int,
             sk_prefix: bytes, now: int):
        """First n unexpired rows of the partition at or after start_key
        whose sortkey starts with sk_prefix: [(key, value)]."""
        order = self._order[pidx]
        if order is None:
            order = self._order[pidx] = sorted(self.rows[pidx])
        i = (bisect.bisect_left if inclusive
             else bisect.bisect_right)(order, start_key)
        rows = self.rows[pidx]
        out = []
        while i < len(order) and len(out) < n:
            _hk, sk, value, ets = rows[order[i]]
            if not self.expired(ets, now) and sk.startswith(sk_prefix):
                out.append((order[i], value))
            i += 1
        return out

    def compact(self, now: int, rules=None) -> None:
        """What a manual compaction leaves: no expired row, no row a
        delete rule matches."""
        for p in range(self.n_partitions):
            self.rows[p] = {
                k: r for k, r in self.rows[p].items()
                if not self.expired(r[3], now)
                and not (rules and rule_matches(rules, r[0], r[1]))}
            self._order[p] = None

    def physical_counts(self):
        return [len(r) for r in self.rows]

    def live_count(self, now: int, value_prefix: bytes = b"") -> int:
        return sum(1 for part in self.rows for r in part.values()
                   if not self.expired(r[3], now)
                   and r[2].startswith(value_prefix))


def make_records(n_records: int, seed: int, now: int):
    """The smoke's table: n/10 hashkeys x 10 sortkeys,
    `field0=%064d` values, 10% already expired. Yields
    (hk, sk, value, expire_ts)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    expired = rng.random(n_records) <= 0.10
    i = 0
    for h in range(max(1, n_records // 10)):
        hk = b"user%08d" % h
        for j in range(10):
            if i >= n_records:
                return
            yield (hk, b"s%02d" % j, b"field0=%064d" % i,
                   max(1, now - 100) if expired[i] else 0)
            i += 1


# -- measurement helpers (jax side) -----------------------------------------

class CompileMeter:
    """Counts what JAX compiled and what the persistent cache served,
    from jax.monitoring's own events."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event in self._DURATIONS:
            with self._lock:
                self.seconds += duration
                if event == self._DURATIONS[2]:
                    self.programs += 1

    def _on_event(self, event, **_kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self):
        with self._lock:
            return {"compile_s": self.seconds, "programs": self.programs,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}


class Phases:
    """Wall seconds per phase with the compile seconds that fell inside
    it taken out, summed by kind (load / serve)."""

    def __init__(self, meter: CompileMeter):
        self.meter = meter
        self.rows = []

    @contextlib.contextmanager
    def phase(self, name: str, kind: str):
        c0 = self.meter.snapshot()["compile_s"]
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.meter.snapshot()["compile_s"] - c0
        self.rows.append({"phase": name, "kind": kind,
                          "wall_s": round(wall, 3),
                          "compile_s": round(comp, 3)})
        log(f"{name}: {wall:.1f}s wall, {comp:.1f}s of it compiling")

    def summary(self):
        out = {"load_s": 0.0, "serve_s": 0.0}
        for r in self.rows:
            # compile events from pool threads overlap; never below 0
            out[r["kind"] + "_s"] += max(0.0, r["wall_s"] - r["compile_s"])
        snap = self.meter.snapshot()
        out = {k: round(v, 3) for k, v in out.items()}
        out["compile_s"] = round(snap["compile_s"], 3)
        out["programs_compiled"] = snap["programs"]
        out["compile_cache_hits"] = snap["cache_hits"]
        out["compile_cache_misses"] = snap["cache_misses"]
        out["phases"] = self.rows
        return out


def measure_link() -> dict:
    """The placement probe's own numbers (RTT, 16 MiB H2D and D2H) and
    the per-program dispatch floor: median wall of a trivial jitted
    program on a resident operand, result awaited."""
    import statistics

    import jax
    import jax.numpy as jnp

    from pegasus_tpu.ops.placement import probe_link

    probe = probe_link()
    x = jnp.zeros(1024, jnp.uint32)
    f = jax.jit(lambda a: a + jnp.uint32(1))
    f(x).block_until_ready()
    laps = []
    for _ in range(300):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        laps.append(time.perf_counter() - t0)
    out = {"dispatch_floor_s": statistics.median(laps)}
    if probe is not None:
        out.update(link_rtt_s=probe.rtt_s, h2d_gbps_16mib=probe.h2d_gbps,
                   d2h_gbps_16mib=probe.d2h_gbps)
    return out


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs), "cpu_count": os.cpu_count()}


def memory_stats() -> list:
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"device": str(d),
                    "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


# -- the device kernels, one by one ------------------------------------------

def check_kernels(pallas_interpret: bool, seed: int) -> dict:
    """Each device program the store has, at the shape the store gives
    it, against plain Python over the same rows. Returns first-call
    seconds (compile + run) per program."""
    import numpy as np

    from pegasus_tpu.base.crc import crc64
    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.ops import pallas_scan
    from pegasus_tpu.ops.compaction import (
        COMPACT_CHUNK_ROWS,
        make_compaction_eval,
    )
    from pegasus_tpu.ops.compaction_rules import parse_rules
    from pegasus_tpu.ops.device_crc import crc64_device
    from pegasus_tpu.ops.predicates import (
        FT_MATCH_ANYWHERE,
        FT_MATCH_POSTFIX,
        FT_MATCH_PREFIX,
        FT_NO_FILTER,
        FilterSpec,
        multi_static_block_predicate,
        static_block_predicate,
    )
    from pegasus_tpu.ops.record_block import RecordBlock, build_record_block
    from pegasus_tpu.storage.sstable import BLOCK_CAPACITY

    import jax

    rng = np.random.default_rng(seed)
    n_parts = 64

    # one store-shaped block: BLOCK_CAPACITY rows, key width 32
    rows = []
    for i in range(BLOCK_CAPACITY):
        hk = b"user%08d" % int(rng.integers(0, 100_000))
        sk = b"s%02d" % int(rng.integers(0, 20))
        rows.append((hk, sk, 0 if i % 7 else 1 + i % 3))
    rows.sort(key=lambda r: model_key(r[0], r[1]))
    block = build_record_block([generate_key(hk, sk) for hk, sk, _e in rows],
                               [e for _hk, _sk, e in rows],
                               capacity=BLOCK_CAPACITY, key_width=32)
    check(block.hash_lo is not None, "native packer gave no hash_lo column")
    pidx = key_hash_parts(rows[0][0]) % n_parts
    pv = n_parts - 1
    now = 2

    def col(pred):
        return np.array([bool(pred(hk, sk, e)) for hk, sk, e in rows])

    owned = col(lambda hk, sk, e: key_hash_parts(hk) % n_parts == pidx)
    expired = col(lambda hk, sk, e: 0 < e <= now)
    flavors = {  # filter type -> (pattern, plain sortkey match)
        FT_NO_FILTER: (b"", np.ones(len(rows), bool)),
        FT_MATCH_PREFIX: (b"s1", col(lambda hk, sk, e: sk.startswith(b"s1"))),
        FT_MATCH_POSTFIX: (b"7", col(lambda hk, sk, e: sk.endswith(b"7"))),
        FT_MATCH_ANYWHERE: (b"1", col(lambda hk, sk, e: b"1" in sk)),
    }
    cases = []  # (name, run, expected)

    # Pallas fused kernel and the XLA static predicate, all four sortkey
    # filter types, ownership validated against the resident hash column
    for ft, (pat, match) in flavors.items():
        cases.append((
            f"pallas_ft{ft}",
            lambda ft=ft, pat=pat: pallas_scan.fused_scan_block(
                block, now, sort_filter=FilterSpec.make(ft, pat), pidx=pidx,
                partition_version=pv, validate_hash=True,
                interpret=pallas_interpret),
            (match & owned & ~expired, expired)))
        cases.append((
            f"static_predicate_ft{ft}",
            lambda ft=ft, pat=pat: np.unpackbits(np.asarray(
                static_block_predicate(
                    block, sort_filter=FilterSpec.make(ft, pat),
                    validate_hash=True, pidx=pidx, partition_version=pv,
                    pack=True))).astype(bool),
            match & owned))
    # the same predicate hashing the key bytes on the device
    bare = RecordBlock(block.keys, block.key_len, block.hashkey_len,
                       block.expire_ts, block.valid, None)
    cases.append((
        "static_predicate_crc_on_device",
        lambda: np.asarray(static_block_predicate(
            bare, hash_filter=FilterSpec.make(FT_MATCH_PREFIX, b"user0"),
            validate_hash=True, pidx=pidx, partition_version=pv)),
        owned & col(lambda hk, sk, e: hk.startswith(b"user0"))))
    # multi-flavor form: two prefix patterns over one block, bit-packed
    specs = [(FilterSpec.none(), FilterSpec.make(FT_MATCH_PREFIX, p))
             for p in (b"s0", b"s1")]
    cases.append((
        "multi_flavor_predicate",
        lambda: multi_static_block_predicate(block, specs, True, pidx, pv),
        np.stack([owned & col(lambda hk, sk, e, p=p: sk.startswith(p))
                  for p in (b"s0", b"s1")])))
    # crc64 on device: the key-width-long loop of table gathers
    ref = np.array([crc64(hk) for hk, _s, _e in rows], dtype=np.uint64)
    cases.append((
        "crc64_device",
        lambda: tuple(np.asarray(a) for a in jax.jit(crc64_device)(
            block.keys, np.asarray(block.hashkey_len), 2)),
        ((ref >> np.uint64(32)).astype(np.uint32),
         (ref & np.uint64(0xFFFFFFFF)).astype(np.uint32))))
    # compaction filter program at its real chunk size, with the rules
    n = COMPACT_CHUNK_ROWS
    reps = -(-n // len(rows))
    ets = rng.integers(0, 4, size=n).astype(np.uint32)
    prog = make_compaction_eval(parse_rules(RULES_BASELINE))
    cases.append((
        "compaction_eval_256k_rows",
        lambda: np.unpackbits(np.asarray(prog(
            np.tile(np.asarray(block.keys), (reps, 1))[:n],
            np.tile(np.asarray(block.key_len), reps)[:n],
            np.tile(np.asarray(block.hashkey_len), reps)[:n], ets,
            np.ones(n, bool), np.tile(np.asarray(block.hash_lo), reps)[:n],
            np.uint32(now), np.uint32(0), np.uint32(pidx), np.uint32(pv),
            True, True, want_ets=False, pack=True)[0]),
            count=n).astype(bool),
        ((ets > 0) & (ets <= now)) | np.tile(
            ~owned | col(lambda hk, sk, e: rule_matches(RULES_BASELINE,
                                                       hk, sk)), reps)[:n]))

    times, errors = {}, {}
    for name, run, expected in cases:
        t0 = time.perf_counter()
        try:
            got = run()
            times[name] = round(time.perf_counter() - t0, 3)
            same = (all(np.array_equal(g, e) for g, e in zip(got, expected))
                    if isinstance(expected, tuple)
                    else np.array_equal(got, expected))
            if not same:
                errors[name] = "result differs from the plain reference"
        except Exception as exc:  # report every broken kernel, not the first
            errors[name] = f"{type(exc).__name__}: {exc}"[:2000]
            log(f"kernel {name} failed: {errors[name]}")
    check(not errors, f"device kernels failed: {json.dumps(errors)}")
    return times


# -- stage A: in-process cluster, the path the benchmark measures -------------

def _scan_all(client, model, requests, now, digest, batch: int = 32):
    """Send `requests` [(pidx, start_key, n, sk_prefix)] through
    client.scan_multi in batches of `batch`; compare each with the
    model's first-n. A one_page reply can stop short of n (the server
    bounds the rows one ranged read examines), so a short, non-empty
    reply is continued from its last key, as a client would."""
    from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX, FT_NO_FILTER
    from pegasus_tpu.server.types import GetScannerRequest

    got = [[] for _ in requests]
    pending = [(i, r[1], True, r[2]) for i, r in enumerate(requests)]
    n_requests = 0
    while pending:
        chunk, pending = pending[:batch], pending[batch:]
        groups = {}
        for i, start, inclusive, remaining in chunk:
            pidx, _s, _n, prefix = requests[i]
            groups.setdefault(pidx, []).append((i, GetScannerRequest(
                start_key=start, start_inclusive=inclusive,
                batch_size=remaining,
                sort_key_filter_type=(FT_MATCH_PREFIX if prefix
                                      else FT_NO_FILTER),
                sort_key_filter_pattern=prefix,
                validate_partition_hash=True, one_page=True)))
        n_requests += len(chunk)
        replies = client.scan_multi(
            {p: [req for _i, req in lst] for p, lst in groups.items()})
        remaining_of = {i: rem for i, _s, _inc, rem in chunk}
        for p, lst in groups.items():
            for (i, _req), resp in zip(lst, replies[p]):
                check(resp.error == 0, f"scan error {resp.error}")
                kvs = [(kv.key, kv.value) for kv in resp.kvs]
                got[i].extend(kvs)
                if 0 < len(kvs) < remaining_of[i]:
                    pending.append((i, kvs[-1][0], False,
                                    remaining_of[i] - len(kvs)))
    for i, (pidx, start, n, prefix) in enumerate(requests):
        want = model.scan(pidx, start, True, n, prefix, now)
        check(got[i] == want,
              f"scan {i} (partition {pidx}, n={n}, prefix={prefix!r}) "
              f"returned {len(got[i])} rows, the model {len(want)}")
        for k, v in got[i]:
            digest.update(k)
            digest.update(v)
    return n_requests


def _get_all(client, model, keys, now, digest, batch: int = 32):
    """Point gets [(hk, sk)] through client.point_read_multi, compared
    with the model (a miss or an expired row is 'not found')."""
    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts

    hits = 0
    for off in range(0, len(keys), batch):
        chunk = keys[off:off + batch]
        groups = {}
        for j, (hk, sk) in enumerate(chunk):
            ph = key_hash_parts(hk, sk)
            groups.setdefault(ph % model.n_partitions, []).append(
                (j, ("get", generate_key(hk, sk), ph)))
        replies = client.point_read_multi(
            {p: [op for _j, op in lst] for p, lst in groups.items()})
        for p, lst in groups.items():
            for (j, _op), (err, value) in zip(lst, replies[p]):
                hk, sk = chunk[j]
                want = model.get(hk, sk, now)
                if want is None:
                    check(err != 0, f"get {hk!r}/{sk!r} found a row the "
                                    f"model does not have")
                else:
                    check(err == 0 and value == want,
                          f"get {hk!r}/{sk!r} wrong: err={err}")
                    hits += 1
                    digest.update(value)
    return hits


def _hashkey_scan(client, model, hk: bytes, sk_prefix: bytes, now, digest):
    """One hashkey's rows through the paging scanner (client.get_scanner)
    against the model. Its window misses are evaluated on the device
    whatever the block codec."""
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.client.client import ScanOptions
    from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX

    opts = ScanOptions(sort_key_filter_type=FT_MATCH_PREFIX,
                       sort_key_filter_pattern=sk_prefix)
    rows = [(generate_key(h, s), v)
            for h, s, v in client.get_scanner(hk, options=opts)]
    # a hashkey has at most a dozen rows: the model's first 20 from its
    # start cover it, the rest belong to later hashkeys
    want = [kv for kv in model.scan(model.partition_of(hk),
                                    generate_key(hk, b""), True, 20,
                                    sk_prefix, now)
            if kv[0][2:2 + len(hk)] == hk]
    check(rows == want, f"hashkey scan of {hk!r} differs")
    for k, v in rows:
        digest.update(k)
        digest.update(v)


def _drift_samples(drift_status: dict) -> int:
    """Device waves the cost-model auditor has seen (DRIFT.status())."""
    return sum(c.get("samples", 0)
               for c in drift_status.get("classes", {}).values())


def _requests(rng, model, n_scans: int, n_hashkeys: int):
    """A YCSB-E-shaped scan stream (zipfian-ish partition and start key, up to
    100 records), half of it with a sortkey prefix; two prefixes of one
    width so a batch also takes the multi-flavor program."""
    from pegasus_tpu.base.key_schema import generate_key

    n_p = model.n_partitions
    weights = 1.0 / (1.0 + rng.permutation(n_p).astype(float))
    weights /= weights.sum()
    pidxs = rng.choice(n_p, size=n_scans, p=weights)
    starts = (rng.random(n_scans) ** 2.0 * n_hashkeys).astype(int)
    lens = rng.integers(1, 101, size=n_scans)
    prefixes = (b"", b"s03", b"", b"s07")
    return [(int(pidxs[i]), generate_key(b"user%08d" % int(starts[i]), b""),
             int(lens[i]), prefixes[i % 4]) for i in range(n_scans)]


def _fallback_counters() -> dict:
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING

    st = MESH_SERVING.status()
    return {"mesh_fallback_count": st["mesh_fallback_count"],
            "compact_mesh_fallback_count": st["compact_mesh_fallback_count"],
            "watchdog_trips": st["watchdog"]["trips"],
            "dispatch_wedged": st["dispatch_wedged"],
            "disabled": st["disabled"]}


def stage_a(workdir: str, seed: int, n_records: int, n_partitions: int,
            n_nodes: int, n_scans: int, n_gets: int, n_sets: int,
            pallas_interpret: bool = False) -> dict:
    import numpy as np

    from pegasus_tpu import native
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.client.table import compact_partitions_parallel
    from pegasus_tpu.ops.compaction_rules import compile_rules
    from pegasus_tpu.ops.placement import placement_verdict
    from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX
    from pegasus_tpu.client.client import ScanOptions
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.server.scan_coordinator import MaskPrefresher
    from pegasus_tpu.server.workload import DRIFT
    from pegasus_tpu.tools.cluster import SimCluster
    from pegasus_tpu.utils.flags import FLAGS
    from pegasus_tpu.utils.metrics import METRICS

    check(native.available(), "the native library did not build")
    meter = CompileMeter()
    phases = Phases(meter)
    facts = dict(device_facts(), records=n_records, partitions=n_partitions,
                 replicas=n_nodes, seed=seed)
    log(f"stage A on {facts['platform']} ({facts['device_kind']} x "
        f"{facts['n_devices']}), {n_records} records / {n_partitions} "
        f"partitions / {n_nodes} replicas")
    facts["link"] = measure_link()
    counters0 = _fallback_counters()
    digest = hashlib.sha256()
    rng = np.random.default_rng(seed + 1)
    n_hashkeys = max(1, n_records // 10)
    model = Model(n_partitions)
    node_metrics = METRICS.entity("storage", "node")
    encoded_probes = node_metrics.relaxed_counter("encoded_probe_count")

    saved_flags = [(s, n, FLAGS.get(s, n)) for s, n in (
        ("pegasus.storage", "block_codec"),
        ("pegasus.server", "rocksdb_max_iteration_count"))]
    # Raw columnar blocks for the first, device-bound part: with the
    # default codec (dcz2) the scan_multi path answers a block's static
    # mask from the encoded form on the host and a TTL compaction never
    # leaves the host either (ROADMAP S1). The last compaction below
    # rewrites the table in the default codec and the serve is repeated.
    FLAGS.set("pegasus.storage", "block_codec", "none")
    with phases.phase("kernels", "serve"):
        facts["kernel_first_call_s"] = check_kernels(pallas_interpret, seed)
    cluster = SimCluster(os.path.join(workdir, "sim"), n_nodes=n_nodes)
    prefresher = None
    try:
        app_id = cluster.create_table("smoke", partition_count=n_partitions,
                                      replica_count=n_nodes)
        client = cluster.client("smoke")
        client.refresh_config()
        primaries = cluster.primaries(app_id)
        gpids = [(app_id, p) for p in range(n_partitions)]
        primary_of = [cluster.stubs[primaries[p]].get_replica(gpids[p])
                      for p in range(n_partitions)]
        replicas_of = [[s.get_replica(g) for s in cluster.stubs.values()
                        if s.get_replica(g) is not None] for g in gpids]
        check(all(len(rs) == n_nodes for rs in replicas_of),
              "a partition has fewer replicas than asked for")
        all_servers = [r.server for rs in replicas_of for r in rs]

        def compact_all(**kw):
            compact_partitions_parallel(all_servers, **kw)

        def physical_equal(what):
            want = model.physical_counts()
            for p, rs in enumerate(replicas_of):
                for r in rs:
                    lsm = r.server.engine.lsm
                    have = (sum(t.total_count for t in lsm.l0)
                            + sum(t.total_count for t in lsm.l1_runs)
                            + len(lsm.memtable))
                    check(have == want[p],
                          f"{what}: partition {p} on {r.name} holds {have} "
                          f"rows, the model {want[p]}")

        def live_count_equal(what, value_prefix=b""):
            opts = ScanOptions()
            if value_prefix:
                opts = ScanOptions(value_filter_type=FT_MATCH_PREFIX,
                                   value_filter_pattern=value_prefix)
            have = sum(s.count() for s in
                       client.get_unordered_scanners(1, opts))
            want = model.live_count(epoch_now(), value_prefix)
            check(have == want, f"{what}: count aggregate says {have}, "
                                f"the model {want}")
            return have

        # load: batched mutations through the primary's 2PC, then sets
        # through the client
        with phases.phase("load", "load"):
            now = epoch_now()
            per_pidx = [[] for _ in range(n_partitions)]
            for hk, sk, value, ets in make_records(n_records, seed, now):
                model.put(hk, sk, value, ets)
                per_pidx[model.partition_of(hk)].append(
                    WriteOp(OP_PUT, (generate_key(hk, sk), value, ets)))
            acked = [0]

            def on_ack(results):
                acked[0] += sum(1 for r in results if r == 0)

            for p, ops in enumerate(per_pidx):
                for off in range(0, len(ops), 1000):
                    primary_of[p].client_write(ops[off:off + 1000], on_ack)
                    cluster.loop.run_until_idle()
            check(acked[0] == n_records,
                  f"{acked[0]} of {n_records} loaded writes were acked")
            # hashkeys no delete rule below matches; a third carry a TTL
            # the TTL compaction (run at a later `now`) will drop
            written = []
            for i in range(n_sets // 2):
                hk, sk = b"write%07d" % i, b"s00"
                value = b"set-before-compaction-%d" % i
                ttl = 3600 if i % 3 == 0 else 0
                ets = epoch_now() + ttl if ttl else 0
                check(client.set(hk, sk, value, ttl_seconds=ttl) == 0,
                      "client.set failed")
                model.put(hk, sk, value, ets)
                written.append((hk, sk))
            # a secondary applies a write when it hears of the commit:
            # the group-check timer tells it
            cluster.step(rounds=2)
            for srv in all_servers:
                srv.flush()
            compact_all()
            model.compact(epoch_now())
            physical_equal("after load + compaction to L1")

        prefresher = MaskPrefresher(
            [r.server for r in primary_of]).start()

        # serve: scans and gets, every answer against the model
        def serve(tag):
            now = epoch_now()
            p0 = encoded_probes.value()
            w0 = _drift_samples(DRIFT.status())
            reqs = _requests(rng, model, n_scans, n_hashkeys)
            sent = _scan_all(client, model, reqs, now, digest)
            keys = []
            for i in range(n_gets):
                h = int(rng.random() ** 2.0 * n_hashkeys)
                if i % 4 == 3:   # misses: absent sortkey, absent hashkey
                    keys.append((b"user%08d" % h, b"s%02d" %
                                 int(rng.integers(10, 20))) if i % 8 == 3
                                else (b"nobody%06d" % h, b"s00"))
                else:
                    keys.append((b"user%08d" % h,
                                 b"s%02d" % int(rng.integers(0, 10))))
            hits = _get_all(client, model, keys, now, digest)
            readback = _get_all(client, model, written, now, digest)
            gone = sum(1 for hk, sk in written
                       if model.get(hk, sk, now) is None)  # TTL ran out
            check(readback == len(written) - gone,
                  f"{len(written) - gone - readback} acknowledged writes "
                  f"were not read back")
            for _ in range(16):
                _hashkey_scan(client, model, b"user%08d" % int(
                    rng.integers(0, n_hashkeys)), b"s0", now, digest)
            facts[f"serve_{tag}"] = {
                "scan_requests": sent, "gets": len(keys), "get_hits": hits,
                "writes_read_back": readback,
                "encoded_host_probes": int(encoded_probes.value() - p0),
                "audited_device_waves":
                    _drift_samples(DRIFT.status()) - w0}
            log(f"serve[{tag}]: {facts[f'serve_{tag}']}")

        with phases.phase("serve_raw_blocks", "serve"):
            serve("raw_blocks")

        # pure-L1 stores from here on, so each compaction takes the bulk
        # block path: whole columnar blocks through the device filter
        with phases.phase("ttl_compaction", "serve"):
            later = epoch_now() + 7200  # past the sets' one-hour TTL
            before = sum(model.physical_counts())
            compact_all(now=later)
            model.compact(later)
            check(sum(model.physical_counts()) < before,
                  "the TTL compaction had nothing to drop")
            physical_equal("after the TTL compaction")

        with phases.phase("rules_compaction", "serve"):
            facts["placement_rules"] = placement_verdict("rules")
            compact_all(now=epoch_now(),
                        rules_filter=compile_rules(RULES_BASELINE))
            model.compact(epoch_now(), RULES_BASELINE)
            physical_equal("after the rules compaction")
            live_count_equal("after the rules compaction")

        # the resident mesh image over every partition's primary
        with phases.phase("mesh", "serve"):
            FLAGS.set("pegasus.server", "rocksdb_max_iteration_count", 0)
            for r in primary_of:
                MESH_SERVING.attach(r.server)
            now = epoch_now()
            # a wave: one scan per partition under a filter no mask is
            # cached for yet, so every block's mask is a miss
            wave = [(p, b"", 10, b"s05") for p in range(n_partitions)]
            _scan_all(client, model, wave, now, digest, batch=n_partitions)
            live_count_equal("mesh count aggregate")
            live_count_equal("mesh count aggregate with a value filter",
                             b"field0=0000000")
            st = MESH_SERVING.status()
            check(st["wave_dispatches"] >= 1,
                  "no scan wave was served by the mesh program")
            check(st["agg_dispatches"] >= 1,
                  "no aggregate was served by the mesh program")
            facts["mesh_image"] = _mesh_image_facts(n_partitions)
            # mesh-filtered compaction; it also rewrites the table in
            # the default codec
            FLAGS.set("pegasus.storage", "block_codec", saved_flags[0][2])
            compact_all(now=epoch_now(),
                        rules_filter=compile_rules(RULES_MESH))
            model.compact(epoch_now(), RULES_MESH)
            physical_equal("after the mesh-filtered compaction")
            st = MESH_SERVING.status()
            check(st["compact_dispatches"] >= 1,
                  "no compaction was filtered by the mesh program")
            facts["mesh"] = {k: st[k] for k in (
                "platform", "devices", "wave_dispatches", "agg_dispatches",
                "compact_dispatches", "compact_mask_serves", "host_waves",
                "slab_builds", "stack_builds", "refresh_reuses",
                "refresh_rebuilds", "compiles", "compile_s",
                "resident_bytes")}
            counters = _fallback_counters()  # reset() zeroes the watchdog
            check(counters == counters0,
                  f"a mesh fallback fired: {counters0} -> {counters}")
            MESH_SERVING.reset()
            FLAGS.set("pegasus.server", "rocksdb_max_iteration_count",
                      saved_flags[1][2])

        with phases.phase("serve_default_codec", "serve"):
            for i in range(n_sets - n_sets // 2):
                hk, sk = b"write%07d" % (n_sets + i), b"s00"
                value = b"set-after-compactions-%d" % i
                check(client.set(hk, sk, value) == 0, "client.set failed")
                model.put(hk, sk, value, 0)
                written.append((hk, sk))
            serve("default_codec")
            live_count_equal("after the last compaction")

        # replicas agree, nothing fell back, the warmer never raised
        cluster.loop.run_until_idle()
        cluster.step(rounds=2)
        for p, rs in enumerate(replicas_of):
            decrees = {r.last_committed_decree for r in rs}
            check(len(decrees) == 1, f"partition {p}: replicas disagree on "
                                     f"the last committed decree {decrees}")
        prefresher.stop()
        check(prefresher.errors == 0,
              f"the mask prefresher raised {prefresher.errors} times")
        facts["prefresher"] = {"masks_warmed": prefresher.refreshed,
                               "errors": prefresher.errors}
        facts["block_cache_device_bytes"] = sum(
            a.nbytes for srv in all_servers
            for blk in list(srv._device_block_cache.values())
            for a in blk if a is not None)
        counters = _fallback_counters()
        check(counters == counters0,
              f"a device fallback fired during the stage: {counters0} -> "
              f"{counters}")
        facts["fallback_counters"] = counters
        facts["drift"] = DRIFT.status()
        facts["memory"] = memory_stats()
        facts["results_sha256"] = digest.hexdigest()
        facts.update(phases.summary())
        return facts
    finally:
        if prefresher is not None:
            prefresher.stop()
        MESH_SERVING.reset()
        cluster.close()
        for s, n, v in saved_flags:
            FLAGS.set(s, n, v)


def _mesh_image_facts(n_partitions: int) -> dict:
    """Where the resident image lives: every device of the mesh holds an
    equal share of the partitions' rows."""
    import jax

    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING

    (tres,) = MESH_SERVING._tables.values()
    keys = tres.stack.keys
    devices = sorted(str(d) for d in keys.sharding.device_set)
    per_device = sorted({s.data.shape[0] for s in keys.addressable_shards})
    check(len(devices) == len(jax.devices()),
          f"the image sits on {len(devices)} devices of "
          f"{len(jax.devices())}")
    check(per_device == [keys.shape[0] // len(devices)],
          f"partitions per device are uneven: {per_device}")
    return {"shape": list(keys.shape), "devices": devices,
            "partitions_per_device": per_device[0],
            "attached_partitions": n_partitions}


# -- stage B: the README quick-start path --------------------------------------

def stage_b(workdir: str, seed: int, n_records: int, n_partitions: int,
            n_scans: int, n_gets: int, chip_node="node0") -> dict:
    """Multi-process onebox over TCP: the meta on the CPU, `chip_node`
    (None: nobody) owning the chip, this process on the CPU backend."""
    import numpy as np

    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.rpc.codec import OP_PUT
    from pegasus_tpu.tools import onebox_cluster as ob
    from pegasus_tpu.utils.errors import PegasusError

    d = os.path.join(workdir, "onebox")
    facts = {"records": n_records, "partitions": n_partitions,
             "chip_node": chip_node}
    digest = hashlib.sha256()
    rng = np.random.default_rng(seed + 2)
    model = Model(n_partitions)
    t_start = time.perf_counter()
    node_log = os.path.join(d, "logs", "node0.log")
    try:
        ob.start(d, n_replica=1, chip_node=chip_node)
        with open(node_log) as f:
            boot = [ln.strip() for ln in f if "jax platform=" in ln]
        check(boot, "node0 logged no device line at boot")
        facts["node0_boot"] = boot[0]
        log(boot[0])
        admin = ob.OneboxAdmin(d)
        deadline = time.monotonic() + 90  # the meta hears node0's beacon
        while True:
            try:
                if admin.call("list_nodes", timeout=6):
                    break
            except PegasusError:
                pass  # a meta still booting drops the first frames
            check(time.monotonic() < deadline, "node0 never joined the meta")
            time.sleep(0.3)
        admin.create_table("smoke", partition_count=n_partitions,
                           replica_count=1)
        client = ob.connect("smoke", d, op_timeout_ms=60_000)
        client.refresh_config()

        t0 = time.perf_counter()
        now = epoch_now()
        groups, pending = {}, 0
        for hk, sk, value, ets in make_records(n_records, seed, now):
            model.put(hk, sk, value, ets)
            ph = key_hash_parts(hk, sk)
            groups.setdefault(ph % n_partitions, []).append(
                (OP_PUT, (generate_key(hk, sk), value, ets), ph))
            pending += 1
            if pending == 2000:
                for results in client.write_multi(groups).values():
                    check(all(r == 0 for r in results), "a write failed")
                groups, pending = {}, 0
        if groups:
            for results in client.write_multi(groups).values():
                check(all(r == 0 for r in results), "a write failed")
        # compact to L1 the way an operator does: the one-shot trigger
        # env, delivered by config-sync; done when every partition has
        # published an L1 run and dropped its L0s
        admin.call("update_app_envs", app_name="smoke", envs={
            "manual_compact.once.trigger_time": str(int(time.time()))})
        sst = os.path.join(d, "data", "node0", "*", "app", "sst")
        deadline = time.monotonic() + 240
        while True:
            l1 = {os.path.dirname(p) for p in
                  glob.glob(os.path.join(sst, "l1-*.sst"))}
            l0 = glob.glob(os.path.join(sst, "l0-*.sst"))
            if len(l1) == n_partitions and not l0:
                break
            check(time.monotonic() < deadline,
                  f"compaction did not finish: {len(l1)} of "
                  f"{n_partitions} partitions have an L1 run, "
                  f"{len(l0)} L0 files left")
            time.sleep(0.5)
        model.compact(epoch_now())
        facts["load_s"] = round(time.perf_counter() - t0, 3)
        log(f"stage B loaded and compacted in {facts['load_s']}s")

        t0 = time.perf_counter()
        now = epoch_now()
        n_hashkeys = max(1, n_records // 10)
        reqs = _requests(rng, model, n_scans // 2, n_hashkeys)
        _scan_all(client, model, reqs, now, digest)
        # the other half through the paging scanner
        for i in range(n_scans - n_scans // 2):
            _hashkey_scan(client, model, b"user%08d" % int(
                rng.integers(0, n_hashkeys)), (b"s0", b"s03")[i % 2], now,
                digest)
        keys = []
        for i in range(n_gets):
            h = int(rng.random() ** 2.0 * n_hashkeys)
            keys.append((b"user%08d" % h, b"s%02d" % int(
                rng.integers(0, 10) if i % 4 else rng.integers(10, 20))))
        facts["get_hits"] = _get_all(client, model, keys, now, digest)
        facts["serve_s"] = round(time.perf_counter() - t0, 3)

        pl = admin.remote_command("node0", "placement", ["rules"])
        facts["placement"] = {"breakdown": pl["breakdown"],
                              "drift": pl["drift"]}
        metrics = admin.remote_command("node0", "metrics", ["storage"])
        facts["node0_prefresh_errors"] = _metric_value(
            metrics, "mask_prefresh_error_count")
        check(facts["node0_prefresh_errors"] == 0,
              "node0's mask prefresher raised")
        facts["results_sha256"] = digest.hexdigest()
        facts["wall_s"] = round(time.perf_counter() - t_start, 3)
        client.net.close()
        admin.close()
        return facts
    except BaseException:
        if os.path.exists(node_log):
            with open(node_log, errors="replace") as f:
                log("node0 log tail:\n" + "".join(f.readlines()[-30:]))
        raise
    finally:
        ob.stop(d)


def _metric_value(snapshot, name: str):
    """A metric's value in a METRICS.snapshot() reply:
    [{"metrics": {name: {"value": v}}}]."""
    for entity in snapshot:
        if name in entity["metrics"]:
            return entity["metrics"][name]["value"]
    raise SmokeFailure(f"node0 reports no metric {name!r}")


def check_stage_b_on_chip(facts: dict) -> None:
    """What must hold when node0 was given the chip."""
    check("platform=tpu" in facts["node0_boot"],
          f"node0 is not on a TPU: {facts['node0_boot']}")
    bd = facts["placement"]["breakdown"]
    check(bd["accelerator_present"], "node0's placement sees no accelerator")
    check("tpu" in str(bd["routed"]).lower(),
          f"node0 routes 'rules' to {bd['routed']!r}, not to a TPU device")
    check(_drift_samples(facts["placement"]["drift"]) >= 1,
          "node0 audited no device wave")


# -- children ------------------------------------------------------------------

def child_probe() -> dict:
    import jax

    facts = device_facts()
    if facts["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke.py needs a TPU; jax found platform "
            f"{facts['platform']!r} ({facts['n_devices']} x "
            f"{facts['device_kind']}). There is no CPU mode.")
    del jax
    # what runs must be built from the committed sources, not a stale
    # .so the copy brought along
    from pegasus_tpu import native
    from pegasus_tpu.native import wire_client

    for so in glob.glob(os.path.join(os.path.dirname(native.__file__),
                                     "*.so")):
        os.remove(so)
    t0 = time.perf_counter()
    check(native.available(), "the native library did not build")
    check(wire_client.load() is not None,
          "the native wire client did not build")
    facts["native_build_s"] = round(time.perf_counter() - t0, 3)
    return facts


def child_stage_a(seed: int) -> dict:
    from pegasus_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    facts = device_facts()
    check(facts["platform"] == "tpu",
          f"stage A holds the chip but jax found {facts['platform']!r}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_a") as tmp:
        out = stage_a(tmp, seed, **SIZE_A)
    out["compile_cache_dir"] = cache_dir
    check(out["placement_rules"] == "device",
          f"placement_verdict('rules') is {out['placement_rules']!r}")
    return out


def child_stage_b(seed: int) -> dict:
    # the launcher and the client must not take the chip from node0
    os.environ["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory(prefix="chip_smoke_b") as tmp:
        out = stage_b(tmp, seed, **SIZE_B)
    check_stage_b_on_chip(out)
    import jax

    check({d.platform for d in jax.devices()} == {"cpu"},
          "the stage B client process was not held to the CPU backend")
    return out


CHILDREN = {"probe": lambda seed: child_probe(), "b": child_stage_b,
            "a": child_stage_a}


def run_child(stage: str, seed: int, out_path: str) -> None:
    facts = CHILDREN[stage](seed)
    with open(out_path, "w") as f:
        json.dump(facts, f, indent=1, default=str)


# -- parent ----------------------------------------------------------------------

def run_stage(stage: str, seed: int, timeout_s: float) -> dict:
    """One child in its own process group; whatever it started is gone
    when this returns."""
    out_path = os.path.join(OUT_DIR, f"stage_{stage}.json")
    if os.path.exists(out_path):
        os.remove(out_path)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--stage", stage,
         "--seed", str(seed), "--out", out_path],
        cwd=HERE, start_new_session=True, stdout=sys.stderr)
    try:
        rc = proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        rc = f"timeout after {timeout_s:.0f}s"
    finally:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
    if rc != 0 or not os.path.exists(out_path):
        raise SystemExit(f"chip_smoke: stage {stage} failed ({rc})")
    with open(out_path) as f:
        facts = json.load(f)
    facts["stage_wall_s"] = round(time.monotonic() - t0, 3)
    return facts


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--stage", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)  # how the parent runs a child
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.stage:
        run_child(args.stage, args.seed, args.out)
        return

    os.makedirs(OUT_DIR, exist_ok=True)
    # the limits add up to less than the 1200 s the whole run may take
    probe = run_stage("probe", args.seed, 120)
    log(f"probe: {probe}")
    b = run_stage("b", args.seed, 300)
    a = run_stage("a", args.seed, 700)
    summary = {
        "ok": True,
        "platform": a["platform"], "device_kind": a["device_kind"],
        "n_devices": a["n_devices"], "cpu_count": a["cpu_count"],
        "native_build_s": probe["native_build_s"],
        "stage_a": {k: a[k] for k in (
            "records", "partitions", "replicas", "stage_wall_s", "load_s",
            "compile_s", "serve_s", "programs_compiled",
            "compile_cache_hits", "compile_cache_misses", "link",
            "kernel_first_call_s", "placement_rules", "mesh", "mesh_image",
            "block_cache_device_bytes", "memory", "fallback_counters",
            "prefresher", "serve_raw_blocks", "serve_default_codec",
            "results_sha256")},
        "stage_b": {k: b[k] for k in (
            "records", "partitions", "stage_wall_s", "load_s", "serve_s",
            "node0_boot", "get_hits", "results_sha256")},
        "stage_b_placement": b["placement"]["breakdown"],
        "total_wall_s": round(time.monotonic() - _T0, 3),
        "claim": None,
    }
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    # the line the driver reads: these keys and no others, the device as
    # the child that held the chip saw it
    print(json.dumps({"ok": True, "device": {
        "platform": a["platform"], "kind": a["device_kind"],
        "count": a["n_devices"]}}), flush=True)


if __name__ == "__main__":
    main()
