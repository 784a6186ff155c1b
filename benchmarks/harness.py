"""One run of one cell: build the cluster, load, warm up, drive the
window, verify what the window received, reduce the metrics.

run.py (the chip) and rehearse.py (a CPU rehearsal at a tiny size) both
call run_cell. Nothing about a cell lives here: the deployment comes
from configs/<config>.json, the mix from traffic/<mix>.json, each op
kind from ops/<kind>.py, each per-layer metric from
layer_metrics/<metric>.json and its reader from readers/<reader>.py.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import importlib
import json
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

from benchmarks import faults as fault_mod
from benchmarks.generator import HERE, Schedule, op_module
from benchmarks.reference import (Model, epoch_now, make_records,
                                  partition_of)

_NULL = contextlib.nullcontext()
TRACE_PROBES = 4    # probe operations at the start of a traced slice


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


class CompileMeter:
    """Counts what JAX compiled and what the persistent cache served,
    from jax.monitoring's own events."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        if event == self._BACKEND:
            with self._lock:
                self.programs += 1
                self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"programs": self.programs, "compile_s": self.seconds,
                    "cache_hits": self.cache_hits}


class GcMeter:
    """Seconds the cyclic collector ran and its full collections, from
    gc.callbacks: the harness keeps every reply until the window has
    closed, so part of the collector's work is the harness's own."""

    def __init__(self):
        self.seconds, self.full, self._t = 0.0, 0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def workdir_bytes(path: str) -> int:
    """Bytes of the files under `path`, a store that may be live: the
    program's compaction threads unlink and rename its files under the
    store's lock, not under one the harness holds. A file or a
    directory that is gone by the time it is looked at counts 0, and
    the walk goes on."""
    total, pending = 0, [path]
    while pending:
        try:
            with os.scandir(pending.pop()) as entries:
                for entry in entries:
                    try:
                        if entry.is_dir(follow_symlinks=False):
                            pending.append(entry.path)
                        else:
                            total += entry.stat().st_size
                    except OSError:
                        pass
        except OSError:
            pass
    return total


def layer_metric_specs(cell: str) -> list:
    """Every layer_metrics/*.json whose cells include this one."""
    specs = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if cell in spec["workloads"]:
            specs.append(spec)
    return specs


def reader_module(name: str):
    return importlib.import_module(f"benchmarks.readers.{name}")


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * p // 100) - 1))]


class Cluster:
    """The deployment of a config file on SimCluster, loaded."""

    def __init__(self, config: dict, workdir: str):
        from pegasus_tpu.tools.cluster import SimCluster

        self.config = config
        self.sim = SimCluster(os.path.join(workdir, "sim"),
                              n_nodes=config["nodes"])
        try:
            self.app_id = self.sim.create_table(
                config["table"], partition_count=config["partitions"],
                replica_count=config["replicas"])
            self.client = self.sim.client(config["table"])
            self.client.refresh_config()
            gpids = [(self.app_id, p) for p in range(config["partitions"])]
            primaries = self.sim.primaries(self.app_id)
            self.primary_of = [self.sim.stubs[primaries[p]].get_replica(g)
                               for p, g in enumerate(gpids)]
            self.replicas_of = [
                [s.get_replica(g) for s in self.sim.stubs.values()
                 if s.get_replica(g) is not None] for g in gpids]
            short = [p for p, rs in enumerate(self.replicas_of)
                     if len(rs) != config["replicas"]]
            if short:
                raise RuntimeError(f"partitions {short} have fewer than "
                                   f"{config['replicas']} replicas")
        except BaseException:
            self.sim.close()
            raise

    def load(self, seed: int, now: int, fault) -> list:
        """The table through the primaries' 2PC in batches of 1000
        rows, then flush and compaction to L1 on every replica. Returns
        the hashkeys of the first and the last unexpired row of every
        partition (hashkeys are of one length and numbered upwards, so
        the store's key order is the order they are made in)."""
        from pegasus_tpu.base.key_schema import generate_key
        from pegasus_tpu.client.table import compact_partitions_parallel
        from pegasus_tpu.replica.mutation import WriteOp
        from pegasus_tpu.rpc.codec import OP_PUT

        c = self.config
        per_pidx = [[] for _ in range(c["partitions"])]
        first, last = {}, {}
        prev_hk = p = None
        for n, (hk, sk, value, ets) in enumerate(make_records(
                seed, c["records"], c["fields"], c["field_length"],
                c["expired_share"], now), 1):
            if hk != prev_hk:       # rows of one record come together
                prev_hk, p = hk, partition_of(hk, c["partitions"])
            if ets == 0:
                first.setdefault(p, hk)
                last[p] = hk
            if fault_mod.drops_loaded_row(fault, n):
                continue    # acknowledged to the reference, never stored
            per_pidx[p].append(
                WriteOp(OP_PUT, (generate_key(hk, sk), value, ets)))
        sent = sum(len(ops) for ops in per_pidx)
        acked = [0]

        def on_ack(results):
            acked[0] += sum(1 for r in results if r == 0)

        for p, ops in enumerate(per_pidx):
            for off in range(0, len(ops), 1000):
                self.primary_of[p].client_write(ops[off:off + 1000], on_ack)
                self.sim.loop.run_until_idle()
        if acked[0] != sent:
            raise RuntimeError(f"{acked[0]} of {sent} loaded rows were acked")
        # a secondary applies a write when it hears of the commit: the
        # group-check timer tells it
        self.sim.step(rounds=2)
        servers = [r.server for rs in self.replicas_of for r in rs]
        for srv in servers:
            srv.flush()
        compact_partitions_parallel(servers)
        return [hk for p in sorted(first) for hk in (first[p], last[p])]

    def decree_spread(self) -> int:
        """Partitions whose replicas disagree on the last committed
        decree, after the timers have told the secondaries."""
        self.sim.loop.run_until_idle()
        self.sim.step(rounds=2)
        return sum(1 for rs in self.replicas_of
                   if len({r.last_committed_decree for r in rs}) != 1)

    def close(self) -> None:
        self.sim.close()


def run_cell(cell: str, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             fault: str = None) -> dict:
    """One run. Returns the numbers run.py prints; raises on a harness
    error. `t_start` is the process's start on time.perf_counter()."""
    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    from pegasus_tpu import native

    if not native.available():
        raise RuntimeError("the native library did not build")
    meter = CompileMeter()
    ops = list(traffic["ops"])
    # a traced run sends the mix's `trace_probe` operations as well,
    # outside the mix: compared like any other, counted in no metric
    probe = traffic.get("trace_probe") if trace else None
    if probe:
        ops.append(dict(probe, role="probe"))
    mods = [op_module(o["kind"]) for o in ops]
    kinds = [o["kind"] for o in ops]
    roles = [o["role"] for o in ops]
    specs = layer_metric_specs(cell)
    readers = [reader_module(s["reader"]) for s in specs]
    with contextlib.ExitStack() as stack:
        workdir = stack.enter_context(
            tempfile.TemporaryDirectory(prefix="pegasus_bench_"))
        cluster = Cluster(config, workdir)
        stack.callback(cluster.close)
        load_now = epoch_now()
        edges = cluster.load(seed, load_now, fault)
        log(f"loaded {config['records']} records into "
            f"{config['partitions']} partitions x {config['replicas']} "
            f"replicas")
        fault_mod.plant_in_cluster(cluster.sim, fault)
        client = fault_mod.wrap_client(cluster.client, fault)
        ctx = {"n_records": config["records"], "fields": config["fields"],
               "field_length": config["field_length"],
               "n_partitions": config["partitions"],
               "next_record": config["records"]}
        schedule = Schedule(traffic, ctx, seed)
        record = []     # (timed, kind index, batch, results, when) per call

        def drive(window, timed, span):
            for k, batch in enumerate(window):
                if not batch:
                    continue
                with span(f"bench.send.{kinds[k]}"):
                    try:
                        results = mods[k].send(client, batch, ctx)
                    except Exception as exc:  # the ops count as failed
                        log(f"{kinds[k]} call failed: "
                            f"{type(exc).__name__}: {exc}"[:500])
                        results = [(None, 0.0)] * len(batch)
                record.append((timed, k, batch, results, time.perf_counter()))

        def probes(n):
            """A window of n probe operations and nothing else."""
            rng = np.random.default_rng([seed, 0x9e0be, len(record)])
            return [[]] * len(traffic["ops"]) + [
                mods[-1].draw(rng, rng, n, probe, ctx)]

        no_span = lambda name: _NULL  # noqa: E731
        # warm-up: the mix's own windows; the last 200 (or all, where
        # there are fewer) are clocked, and half as many windows again
        # as that rate would send in the window are drawn before it
        n_warm = traffic["warmup_windows"]
        clocked = min(n_warm, 200)
        schedule.draw(n_warm)
        for w in range(n_warm):
            if w == n_warm - clocked:
                t_clock = time.perf_counter()
            drive(schedule.next_window(), False, no_span)
        rate = clocked / (time.perf_counter() - t_clock)
        if probe:
            # the records at the two ends of every partition (a
            # partition's last block is shorter than the others, so a
            # device program over it has a shape of its own), then as
            # many as the slice will send, eight times over
            drive([[]] * len(traffic["ops"])
                  + [mods[-1].warm(edges, probe, ctx)], False, no_span)
            drive(probes(8 * TRACE_PROBES), False, no_span)
        schedule.draw(math.ceil(1.5 * rate * seconds))
        cluster.sim.step()
        before = [r.begin(s) for r, s in zip(readers, specs)]
        compiled0 = meter.snapshot()
        gc_meter = GcMeter()
        stack.callback(gc_meter.close)
        span = TraceAnnotation if trace else no_span
        trace_dir = os.path.join(workdir, "trace")
        slice_at, slice_len = traffic["trace_slice_s"]
        slice_cm, tracing = None, "not_started" if trace else "off"
        interval = cluster.sim.beacon_interval

        setup_s = time.perf_counter() - t_start
        t0 = last_timers = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if tracing == "not_started" and now - t0 >= slice_at:
                opts = ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                slice_cm = TraceAnnotation("bench.slice")
                slice_cm.__enter__()
                tracing, slice_t0 = "on", now
                if probe:
                    drive(probes(TRACE_PROBES), False, span)
            elif tracing == "on" and now - slice_t0 >= slice_len:
                slice_cm.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = "done"
            drive(schedule.next_window(), True,
                  span if tracing == "on" else no_span)
            if time.perf_counter() - last_timers >= interval:
                # the sim has no timer thread: beacons, group check and
                # config sync fire here, as often as real time would
                with (span("bench.timers") if tracing == "on" else _NULL):
                    cluster.sim.step()
                last_timers = time.perf_counter()
        window_s = time.perf_counter() - t0
        gc_in_window = (gc_meter.seconds, gc_meter.full)
        if tracing == "on":
            slice_cm.__exit__(None, None, None)
            jax.profiler.stop_trace()
        compiled_in_window = (meter.snapshot()["programs"]
                              - compiled0["programs"])
        if compiled_in_window:
            log(f"WARNING: {compiled_in_window} programs compiled inside the "
                f"window: the warm-up missed a shape")
        peak = memory_peak_bytes()
        on_disk = workdir_bytes(workdir)

        # ---- what the window received, against the reference -------------
        # the reference table is made here, from the seed, and not
        # before the window: a million rows on the harness's heap would
        # be the collector's to walk while the window is timed
        t_verify = time.perf_counter()
        spread = cluster.decree_spread()
        model = Model(config["partitions"])
        for row in make_records(seed, config["records"], config["fields"],
                                config["field_length"],
                                config["expired_share"], load_now):
            model.put(*row)
        now_ts = epoch_now()
        attempted = failed = wrong = 0
        latencies = {"read": [], "write": []}
        done_by_kind = dict.fromkeys(kinds[:len(traffic["ops"])], 0)
        acked_rows = []
        first_wrong = None
        quarters = [0, 0, 0, 0]
        for timed, k, batch, results, at in record:
            for args, (reply, took) in zip(batch, results):
                if timed:
                    attempted += 1
                if reply is None:
                    failed += 1
                    continue
                why = mods[k].check(model, args, reply, now_ts)
                if why is not None:
                    wrong += 1
                    first_wrong = first_wrong or why
                    continue
                if timed:
                    latencies[roles[k]].append(took)
                    done_by_kind[kinds[k]] += 1
                    quarters[min(3, int(4 * (at - t0) / window_s))] += 1
            # a call's writes land after its reads were answered
            for args, (reply, _took) in zip(batch, results):
                if reply is not None:
                    mods[k].apply(model, args)
                    acked_rows += mods[k].readback(args)
        missing = _read_back(cluster.client, acked_rows, config)
        log(f"verified {attempted} timed operations and "
            f"{len(acked_rows)} acknowledged rows in "
            f"{time.perf_counter() - t_verify:.1f}s")
        if first_wrong:
            log(f"first wrong answer: {first_wrong}")

        done = sum(done_by_kind.values())
        p95_ms = {role: 1000.0 * percentile(v, 95)
                  for role, v in latencies.items() if v}
        run = {"ops": done, "by_kind": done_by_kind, "window_s": window_s,
               "memory_peak_bytes": peak, "latency_p95_ms": p95_ms,
               "trace": None}
        if trace:
            from benchmarks.trace_reduce import load_xplane, reduce_trace

            run["trace"] = reduce_trace(load_xplane(trace_dir),
                                        n_chips=config["chips"])
        end_to_end = {"setup_s": (setup_s, "s"),
                      "throughput_ops_s": (done / window_s, "ops/s")}
        for role, value in p95_ms.items():
            end_to_end[f"{role}_p95_ms"] = (value, "ms")
        per_layer = {}
        for r, s, b in zip(readers, specs, before):
            value = r.read(s, b, run)
            if value is not None:
                per_layer[s["name"]] = (value, s["unit"])
        # exact comparisons, so every limit is 0; a cell is held to the
        # numbers its deployment and mix can move
        checks = {"wrong_answers": (wrong, 0), "failed_ops": (failed, 0)}
        if "write" in roles:
            checks["missing_readbacks"] = (missing, 0)
        if config["replicas"] > 1:
            checks["replica_decree_spread"] = (spread, 0)
        return {
            "correct": all(v <= lim for v, lim in checks.values()),
            "attempted": attempted, "failed": failed + wrong,
            "end_to_end": end_to_end, "per_layer": per_layer,
            "trace": run["trace"], "memory_peak_bytes": peak,
            "checks": checks,
            "info": {"window_s": window_s, "ops_by_kind": done_by_kind,
                     "compiled_in_window": compiled_in_window,
                     "compiled_in_setup": compiled0,
                     "warmup_windows_per_s": rate,
                     "chunks_drawn_in_window": schedule.late_chunks,
                     "collector_s_in_window": gc_in_window[0],
                     "full_collections_in_window": gc_in_window[1],
                     "workdir_bytes_after_window": on_disk,
                     "ops_s_by_quarter": [4 * q / window_s for q in quarters],
                     "latency_samples": {r: len(v)
                                         for r, v in latencies.items()}}}


def _read_back(client, rows, config) -> int:
    """Every acknowledged row of the window, read from the primary
    after it: how many are missing or differ."""
    from pegasus_tpu.base.key_schema import generate_key, key_hash_parts

    missing = 0
    for off in range(0, len(rows), 320):
        chunk = rows[off:off + 320]
        groups = {}
        for hk, sk, _value in chunk:
            ph = key_hash_parts(hk, sk)
            groups.setdefault(ph % config["partitions"], []).append(
                ("get", generate_key(hk, sk), ph))
        replies = client.point_read_multi(groups)
        cursor = dict.fromkeys(groups, 0)
        for hk, sk, value in chunk:
            p = key_hash_parts(hk, sk) % config["partitions"]
            err, got = replies[p][cursor[p]]
            cursor[p] += 1
            missing += not (err == 0 and got == value)
    return missing
