#!/usr/bin/env python
# Diagnostic sidecar (not part of the framework): bulk-compaction
# throughput at BASELINE scale, reusing the bench's fixture builder
# (bench.build_compact_store) so the synthetic-SST layout lives in ONE
# place. CPU-only by default; PEGPROF_DEVICE=accel places eval on the
# ambient accelerator. PEGPROF_PROFILE=1 wraps the pass in cProfile.
"""`--mesh` is a fast no-accelerator selftest: over a forced
8-CPU-device mesh it proves one whole-table dispatch serves every
partition's drop masks byte-identically to the host filter stage, that
a wedged watchdog degrades to host filtering, and exits 0 on PASS —
CI-drivable without hardware."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if "--mesh" in sys.argv[1:]:
    # keep the selftest off any real accelerator, and give the mesh its
    # 8 virtual CPU devices BEFORE jax initializes
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    import shutil
    import tempfile

    import numpy as np

    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.client.client import PegasusClient
    from pegasus_tpu.client.table import Table
    from pegasus_tpu.ops import placement
    from pegasus_tpu.ops.compaction import (
        compaction_eval_drain,
        compaction_eval_submit,
    )
    from pegasus_tpu.parallel.mesh_resident import MESH_SERVING
    from pegasus_tpu.utils.flags import FLAGS

    with tempfile.TemporaryDirectory(prefix="pegmeshcompact") as tmp:
        FLAGS.set("pegasus.storage", "block_codec", "none")
        table = Table(os.path.join(tmp, "t"), partition_count=8)
        c = PegasusClient(table)
        for i in range(1600):
            rc = c.set(b"hk%03d" % (i % 40), b"s%05d" % i,
                       b"v%05d" % i,
                       ttl_seconds=7 if i % 3 == 0 else 0)
            assert rc == 0
        table.flush_all()
        for s in table.partitions.values():
            s.engine.flush()
            s.engine.manual_compact()
        now = epoch_now() + 3600
        placement.mesh_compact_pays = lambda *_a, **_k: True
        for s in table.partitions.values():
            MESH_SERVING.attach(s)
        served = 0
        for pidx, s in sorted(table.partitions.items()):
            lsm = s.engine.lsm
            entries = lsm.bulk_compact_entries()
            masks = MESH_SERVING.try_compact_masks(
                lsm, entries, now, 0, pidx, s.partition_version,
                False, None, want_ets=False, n_windows=1)
            assert masks is not None, f"p{pidx} declined"
            served += 1
            blocks = [((run, i), run.read_block(i), pidx)
                      for run, i, _bm in entries]
            pend = compaction_eval_submit(
                blocks, now, 0, s.partition_version, False,
                operations=None, eval_device=None, want_ets=False)
            host = {tag: drop for tag, drop, _e in
                    compaction_eval_drain(pend, want_ets=False)}
            for run, i, _bm in entries:
                assert np.array_equal(
                    np.asarray(host[(run, i)], bool),
                    np.asarray(masks[(run, i)][0], bool)), \
                    f"p{pidx} block {i} mask mismatch"
        st = MESH_SERVING.status()
        assert st["compact_dispatches"] == 1, st
        assert st["compact_mask_serves"] == 8, st
        # wedged leg: an impossible deadline must decline, not hang
        MESH_SERVING.watchdog.deadline_s = 1e-9
        MESH_SERVING._compact_cache.clear()
        got = MESH_SERVING.try_compact_masks(
            lsm, entries, now + 1, 0, pidx, s.partition_version,
            False, None, want_ets=False, n_windows=1)
        assert got is None, "wedged watchdog still served masks"
        st = MESH_SERVING.status()
        assert st["compact_mesh_fallback_count"] >= 1, st
        MESH_SERVING.reset()
        table.close()
        print(f"mesh compact selftest: PASS (1 dispatch served "
              f"{served}/8 partitions host-identically; wedged "
              f"watchdog declined to host)")
        sys.exit(0)

if os.environ.get("PEGPROF_DEVICE", "cpu") == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"  # before bench imports jax

import bench as B  # noqa: E402

GB = float(os.environ.get("PEGPROF_GB", "1"))
EXPIRED = float(os.environ.get("PEGPROF_EXPIRED", "0.3"))
PARTS = int(os.environ.get("PEGPROF_PARTS", "1"))


def main() -> None:
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    n_records = int(GB * 1e9 / 145)
    with tempfile.TemporaryDirectory(prefix="pegprof",
                                     dir=os.environ.get("PEGPROF_TMP")
                                     ) as tmp:
        t0 = time.perf_counter()
        engines = B.build_compact_store(tmp, n_records, EXPIRED, PARTS, 7)
        size = B._store_bytes(engines)
        print(f"built {n_records} records ({size/1e9:.2f} GB, "
              f"{PARTS} parts) in {time.perf_counter()-t0:.1f}s",
              flush=True)
        pr = None
        if os.environ.get("PEGPROF_PROFILE") == "1":
            import cProfile
            pr = cProfile.Profile()
            pr.enable()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=min(4, PARTS)) as ex:
            for f in [ex.submit(lambda e: e.manual_compact(), e)
                      for e in engines]:
                f.result()
        secs = time.perf_counter() - t0
        if pr is not None:
            import pstats
            pr.disable()
            pstats.Stats(pr).sort_stats("cumulative").print_stats(30)
        size2 = B._store_bytes(engines)
        print(f"compact: {secs:.2f}s -> {size/1e9/secs:.3f} GB/s "
              f"({size/1e9:.2f} GB -> {size2/1e9:.2f} GB)", flush=True)
        for e in engines:
            e.close()


if __name__ == "__main__":
    main()
