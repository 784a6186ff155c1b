#!/usr/bin/env python3
"""Split a benchmark config's load phase (`benchmarks/harness.py`
`Cluster.load`, most of `setup_s`) into make records / 2PC / flush /
compaction, on the harness's own `Cluster` at the config's size.

    python3 profile_setup.py <root> <config> [records]

<root>: the checkout to import (`.`, or a `git archive` of the parent
unpacked under a git-ignored directory), <config>: a name under
`benchmarks/configs/` (`ycsb_p64r3`, `ycsb_p4r1`). SPLIT_PARALLEL=<n>
overrides `compact_partitions_parallel`'s pool. Prints one JSON line
with the seconds of each step and the engines' compaction counters.
Chip or CPU (a CPU run gives structure and counts, no rate). The
process's first JAX call (the TPU client's start, ~7.5 s on the chip)
falls into the compaction step. PR 28's tool; not part of the
benchmark, and the driver never runs it."""
import json
import os
import sys
import tempfile
import time


def main() -> None:
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    os.chdir(root)

    from benchmarks.harness import Cluster, make_records
    from pegasus_tpu.base.value_schema import epoch_now
    from pegasus_tpu.client import table as table_mod
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.utils.metrics import METRICS

    with open(os.path.join(root, "benchmarks", "configs", sys.argv[2] + ".json")) as f:
        config = json.load(f)
    if len(sys.argv) > 3:
        config["records"] = int(sys.argv[3])

    spent = {"flush": 0.0, "compact": 0.0}
    real_flush = PartitionServer.flush
    real_compact = table_mod.compact_partitions_parallel


    def flush(self, *a, **kw):
        t0 = time.perf_counter()
        try:
            return real_flush(self, *a, **kw)
        finally:
            spent["flush"] += time.perf_counter() - t0


    def compact(servers, *a, **kw):
        if os.environ.get("SPLIT_PARALLEL"):
            kw["parallel"] = int(os.environ["SPLIT_PARALLEL"])
        t0 = time.perf_counter()
        try:
            return real_compact(servers, *a, **kw)
        finally:
            spent["compact"] += time.perf_counter() - t0


    PartitionServer.flush = flush
    table_mod.compact_partitions_parallel = compact

    import jax

    t0 = time.perf_counter()
    now = epoch_now()
    n = sum(1 for _ in make_records(7, config["records"], config["fields"],
                                    config["field_length"],
                                    config["expired_share"], now))
    make_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="pegasus_split_") as wd:
        t0 = time.perf_counter()
        cluster = Cluster(config, wd)
        cluster_s = time.perf_counter() - t0
        try:
            t0 = time.perf_counter()
            cluster.load(7, now, None)
            load_s = time.perf_counter() - t0
            eng = {}
            for e in METRICS.snapshot():
                if e["type"] == "engine":
                    for k, v in e["metrics"].items():
                        if k.startswith("compact_") and "value" in v:
                            eng[k] = eng.get(k, 0) + v["value"]
            blocks = [len(r.blocks) for rs in cluster.replicas_of
                      for r in rs[:1] for r in r.server.engine.lsm.l1_runs]
        finally:
            cluster.close()
    # the compaction's own flush (memtable empty: nothing) is inside compact
    print(json.dumps({
        "root": sys.argv[1], "config": sys.argv[2], "records": config["records"],
        "platform": jax.devices()[0].platform, "rows": n,
        "make_records_s": round(make_s, 3), "cluster_s": round(cluster_s, 3),
        "load_s": round(load_s, 3), "flush_s": round(spent["flush"], 3),
        "compact_s": round(spent["compact"], 3),
        "two_pc_s": round(load_s - spent["flush"] - spent["compact"] - make_s, 3),
        "engine": eng, "l1_blocks_primary": blocks[:8]}))


if __name__ == "__main__":
    main()
