"""How a configuration's table is made: the loader it names
(`loader`, benchmarks/loaders/), the envs the table is created with
(`table_envs`) and the clock of the set-up's compaction
(`load_compaction_age_s`). The default loader makes exactly the rows it
always made; rows that expired after the set-up's compaction clock stay
on every replica and out of every answer; the envs are on every replica
before the first row; a loaded row that expires inside the run is a
harness error. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_table_loader.py -q -p no:cacheprovider
"""

import hashlib
import json
import math
import os
import sys
import tempfile
import time
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.generator import load_json, op_module  # noqa: E402
from benchmarks.harness import Cluster, run_cell, table_rows  # noqa: E402
from benchmarks.reference import (Model, epoch_now, hashkey_of,  # noqa: E402
                                  make_records, partition_of, sortkey_of)
from pegasus_tpu.base.key_schema import (generate_key,  # noqa: E402
                                         key_hash_parts)

# the configurations of the six cells that were there before loaders
CELL_CONFIGS = ("ycsb_p64r3", "ycsb_p4r1", "ycsb_rules_p64r3",
                "ycsb_a_p64r3", "ycsb_prefixscan_p64r3", "ycsb_f_p64r3")
LOADER_SEEDS = (2_147_483_659, 4_000_000_037)
EXPIRED_SHARE = 0.2


def _config(name: str, **changes) -> dict:
    return dict(load_json("configs", name), **changes)


def _digest(rows) -> str:
    h = hashlib.sha256()
    for hk, sk, value, ets in rows:
        h.update(b"%d:%s%d:%s%d:%s%d\n" % (len(hk), hk, len(sk), sk,
                                           len(value), value, ets))
    return h.hexdigest()


@pytest.mark.parametrize("name", CELL_CONFIGS)
@pytest.mark.parametrize("seed", LOADER_SEEDS)
def test_loader_default_is_make_records(name, seed):
    cfg = _config(name, records=2_000)
    now = epoch_now()
    want = _digest(make_records(seed, cfg["records"], cfg["fields"],
                                cfg["field_length"], cfg["expired_share"],
                                now))
    assert _digest(table_rows(cfg, seed, now)) == want


def _ttl_rows(seed, config, now):
    """YCSB's records with EXPIRED_SHARE of the rows 10-50 s past their
    expiry at `now`, the others without one."""
    rng = np.random.default_rng([seed, 0x77])
    n = config["records"] * config["fields"]
    expired = rng.random(n) < EXPIRED_SHARE
    ago = rng.integers(10, 51, size=n)
    values = rng.integers(32, 127, size=(n, config["field_length"]),
                          dtype=np.uint8)
    i = 0
    for r in range(config["records"]):
        for j in range(config["fields"]):
            ets = now - int(ago[i]) if expired[i] else 0
            yield hashkey_of(r), sortkey_of(j), values[i].tobytes(), ets
            i += 1


@pytest.fixture
def loader_named(monkeypatch):
    """Registers a loader under benchmarks.loaders.<name>, as a file of
    the package would be found, for the test alone."""
    def register(name, rows):
        monkeypatch.setitem(sys.modules, f"benchmarks.loaders.{name}",
                            types.SimpleNamespace(rows=rows))
    return register


def _expired_on_disk(replica, now) -> set:
    return {key for key, value, ets in replica.server.engine.iterate()
            if value is not None and 0 < ets <= now}


def _loaded(config, seed):
    """A cluster of the config, loaded; its load clock; its reference."""
    workdir = tempfile.TemporaryDirectory(prefix="loader_test_")
    cluster = Cluster(config, workdir.name)
    now = epoch_now()
    try:
        cluster.load(seed, now, None)
    except BaseException:
        cluster.close()
        workdir.cleanup()
        raise
    model = Model(config["partitions"])
    expired = [set() for _ in range(config["partitions"])]
    for hk, sk, value, ets in table_rows(config, seed, now):
        model.put(hk, sk, value, ets)
        if ets:
            expired[partition_of(hk, config["partitions"])].add(
                generate_key(hk, sk))
    return workdir, cluster, now, model, expired


def test_loader_ttl_rows_stay_on_disk_and_out_of_answers(loader_named):
    loader_named("ttl_on_disk", _ttl_rows)
    seed = 3_000_000_019
    cfg = _config("ycsb_p64r3", partitions=8, records=500,
                  loader="ttl_on_disk", load_compaction_age_s=3_600)
    workdir, cluster, now, model, expired = _loaded(cfg, seed)
    try:
        assert sum(map(len, expired)) > 0.15 * 5_000
        for p, replicas in enumerate(cluster.replicas_of):
            assert len(replicas) == 3
            for r in replicas:
                assert _expired_on_disk(r, now) == expired[p]
        assert cluster.next_expiry == math.inf
        ctx = {"n_records": cfg["records"], "fields": cfg["fields"],
               "field_length": cfg["field_length"],
               "n_partitions": cfg["partitions"],
               "next_record": cfg["records"]}
        rng = np.random.default_rng(seed)
        scan = op_module("scan")
        scans = scan.draw(rng, rng, 300, {
            "key": {"dist": "uniform"},
            "length": {"dist": "uniform", "min": 1, "max": 100}}, ctx)
        get = op_module("get")
        gets = []
        for hk, sk, _value, _ets in table_rows(cfg, seed, now):
            ph = key_hash_parts(hk, sk)
            gets.append((hk, sk, generate_key(hk, sk), ph,
                         ph % cfg["partitions"]))
        verdicts = []
        for mod, ops in ((scan, scans), (get, gets)):
            for off in range(0, len(ops), 500):
                batch = ops[off:off + 500]
                replies = mod.send(cluster.client, batch, ctx)
                at = epoch_now()
                verdicts += [mod.check(model, args, reply, at)
                             for args, (reply, _took) in zip(batch, replies)]
        assert len(verdicts) == 300 + 5_000
        assert [v for v in verdicts if v is not None] == []
    finally:
        cluster.close()
        workdir.cleanup()

    # the engine's own clock, as every cell before: gone in the set-up
    del cfg["load_compaction_age_s"]
    workdir, cluster, now, _model, expired = _loaded(cfg, seed)
    try:
        assert all(_expired_on_disk(r, now) == set()
                   for replicas in cluster.replicas_of for r in replicas)
    finally:
        cluster.close()
        workdir.cleanup()


def test_loader_table_envs_reach_every_replica_before_the_first_row(
        loader_named):
    envs = {"default_ttl": "2592000", "replica.slow_query_threshold_ms": "50"}
    cfg = _config("ycsb_p64r3", partitions=4, records=20, loader="env_probe",
                  table_envs=envs)
    seen = []

    def rows(seed, config, now):
        seen.append([(dict(r.server.app_envs), r.server._default_ttl)
                     for replicas in cluster.replicas_of for r in replicas])
        yield from _ttl_rows(seed, config, now)

    loader_named("env_probe", rows)
    with tempfile.TemporaryDirectory(prefix="loader_test_") as workdir:
        cluster = Cluster(cfg, workdir)
        try:
            cluster.load(3_000_000_023, epoch_now(), None)
        finally:
            cluster.close()
    assert len(seen) == 1 and len(seen[0]) == 4 * 3
    assert all(got == (envs, 2_592_000) for got in seen[0])


@pytest.mark.parametrize("expires_in_s,raises", [(1, True), (3_600, False)])
def test_loader_row_expiring_inside_the_run_is_a_harness_error(
        loader_named, expires_in_s, raises):
    def rows(seed, config, now):
        for n, (hk, sk, value, _ets) in enumerate(make_records(
                seed, config["records"], config["fields"],
                config["field_length"], 0.0, now)):
            yield hk, sk, value, now + expires_in_s if n == 7 else 0

    loader_named("expiring", rows)
    cfg = _config("ycsb_p4r1", records=400, loader="expiring")
    traffic = dict(load_json("traffic", "ycsb_c"), warmup_windows=8)
    run = lambda: run_cell("ycsb_c.p4r1", cfg, traffic,  # noqa: E731
                           3_000_000_041, 2.0, False, time.perf_counter())
    if raises:
        with pytest.raises(RuntimeError, match="inside the run"):
            run()
    else:
        res = run()
        assert res["correct"], json.dumps(res["checks"])
