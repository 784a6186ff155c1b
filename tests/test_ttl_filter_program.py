"""The TTL filter of a rule-less compaction pass on the device
(ops/compaction.ttl_filter_window), the table env `default_ttl` on the
write path, and the read-side count of rows skipped as expired.

The program is held to encoded_drop_mask (its host twin) and to a plain
per-row reference written from check_if_ts_expired and the crc64 routing
hash; a store compacted under each placement must come out as the same
bytes, and a put without a TTL into a `default_ttl` table must be stored
with now + default_ttl on every replica.
"""

import hashlib
import os
import shutil

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key, key_hash
from pegasus_tpu.base.value_schema import (
    check_if_ts_expired,
    epoch_now,
    extract_expire_ts,
    generate_value,
)
from pegasus_tpu.ops import compaction, placement
from pegasus_tpu.ops.compaction import (
    encoded_drop_mask,
    ttl_filter_drain,
    ttl_filter_submit,
    ttl_filter_window,
)
from pegasus_tpu.storage import compact_pipeline
from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
from pegasus_tpu.storage.wal import OP_PUT
from pegasus_tpu.utils.metrics import METRICS

NOW = 300_000_000
DAY = 86_400


def _rows(seed: int, n: int, now: int):
    """(key, expire_ts) sorted by key: TTLs at and around `now` (0,
    now, now +- 1) and spread a day either way."""
    rng = np.random.default_rng(seed)
    keys = sorted({generate_key(b"h%07d" % int(h), b"s%d" % int(s))
                   for h, s in zip(rng.integers(0, 10 ** 6, n),
                                   rng.integers(0, 10, n))})
    edges = np.array([0, now, now - 1, now + 1], dtype=np.int64)
    spread = rng.integers(now - DAY, now + DAY, len(keys))
    pick = rng.integers(0, 6, len(keys))
    ets = np.where(pick < 4, edges[np.minimum(pick, 3)], spread)
    return list(zip(keys, ets.tolist()))


def _store(path: str, rows, block_capacity: int = 64,
           values_carry_expire_header: bool = False) -> StorageEngine:
    eng = StorageEngine(path, block_capacity=block_capacity,
                        values_carry_expire_header=values_carry_expire_header)
    items = [WriteBatchItem(OP_PUT, k, generate_value(1, b"v" + k, e), e)
             for k, e in rows]
    for d, off in enumerate(range(0, len(items), 500), 1):
        eng.write_batch(items[off:off + 500], decree=d)
    eng.flush()
    return eng


def _reference(rows, now, default_ttl, pidx, pv, validate):
    """Per row, as KeyWithTTLCompactionFilter::Filter without rules:
    the default-TTL rewrite, then expiry and the stale-split check."""
    drop, new_ets = [], []
    for key, ets in rows:
        if default_ttl and ets == 0:
            ets = now + default_ttl
        stale = validate and (key_hash(key) & pv) != pidx
        drop.append(check_if_ts_expired(now, ets) or stale)
        new_ets.append(ets)
    return np.array(drop), np.array(new_ets, dtype=np.uint32)


@pytest.mark.parametrize("seed", [7, 2_147_483_659])
@pytest.mark.parametrize("default_ttl", [0, 3 * DAY])
@pytest.mark.parametrize("validate", [False, True])
def test_program_equals_host_twin_and_per_row_reference(tmp_path, seed,
                                                        default_ttl,
                                                        validate):
    rows = _rows(seed, 700, NOW)
    eng = _store(str(tmp_path / "e"), rows)
    (table,) = eng.lsm.l0
    assert table.codec is not None
    pidx, pv = 5, 7
    encoded = [(i, table.read_block_encoded(i))
               for i in range(len(table.blocks))]
    decoded = [(i, table.read_block(i)) for i in range(len(table.blocks))]
    want_drop, want_ets = _reference(rows, NOW, default_ttl, pidx, pv,
                                     validate)
    for blocks in (encoded, decoded):
        got = list(ttl_filter_drain(ttl_filter_submit(
            blocks, NOW, default_ttl, pidx, pv, validate,
            want_ets=bool(default_ttl))))
        assert [tag for tag, _d, _e in got] == [i for i, _b in blocks]
        for (_i, blk), (_t, drop, new_ets) in zip(blocks, got):
            host_drop, host_ets = encoded_drop_mask(
                blk, NOW, default_ttl, pidx, pv, validate,
                want_ets=bool(default_ttl))
            np.testing.assert_array_equal(drop, host_drop)
            if default_ttl:
                np.testing.assert_array_equal(new_ets, host_ets)
            else:
                assert new_ets is None and host_ets is None
        drop = np.concatenate([d for _t, d, _e in got])
        np.testing.assert_array_equal(drop, want_drop)
        if default_ttl:
            np.testing.assert_array_equal(
                np.concatenate([e for _t, _d, e in got]), want_ets)
    # both outcomes occur, and the edges fall where the reference says
    assert 0 < want_drop.sum() < len(rows)
    eng.close()


def test_program_pads_to_a_bucket_and_ignores_the_padding():
    """Padding rows (expire_ts 0, hash_lo 0) never drop, whatever the
    stale-split check would say of them; the scalars wrap like the
    host twin's uint32 arithmetic."""
    ets = np.zeros(4096, dtype=np.uint32)
    ets[:3] = (NOW, NOW + 1, 0)
    hash_lo = np.full(4096, 3, dtype=np.uint32)
    packed, new_ets = ttl_filter_window(
        ets, hash_lo, np.int32(3), np.uint32(NOW), np.uint32(60),
        np.uint32(1), np.uint32(1), validate_hash=True, want_ets=True)
    drop = np.unpackbits(np.asarray(packed)).astype(bool)
    assert drop[:3].tolist() == [True, False, False]
    assert not drop[3:].any()
    assert np.asarray(new_ets)[:3].tolist() == [NOW, NOW + 1, NOW + 60]
    packed, = ttl_filter_window(
        ets, hash_lo[:1], np.int32(3), np.uint32(NOW), np.uint32(0),
        np.uint32(0), np.uint32(0), validate_hash=False, want_ets=False)
    assert np.unpackbits(np.asarray(packed))[:3].tolist() == [1, 0, 0]


def _colocated_link(monkeypatch) -> None:
    """The placement probe as a device on this host's own bus would
    read it: the policy then places the TTL program on the ambient
    default device, which the CPU backend stands in for."""
    import jax

    monkeypatch.setattr(placement, "_PROBE", placement.LinkProbe(
        1e-4, 10.0, 10.0, jax.devices()[0]))
    assert placement.placement_verdict("ttl") == "device"


def _digest(eng: StorageEngine) -> str:
    h = hashlib.sha256()
    for k, v, e in eng.iterate():
        h.update(k + v + b"%d" % e)
    sst = os.path.join(eng.data_dir, "sst")
    for name in sorted(os.listdir(sst)):
        if name.endswith(".sst"):
            with open(os.path.join(sst, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _engine_counts(eng: StorageEngine) -> dict:
    names = ("compact_rows_dropped_ttl", "compact_rows_ttl_rewritten",
             "compact_ttl_device_blocks", "compact_ttl_host_blocks")
    for ent in METRICS.snapshot():
        if ent["type"] == "engine" and ent["id"] == eng.data_dir:
            return {n: ent["metrics"][n]["value"] for n in names}
    raise AssertionError(f"no engine entity for {eng.data_dir}")


@pytest.mark.parametrize("window", [128, 4])
@pytest.mark.parametrize("default_ttl", [0, 3 * DAY])
def test_placements_write_the_same_bytes(tmp_path, monkeypatch, window,
                                         default_ttl):
    """A store compacted twice (its L0 into L1, then the L1), once with
    the filter on the host and once with the program forced on (the CPU
    backend stands in for the device): the same files, the same counts
    of dropped and rewritten rows, and the blocks counted under the
    placement that filtered them. A window of 4 blocks hands the store
    to the stage threads where the host has the cores."""
    import pegasus_tpu.storage.engine as engine_mod

    monkeypatch.setattr(engine_mod, "epoch_now", lambda: NOW)
    monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", window)
    rows = _rows(11, 1500, NOW)
    src = str(tmp_path / "src")
    _store(src, rows, values_carry_expire_header=True).close()
    out = {}
    for verdict in ("host-XLA", "device"):
        if verdict == "device":
            _colocated_link(monkeypatch)
        d = str(tmp_path / verdict)
        shutil.copytree(src, d)
        eng = StorageEngine(d, block_capacity=64,
                            values_carry_expire_header=True)
        for _ in range(2):
            eng.manual_compact(default_ttl=default_ttl, pidx=3,
                               partition_version=3, validate_hash=True,
                               now=NOW)
        out[verdict] = (_digest(eng), _engine_counts(eng),
                        [(k, extract_expire_ts(1, v), e)
                         for k, v, e in eng.iterate()])
        eng.close()
    (host_dig, host_n, host_rows), (dev_dig, dev_n, dev_rows) = (
        out["host-XLA"], out["device"])
    assert dev_dig == host_dig
    assert dev_rows == host_rows
    # every row's value header carries the TTL its column does
    assert all(h == e for _k, h, e in dev_rows)
    blocks = host_n.pop("compact_ttl_host_blocks")
    assert host_n.pop("compact_ttl_device_blocks") == 0
    assert dev_n.pop("compact_ttl_host_blocks") == 0
    assert dev_n.pop("compact_ttl_device_blocks") == blocks > 0
    assert dev_n == host_n
    assert host_n["compact_rows_dropped_ttl"] > 0
    assert bool(host_n["compact_rows_ttl_rewritten"]) == bool(default_ttl)


def test_warm_compiles_every_bucket_a_later_pass_uses(tmp_path,
                                                      monkeypatch):
    """With the program placed on the device, the warm-up compiles the
    buckets of the store's windows, every smaller one and the next: the
    pass, over rows written after the warm-up that carry its window
    into the next bucket, and a later pass over what the first left,
    compile nothing."""
    _colocated_link(monkeypatch)
    rows = _rows(23, 4000, NOW)
    eng = _store(str(tmp_path / "e"), rows, block_capacity=128)
    assert 2048 < len(rows) <= 4096        # one window, bucket 4096
    eng.warm_manual_compact(default_ttl=DAY, pidx=0, partition_version=0,
                            validate_hash=True)
    compiled = ttl_filter_window._cache_size()
    more = [WriteBatchItem(OP_PUT, generate_key(b"z%05d" % i, b"s"),
                           generate_value(1, b"v", 0), 0)
            for i in range(4200 - len(rows) + 200)]
    eng.write_batch(more, decree=100)
    eng.flush()
    for _ in range(2):
        eng.manual_compact(default_ttl=DAY, pidx=0, partition_version=0,
                           validate_hash=True, now=NOW)
    assert ttl_filter_window._cache_size() == compiled
    eng.close()


def test_host_verdict_keeps_the_host_twin(tmp_path, monkeypatch):
    """On the CPU the policy says host-XLA: no program is dispatched."""
    calls = []
    monkeypatch.setattr(compaction, "ttl_filter_submit",
                        lambda *a, **k: calls.append(a))
    assert placement.placement_verdict("ttl") == "host-XLA"
    eng = _store(str(tmp_path / "e"), _rows(5, 300, NOW))
    eng.manual_compact(now=NOW)
    eng.manual_compact(now=NOW)
    assert calls == []
    eng.close()


# ---- default_ttl on the write path, on every replica -------------------

def _cluster(tmp_path, envs):
    from pegasus_tpu.tools.cluster import SimCluster

    sim = SimCluster(str(tmp_path / "sim"), n_nodes=3)
    app_id = sim.create_table("t", partition_count=4, replica_count=3,
                              envs=envs)
    client = sim.client("t")
    client.refresh_config()
    return sim, app_id, client


def _stored_ets(sim, app_id, hk, sk):
    """(column expire_ts, value-header expire_ts) on every replica."""
    from pegasus_tpu.base.key_schema import partition_index

    sim.step(rounds=2)      # the group check tells the secondaries
    gpid = (app_id, partition_index(hk, 4))
    out = []
    for stub in sim.stubs.values():
        r = stub.get_replica(gpid)
        if r is not None:
            value, ets = r.server.engine.get(generate_key(hk, sk))
            out.append((ets, extract_expire_ts(1, value)))
    assert len(out) == 3
    return out


def test_a_put_without_ttl_takes_the_tables_default_on_every_replica(
        tmp_path):
    from pegasus_tpu.server.types import CheckAndMutateRequest, Mutate

    default_ttl = 7 * DAY
    sim, app_id, client = _cluster(tmp_path,
                                   {"default_ttl": str(default_ttl)})
    try:
        before = epoch_now()
        assert client.set(b"hk1", b"a", b"v") == 0
        assert client.multi_set(b"hk2", [(b"a", b"v"), (b"b", b"w")]) == 0
        assert client.incr(b"hk3", b"n", 5).error == 0
        assert client.check_and_set(b"hk4", b"c", 0, b"", b"c",
                                    b"x").error == 0
        assert client.check_and_mutate(b"hk6", b"c", 0, b"", [
            Mutate(0, b"m", b"x")]).error == 0
        assert client.set(b"hk5", b"a", b"v", ttl_seconds=60) == 0
        after = epoch_now()
        for hk, sk in ((b"hk1", b"a"), (b"hk2", b"a"), (b"hk2", b"b"),
                       (b"hk3", b"n"), (b"hk4", b"c"), (b"hk6", b"m")):
            for ets, header in _stored_ets(sim, app_id, hk, sk):
                assert ets == header
                assert before + default_ttl - 5 < ets <= after \
                    + default_ttl, (hk, sk, ets - after)
            err, ttl = client.ttl(hk, sk)
            assert err == 0 and default_ttl - 5 < ttl <= default_ttl
        # a put with its own TTL keeps it
        for ets, header in _stored_ets(sim, app_id, b"hk5", b"a"):
            assert ets == header and ets <= after + 60
    finally:
        sim.close()


def test_a_table_without_the_env_stores_no_ttl(tmp_path):
    sim, app_id, client = _cluster(tmp_path, None)
    try:
        assert client.set(b"hk1", b"a", b"v") == 0
        assert _stored_ets(sim, app_id, b"hk1", b"a") == [(0, 0)] * 3
        assert client.ttl(b"hk1", b"a") == (0, -1)
    finally:
        sim.close()


def test_reads_count_the_rows_they_skip_as_expired(tmp_path):
    """A get and a scan over expired rows: each skipped row counts
    once in storage/scan_rows_expired."""
    from pegasus_tpu.server.types import GetScannerRequest

    def expired_count():
        return sum(ent["metrics"]["scan_rows_expired"]["value"]
                   for ent in METRICS.snapshot()
                   if ent["type"] == "storage"
                   and "scan_rows_expired" in ent["metrics"])

    sim, _app_id, client = _cluster(tmp_path, None)
    try:
        past = epoch_now() - 100
        for sk in (b"a", b"b", b"c"):
            assert client.set(b"hk", sk, b"v") == 0
        # an already-expired row, as a load that carries expire_ts
        # writes it
        from pegasus_tpu.base.key_schema import partition_index
        from pegasus_tpu.replica.mutation import WriteOp
        from pegasus_tpu.rpc.codec import OP_PUT as RPC_PUT

        p = partition_index(b"hk", 4)
        primary = sim.stubs[sim.primaries(_app_id)[p]].get_replica(
            (_app_id, p))
        primary.client_write([WriteOp(RPC_PUT, (
            generate_key(b"hk", b"y"), b"v", past))], lambda _r: 0)
        sim.loop.run_until_idle()
        base = expired_count()
        assert client.get(b"hk", b"y")[0] != 0
        assert expired_count() == base + 1
        replies = client.scan_multi({p: [GetScannerRequest(
            start_key=generate_key(b"hk", b""), batch_size=100,
            validate_partition_hash=True, one_page=True)]})
        got = [kv.key for kv in replies[p][0].kvs]
        assert generate_key(b"hk", b"y") not in got
        assert expired_count() >= base + 2
    finally:
        sim.close()


def test_a_one_page_scan_reads_past_a_window_of_expired_rows(tmp_path):
    """scan_multi plans a short scan's rows by block (at least twice its
    batch, `plan_scan_batch`) and applies TTL at assembly. Where every
    planned row has expired, the page must still hold the live rows
    that follow: a one-page scan whose page comes back empty reads as
    the end of its range."""
    from pegasus_tpu.base.key_schema import partition_index
    from pegasus_tpu.replica.mutation import WriteOp
    from pegasus_tpu.rpc.codec import OP_PUT as RPC_PUT
    from pegasus_tpu.server.types import GetScannerRequest

    sim, app_id, client = _cluster(tmp_path, None)
    try:
        now = epoch_now()
        p = partition_index(b"hk", 4)
        primary = sim.stubs[sim.primaries(app_id)[p]].get_replica(
            (app_id, p))
        # block 0 holds s0000..s1023; its last 100 rows have expired
        rows = [WriteOp(RPC_PUT, (generate_key(b"hk", b"s%04d" % i), b"v%d" % i,
                                  now - 100 if 924 <= i < 1024 else 0))
                for i in range(3000)]
        for off in range(0, 3000, 1000):
            primary.client_write(rows[off:off + 1000], lambda _r: 0)
            sim.loop.run_until_idle()
        sim.step(rounds=2)
        srv = primary.server
        srv.flush()
        srv.manual_compact(now=now - 1000)      # the expired rows stay
        assert srv.engine.lsm.l1_runs
        for n in (9, 40):
            req = GetScannerRequest(
                start_key=generate_key(b"hk", b"s0924"), batch_size=n,
                validate_partition_hash=True, one_page=True)
            (resp,) = client.scan_multi({p: [req]})[p]
            assert resp.error == 0
            assert [kv.key for kv in resp.kvs] == [
                generate_key(b"hk", b"s%04d" % i)
                for i in range(1024, 1024 + n)]
    finally:
        sim.close()
