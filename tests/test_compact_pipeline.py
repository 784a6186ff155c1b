"""PR 8: accelerator-pipelined compaction + the background-IO scheduler.

Covers the four acceptance surfaces:
- byte-identity of the stage threads vs the inline loop over MIXED
  legacy(none)+dcz+dcz2 stores (both compaction shapes), and which of
  the two the engine picks from the snapshot's size and the cores;
- crash mid-pipeline: a write fault aborts the compaction, nothing of
  the half-built output is adopted at reopen (manifest-then-unlink
  ordering holds) and the data still serves;
- the dcz2 column codecs (FOR expire_ts, dict-indexed hash_lo):
  round-trip equivalence with v1, native-subset parity, and the
  down-transcode guard that keeps v2 blocks out of 'dcz' files;
- the schedulers: seeded governor AIMD backoff under growing
  shed/deadline counters (and recovery), the meta coordinator's
  stagger invariants, and the env-trigger defer/grant path.
"""

from __future__ import annotations

import hashlib
import os
import shutil

import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.base.value_schema import epoch_now
from pegasus_tpu.storage import compact_pipeline
from pegasus_tpu.storage.compact_governor import CompactionGovernor
from pegasus_tpu.storage.engine import StorageEngine, WriteBatchItem
from pegasus_tpu.storage.wal import OP_PUT
from pegasus_tpu.utils.flags import FLAGS


def _set_flag(section, name, value):
    old = FLAGS.get(section, name)
    FLAGS.set(section, name, value)
    return old


@pytest.fixture
def pipeline_flags():
    """Snapshot + restore the storage flag the tests flip."""
    saved = FLAGS.get("pegasus.storage", "block_codec")
    yield
    FLAGS.set("pegasus.storage", "block_codec", saved)


# _build_mixed_store's snapshot is 60 entries: a window of 8 is what
# the engine hands the stage threads, the module's own 128 what it
# runs inline (tests of the threaded side need a host of 4+ cores,
# which stage_threads_enabled reads off os.cpu_count)
LOOPS = {"inline": 128, "stage_threads": 8}
STAGE_THREADS = ["compact-read", "compact-filter"]


def _stage_threads(started):
    return [n for n in started if n in STAGE_THREADS]


def _build_mixed_store(d: str, block_capacity: int = 64) -> None:
    """A store whose L0s span all three codecs (a rolling-upgrade
    shape: legacy files keep serving beside both dcz generations)."""
    eng = StorageEngine(d, block_capacity=block_capacity)
    now = epoch_now()
    rng = np.random.default_rng(11)
    dec = 0
    for codec in ("none", "dcz", "dcz2"):
        FLAGS.set("pegasus.storage", "block_codec", codec)
        for b in range(4):
            items = []
            for j in range(300):
                i = dec * 300 + j
                k = generate_key(b"hk%05d" % (i // 25),
                                 b"s%03d" % (i % 25))
                ets = int(now) - 40 if rng.random() < 0.25 else 0
                items.append(WriteBatchItem(
                    OP_PUT, k, b"value-%06d|" % i * 3, ets))
            dec += 1
            eng.write_batch(items, dec)
            eng.flush()
    eng.close()


def _digest(eng: StorageEngine) -> str:
    h = hashlib.sha256()
    for k, v, e in eng.iterate():
        h.update(k)
        h.update(v)
        h.update(b"%d" % e)
    sst = os.path.join(eng.data_dir, "sst")
    for name in sorted(os.listdir(sst)):
        if name.endswith(".sst"):
            with open(os.path.join(sst, name), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def test_pipelined_identical_to_serial_mixed_codecs(tmp_path,
                                                    pipeline_flags,
                                                    monkeypatch,
                                                    started_threads):
    """The tentpole gate: the stage threads must produce the exact
    bytes the inline loop produces (the engine picks by the snapshot's
    size against the window), over a store mixing legacy raw,
    dcz, and dcz2 runs — through both shapes of the block path (twelve
    L0 tables of three codecs chained by key range, then pure L1)."""
    import pegasus_tpu.storage.engine as engine_mod

    # the compaction meta stamps manual_compact_finish_time =
    # epoch_now() into the SST index, and the TTL drop masks read the
    # clock too — freeze it so the two runs can't straddle a second
    # boundary and diverge on bytes that have nothing to do with the
    # pipeline
    monkeypatch.setattr(engine_mod, "epoch_now", lambda: 334_000_000)
    src = str(tmp_path / "src")
    _build_mixed_store(src)
    FLAGS.set("pegasus.storage", "block_codec", "dcz2")
    digs, threads = {}, {}
    for loop, window in LOOPS.items():
        d = str(tmp_path / loop)
        shutil.copytree(src, d)
        monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", window)
        del started_threads[:]
        eng = StorageEngine(d, block_capacity=64)
        snap = eng.lsm.bulk_compact_snapshot()
        assert len(snap.chain) == 12 and not snap.overlay
        eng.manual_compact()          # chained L0s -> L1
        assert eng.lsm.bulk_compact_eligible()
        eng.manual_compact()          # pure L1
        digs[loop] = _digest(eng)
        threads[loop] = _stage_threads(started_threads)
        eng.close()
    assert digs["stage_threads"] == digs["inline"]
    assert threads == {"inline": [], "stage_threads": STAGE_THREADS * 2}


@pytest.mark.parametrize("loop", list(LOOPS))
def test_crash_mid_pipeline_keeps_old_store(tmp_path, pipeline_flags,
                                            monkeypatch,
                                            started_threads, loop):
    """A disk fault mid-compaction must abort either loop cleanly:
    the error propagates, stage threads (where the snapshot's size
    started them) stop, no half-built l1 output is adopted at reopen
    (the manifest still names the old runs), and every record still
    serves."""
    import threading

    from pegasus_tpu.utils.fail_point import FAIL_POINTS

    d = str(tmp_path / "s")
    _build_mixed_store(d)
    FLAGS.set("pegasus.storage", "block_codec", "dcz2")
    monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", LOOPS[loop])
    eng = StorageEngine(d, block_capacity=64)
    eng.manual_compact()  # pure L1 now
    del started_threads[:]
    before = _digest(eng)
    runs_before = [os.path.basename(t.path) for t in eng.lsm.l1_runs]
    gen = eng.lsm.generation
    FAIL_POINTS.teardown()
    FAIL_POINTS.setup()
    FAIL_POINTS.seed(3)
    FAIL_POINTS.cfg("vfs::write", "return(eio)")
    try:
        with pytest.raises(OSError):
            eng.manual_compact()
    finally:
        FAIL_POINTS.teardown()
    assert _stage_threads(started_threads) == (
        STAGE_THREADS if loop == "stage_threads" else [])
    assert not [t for t in threading.enumerate()
                if t.name in STAGE_THREADS]
    # publish never happened: same run set, same generation
    assert eng.lsm.generation == gen
    assert [os.path.basename(t.path)
            for t in eng.lsm.l1_runs] == runs_before
    eng.close()
    # reopen: boot must clean any orphan outputs and serve identically
    eng2 = StorageEngine(d, block_capacity=64)
    assert [os.path.basename(t.path)
            for t in eng2.lsm.l1_runs] == runs_before
    assert _digest(eng2) == before
    # and a clean retry completes
    eng2.manual_compact()
    eng2.close()


@pytest.mark.parametrize("case", ["one_window", "window_plus_one",
                                  "two_cores"])
def test_stage_choice_follows_what_the_engine_observes(
        tmp_path, pipeline_flags, monkeypatch, started_threads, case):
    """No switch selects the loop: a snapshot of exactly one window
    runs inline, one entry more starts both stage threads, and a host
    of 2 cores starts none whatever the size. Same bytes in all."""
    import pegasus_tpu.storage.engine as engine_mod

    monkeypatch.setattr(engine_mod, "epoch_now", lambda: 334_000_000)
    d = str(tmp_path / "s")
    _build_mixed_store(d)
    FLAGS.set("pegasus.storage", "block_codec", "dcz2")

    def compact(window, cores):
        c = str(tmp_path / f"w{window}c{cores}")
        shutil.copytree(d, c)
        monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", window)
        monkeypatch.setattr(os, "cpu_count", lambda: cores)
        eng = StorageEngine(c, block_capacity=64)
        del started_threads[:]
        eng.manual_compact()
        dig = _digest(eng)
        eng.close()
        return dig, _stage_threads(started_threads)

    eng = StorageEngine(d, block_capacity=64)
    n = len(eng.lsm.bulk_compact_entries(eng.lsm.bulk_compact_snapshot()))
    eng.close()
    assert n > 16   # more than one transform chunk: the pool runs too
    window, cores, want = {
        "one_window": (n, 8, []),
        "window_plus_one": (n - 1, 8, STAGE_THREADS),
        "two_cores": (8, 2, []),
    }[case]
    dig, threads = compact(window, cores)
    assert threads == want
    # the reference: the module's own window of 128, inline
    assert dig == compact(128, 8)[0]


# ---- dcz2 column codecs ------------------------------------------------


def _raw_block(n=120, seed=3, wide_ttl=False):
    rng = np.random.default_rng(seed)
    keys_list = []
    for h in range(n // 6):
        for s in range(6):
            hk = b"user%04d" % h
            sk = b"s%02d" % s
            keys_list.append(bytes([0, len(hk)]) + hk + sk)
    keys_list = sorted(keys_list)[:n]
    keys_list[0] = bytes([0, 0]) + b"aaa-sortonly"  # empty hashkey
    keys_list.sort()
    n = len(keys_list)
    width = 32
    keys = np.zeros((n, width), dtype=np.uint8)
    key_len = np.zeros(n, dtype=np.int32)
    for i, k in enumerate(keys_list):
        keys[i, :len(k)] = np.frombuffer(k, dtype=np.uint8)
        key_len[i] = len(k)
    ets = np.where(rng.random(n) < 0.5, 0,
                   1_700_000_000
                   + rng.integers(0, 900, n)).astype(np.uint32)
    if wide_ttl:
        ets[1] = 17
        ets[2] = 0xE0000000
    flags = np.zeros(n, dtype=np.uint8)
    vals = [b"v%04d|" % i
            + bytes(rng.integers(32, 127, 18, dtype=np.uint8))
            for i in range(n)]
    offs = np.zeros(n + 1, dtype=np.uint32)
    offs[1:] = np.cumsum([len(v) for v in vals])
    heap = b"".join(vals)
    from pegasus_tpu.base.crc import crc64_batch

    hkl = (keys[:, 0].astype(np.int64) << 8) \
        | keys[:, 1].astype(np.int64)
    region = np.where(hkl > 0, hkl, key_len.astype(np.int64) - 2)
    hash_lo = (crc64_batch(keys, region, start=2)
               & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return keys, key_len, ets, hash_lo, flags, offs, heap


@pytest.mark.parametrize("wide_ttl", [False, True])
def test_dcz2_roundtrip_equals_v1(wide_ttl):
    """FOR expire_ts + dict-indexed hash_lo must reproduce exactly the
    columns the v1 layout stores raw — including the empty-hashkey
    rows whose hash is NOT group-constant (they ride the overflow
    array) and the wide-TTL spread that falls back to raw u32."""
    from pegasus_tpu.storage.block_codec import (
        EncodedBlock,
        block_version,
        encode_block,
    )

    cols = _raw_block(wide_ttl=wide_ttl)
    b1 = encode_block(*cols, version=1)
    b2 = encode_block(*cols, version=2)
    assert block_version(b1) == 1 and block_version(b2) == 2
    keys, key_len, ets, hash_lo, flags, offs, heap = cols
    for b in (b1, b2):
        enc = EncodedBlock.parse(b)
        assert np.array_equal(enc.expire_ts, ets)
        assert np.array_equal(enc.hash_lo, hash_lo)
        blk = enc.decode()
        assert np.array_equal(blk.keys, keys)
        assert np.array_equal(blk.value_offs, offs)
        assert bytes(np.asarray(blk.value_heap)) == heap
    if not wide_ttl:
        # the whole point: v2 stores the predicate columns smaller
        assert len(b2) < len(b1)


def test_dcz2_native_subset_parity():
    """The native kernel must subset a v2 block to the same logical
    content as the same v1 block — keys, rewritten TTLs, hashes,
    bloom hashes, fences — and keep the block's format version."""
    from pegasus_tpu import native
    from pegasus_tpu.storage.block_codec import (
        EncodedBlock,
        block_version,
        encode_block,
    )

    sub = native.cblock_subset_fn()
    if sub is None:
        pytest.skip("native library unavailable")
    cols = _raw_block(seed=9)
    n = cols[0].shape[0]
    rng = np.random.default_rng(4)
    keep = rng.random(n) > 0.35
    ets = cols[2]
    new_ets = np.where(ets == 0, 0, ets + 9).astype(np.uint32)
    got = {}
    for ver in (1, 2):
        b = encode_block(*cols, version=ver)
        enc = EncodedBlock.parse(b)
        r = sub(bytes(enc.raw) if not isinstance(enc.raw, bytes)
                else enc.raw, enc.raw_heap_len, enc.key_width, keep,
                new_ets, True, want_hashes=True)
        assert r is not None
        buf, hashes, m, vsub, fk, lk = r
        assert block_version(buf) == ver
        assert m == int(keep.sum())
        got[ver] = (EncodedBlock.parse(buf), hashes, fk, lk)
    e1, h1, fk1, lk1 = got[1]
    e2, h2, fk2, lk2 = got[2]
    assert np.array_equal(h1, h2)
    assert (fk1, lk1) == (fk2, lk2)
    assert np.array_equal(e1.hash_lo, e2.hash_lo)
    d1, d2 = e1.decode(), e2.decode()
    assert np.array_equal(d1.keys, d2.keys)
    assert np.array_equal(d1.expire_ts, d2.expire_ts)
    assert np.array_equal(d1.expire_ts, new_ets[keep])
    assert bytes(np.asarray(d1.value_heap)) == \
        bytes(np.asarray(d2.value_heap))


def test_dcz_writer_never_embeds_v2(tmp_path, pipeline_flags):
    """Format-version containment: compacting a dcz2 store under a
    'dcz' writer must down-transcode every block — the output file's
    blocks are all v1, so a build that knows only dcz can serve it."""
    from pegasus_tpu.storage.block_codec import block_version

    d = str(tmp_path / "s")
    FLAGS.set("pegasus.storage", "block_codec", "dcz2")
    eng = StorageEngine(d, block_capacity=64)
    now = epoch_now()
    items = [WriteBatchItem(
        OP_PUT, generate_key(b"hk%03d" % (i // 10), b"s%02d" % (i % 10)),
        b"payload-%04d|" % i * 3,
        int(now) - 30 if i % 4 == 0 else 0) for i in range(600)]
    eng.write_batch(items, 1)
    eng.flush()
    eng.manual_compact()
    eng.manual_compact()  # bulk: pure-L1 dcz2 store now
    before = {k: (v, e) for k, v, e in eng.iterate()}
    assert all(t.codec == "dcz2" for t in eng.lsm.l1_runs)
    FLAGS.set("pegasus.storage", "block_codec", "dcz")
    eng.manual_compact()  # rewrites under the dcz writer
    for t in eng.lsm.l1_runs:
        assert t.codec == "dcz"
        for i in range(len(t.blocks)):
            raw, _bm = t._read_raw_block(i)
            assert block_version(bytes(raw[:48])) == 1
    after = {k: (v, e) for k, v, e in eng.iterate()}
    assert after == before
    eng.close()


# ---- the governor (node scheduler) -------------------------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _governor(clock, pressure):
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.t += s

    g = CompactionGovernor(clock=clock, sleep=sleep,
                           pressure_source=lambda: pressure[0])
    return g, sleeps


def test_governor_backs_off_under_pressure_and_recovers():
    """Seeded feedback loop: growing shed/deadline counters must
    engage a cap and halve it per interval (never below the floor);
    quiet intervals recover multiplicatively until the cap disengages.
    Background progress never stops: acquire() always returns."""
    clock = _Clock()
    pressure = [0]
    g, sleeps = _governor(clock, pressure)
    step = 1 << 20  # 1 MiB per acquire
    # establish a measured rate with no pressure: never throttled
    for _ in range(40):
        g.acquire(step)
        clock.t += 0.05  # ~20 MB/s offered
    assert g.status()["throttle_mbps"] == 0
    assert not sleeps
    # pressure grows across two feedback intervals: cap engages, halves
    pressure[0] = 10
    clock.t += 1.1
    g.acquire(step)
    t1 = g.status()["throttle_mbps"]
    assert t1 > 0
    pressure[0] = 25
    clock.t += 1.1
    g.acquire(step)
    t2 = g.status()["throttle_mbps"]
    assert t2 == pytest.approx(max(t1 / 2,
                                   FLAGS.get("pegasus.storage",
                                             "compact_min_mbps")))
    assert g._c_backoff.value() >= 2
    # throttled acquires now sleep (bytes/s bounded) but still return
    n_sleeps = len(sleeps)
    for _ in range(30):
        g.acquire(step)
    assert len(sleeps) > n_sleeps
    # pressure stops growing: recovery climbs and eventually uncaps
    for _ in range(30):
        clock.t += 1.1
        g.acquire(step)
        if g.status()["throttle_mbps"] == 0:
            break
    assert g.status()["throttle_mbps"] == 0


def test_governor_floor_guarantees_progress():
    """However long the pressure persists, the throttle never drops
    below compact_min_mbps — compaction keeps moving."""
    clock = _Clock()
    pressure = [0]
    g, _sleeps = _governor(clock, pressure)
    g.acquire(1 << 20)
    for i in range(12):
        pressure[0] += 5
        clock.t += 1.1
        g.acquire(1 << 20)
    floor = float(FLAGS.get("pegasus.storage", "compact_min_mbps"))
    assert g.status()["throttle_mbps"] == pytest.approx(floor)


def test_governor_grant_lease():
    clock = _Clock()
    g, _ = _governor(clock, [0])
    assert g.heavy_allowed()  # no coordinator ever answered: open
    g.set_cluster_grant(False)
    assert not g.heavy_allowed()
    g.set_cluster_grant(True)
    assert g.heavy_allowed()
    g.set_cluster_grant(False)
    lease = float(FLAGS.get("pegasus.storage", "compact_grant_lease_s"))
    clock.t += lease + 1
    # an EXPIRED denial fails open: a dead meta must not wedge
    # compaction cluster-wide
    assert g.heavy_allowed()


# ---- the coordinator (meta scheduler) ----------------------------------


class _FakeMeta:
    def __init__(self):
        self.t = 0.0
        self.name = "meta1"

    def clock(self):
        return self.t


def test_coordinator_staggers_and_rotates():
    """At most K nodes hold the grant; a holder that finishes releases
    its slot the same round; waiters admit in first-seen order; a
    holder that goes silent ages out after the lease."""
    from pegasus_tpu.meta.compaction_scheduler import (
        CompactionCoordinator,
    )

    meta = _FakeMeta()
    c = CompactionCoordinator(meta)
    old = FLAGS.get("pegasus.meta", "compaction_concurrent_nodes")
    FLAGS.set("pegasus.meta", "compaction_concurrent_nodes", 1)
    try:
        def report(node, running=0, waiting=False):
            return c.on_report(node, {"compaction": {
                "running": running, "waiting": waiting,
                "bytes_per_s": 0}})

        lease = float(FLAGS.get("pegasus.meta",
                                "compaction_grant_lease_s"))
        grace = lease / 3
        # three nodes want to compact: exactly one granted
        got = {n: report(n, waiting=True) for n in ("n1", "n2", "n3")}
        assert sum(got.values()) == 1
        winner = next(n for n, g in got.items() if g)
        # within the delivery grace a not-yet-running holder KEEPS its
        # slot (the grant rides the NEXT reply; a graceless release
        # would pass it around the ring with no reply ever saying yes)
        meta.t += 1
        assert report(winner, running=0, waiting=True) is True
        # winner runs; others keep asking — still only the winner,
        # well past the grace (running holders are never released)
        for _ in range(3):
            meta.t += grace
            assert report(winner, running=1) is True
            for n in ("n1", "n2", "n3"):
                if n != winner:
                    assert report(n, waiting=True) is False
        # winner finishes: once past the grace the slot releases and
        # the FIRST waiter gets it
        meta.t += grace + 1
        assert report(winner, running=0, waiting=False) is False
        waiters = [n for n in ("n1", "n2", "n3") if n != winner]
        got2 = {n: report(n, waiting=True) for n in waiters}
        assert sum(got2.values()) == 1
        second = next(n for n, g in got2.items() if g)
        # a holder that only ever reports waiting (never running) also
        # rotates out after the grace — camping would livelock every
        # other node (sim nodes even share the governor waiting flag)
        meta.t += grace + 1
        assert report(second, running=0, waiting=True) is False
        got3 = {n: report(n, waiting=True) for n in waiters
                if n != second}
        assert sum(got3.values()) == 1
        second = next(n for n, g in got3.items() if g)
        # the new holder dies silently: its grant ages out and the
        # remaining waiter is admitted
        last = next(n for n in waiters if n != second)
        lease = float(FLAGS.get("pegasus.meta",
                                "compaction_grant_lease_s"))
        meta.t += lease + 1
        assert report(last, waiting=True) is True
        # stagger off (k=0): everyone granted
        FLAGS.set("pegasus.meta", "compaction_concurrent_nodes", 0)
        assert report(second, waiting=True) is True
        assert report(last, waiting=True) is True
        # nodes with no compaction block are never gated
        assert c.on_report("old-node", {}) is None
    finally:
        FLAGS.set("pegasus.meta", "compaction_concurrent_nodes", old)


@pytest.fixture
def server(tmp_path):
    from pegasus_tpu.server.partition_server import PartitionServer

    s = PartitionServer(str(tmp_path / "p0"))
    yield s
    s.close()


def test_env_trigger_defers_until_granted(server):
    """The heavy-compaction gate on the env trigger: denied -> the
    trigger defers (demand recorded, trigger_seen NOT consumed);
    granted -> the SAME re-delivered env starts the compaction."""
    import time

    from pegasus_tpu.storage.compact_governor import GOVERNOR

    for i in range(40):
        server.engine.write_batch(
            [WriteBatchItem(OP_PUT,
                            generate_key(b"gk%02d" % i, b"s"),
                            b"v%d" % i, 0)],
            server.engine.last_committed_decree + 1)
    lsm = server.engine.lsm
    assert not lsm.l1_runs
    trigger = {"manual_compact.once.trigger_time":
               str(int(time.time()))}
    GOVERNOR.set_cluster_grant(False)
    d0 = GOVERNOR.status()["defer_count"]
    server.update_app_envs(trigger)
    assert not server._mc_running
    assert GOVERNOR.status()["defer_count"] == d0 + 1
    assert GOVERNOR.report()["waiting"] is True
    assert not lsm.l1_runs
    # the grant arrives (next config-sync reply): the re-delivered env
    # now starts the run
    GOVERNOR.set_cluster_grant(True)
    server.update_app_envs(trigger)
    deadline = time.monotonic() + 30
    while server._mc_running and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not server._mc_running
    assert lsm.l1_runs and not len(lsm.memtable)


# ---- scrub restart-once under pipelined publishes ----------------------


def test_scrub_restarts_once_per_publish(tmp_path, pipeline_flags):
    """One manual compaction bumps the store generation
    more than once (freeze-flush + publish cut-over); the scrubber
    must restart its pass exactly ONCE for it — and pause (not
    restart) while the compaction holds the lock."""
    from pegasus_tpu.storage.scrub import ReplicaScrubber
    from pegasus_tpu.utils.metrics import METRICS

    d = str(tmp_path / "s")
    _build_mixed_store(d)
    eng = StorageEngine(d, block_capacity=64)

    class _Rep:
        class server:
            engine = eng

    reps = {(1, 0): _Rep()}
    scrubber = ReplicaScrubber(lambda: reps, lambda g, e: None,
                               blocks_per_tick=2)
    scrubber.pass_interval = 0.0
    restart = METRICS.entity("storage", "node").counter(
        "scrub_restart_count")
    scrubber.tick()  # opens a cursor mid-pass (2 blocks of many)
    assert (1, 0) in scrubber._cursor
    r0 = restart.value()
    # freeze-flush + compact + publish: >= 2 generation bumps
    gen0 = eng.lsm.generation
    with eng.compact_lock:
        # while the lock is held (mid-compaction), ticks PAUSE the
        # cursor rather than restarting it
        scrubber.tick()
        assert restart.value() == r0
        assert (1, 0) in scrubber._cursor
    eng.write_batch(
        [WriteBatchItem(OP_PUT, generate_key(b"fresh", b"s"),
                        b"v", 0)],
        eng.last_committed_decree + 1)
    eng.flush()          # the freeze-flush half of the publish
    eng.manual_compact()  # the cut-over half
    assert eng.lsm.generation >= gen0 + 2
    # however many ticks observe the new generation, the restart fires
    # exactly once
    scrubber.tick()
    scrubber.tick()
    scrubber.tick()
    assert restart.value() == r0 + 1
    eng.close()


def test_pipeline_stall_counters_populate(tmp_path, pipeline_flags,
                                          monkeypatch):
    """Observability satellite: a threaded compaction must leave
    per-stage evidence behind (bytes/s gauge; stall counters may or
    may not tick depending on which stage bottlenecks, but the gauges
    exist on the storage entity and the run must not zero them out)."""
    from pegasus_tpu.utils.metrics import METRICS

    monkeypatch.setattr(compact_pipeline, "PIPELINE_WINDOW", 4)
    d = str(tmp_path / "s")
    _build_mixed_store(d)
    eng = StorageEngine(d, block_capacity=64)
    eng.manual_compact()
    eng.manual_compact()
    eng.close()
    snap = [s["metrics"] for s in METRICS.snapshot("storage")][0]
    for name in ("compaction_bytes_per_s", "compact_read_stall_ms",
                 "compact_filter_stall_ms", "compact_write_stall_ms",
                 "compact_readq_depth", "compact_filtq_depth"):
        assert name in snap, name
