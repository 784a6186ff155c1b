"""The cell ycsb_e_compact.p64r3's own pieces: ops/scan_rules.check on
a hand-built reference (it accepts the four legal shapes and rejects
the five faults), the mix against the config, the roofline reader on a
hand-built trace and counters, and the CPU rehearsal of every cell,
sound and with a planted fault. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_rules_cell.py -q -p no:cacheprovider
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.generator import load_json  # noqa: E402
from benchmarks.ops import compact, scan_rules  # noqa: E402
from benchmarks.reference import Model, model_key  # noqa: E402

RULES = [{"op": "delete_key", "rules": [
    {"type": "hashkey_pattern", "pattern": "user9", "match": "prefix"},
    {"type": "sortkey_pattern", "pattern": "f9", "match": "prefix"}]}]
NOW = 1000


def _model(triggered=True):
    """One partition: user1 and user9 with f0..f9 (user9's f9 matched),
    user1's f3 expired."""
    model = Model(1)
    for hk in (b"user1", b"user9"):
        for j in range(10):
            ets = 5 if (hk, j) == (b"user1", 3) else 0
            model.put(hk, b"f%d" % j, b"%s.%d" % (hk, j), ets)
    if triggered:
        compact.apply(model, ("t", {"user_specified_compaction":
                                    json.dumps(RULES)}))
    return model


def _rows(model, hk, fields):
    return [(model_key(hk, b"f%d" % j), model.rows[0][
        model_key(hk, b"f%d" % j)][0]) for j in fields]


def _reply(*pages, call=1):
    reply = scan_rules.Pages(
        SimpleNamespace(kvs=[SimpleNamespace(key=k, value=v)
                             for k, v in page]) for page in pages)
    reply.call = call
    return reply


def _args(hk, n):
    return (0, model_key(hk, b""), n, None)


def test_accepts_with_without_and_continued_pages():
    model = _model()
    nine = _rows(model, b"user9", range(10))
    # with the matched row; continued after a short page
    assert scan_rules.check(model, _args(b"user9", 10),
                            _reply(nine[:4], nine[4:]), NOW) is None
    # without it, to the partition's end: 9 rows and an empty page
    assert scan_rules.check(model, _args(b"user9", 10),
                            _reply(nine[:9], [], call=2), NOW) is None
    assert model.rules_state["first_without"] == {0: (2, 1)}
    # an expired row is in neither
    one = _rows(model, b"user1", [0, 1, 2, 4])
    assert scan_rules.check(model, _args(b"user1", 4),
                            _reply(one, call=3), NOW) is None


FAULTS = {
    "dropped_unmatched_row":
        lambda m: _reply(_rows(m, b"user9", [0, 1, 3, 4])),
    "changed_value":
        lambda m: _reply([(k, v + b"!") for k, v in
                          _rows(m, b"user9", [0, 1, 2])][:3]),
    "stops_short_of_the_partitions_end":
        lambda m: _reply(_rows(m, b"user9", range(9))),
    "rows_too_many":
        lambda m: _reply(_rows(m, b"user9", range(10))
                         + [(b"zz", b"zz")]),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_rejects_after_the_trigger(fault):
    model = _model()
    n = 10 if fault in ("stops_short_of_the_partitions_end",
                        "rows_too_many") else (
        4 if fault == "dropped_unmatched_row" else 3)
    assert scan_rules.check(model, _args(b"user9", n),
                            FAULTS[fault](model), NOW) is not None


def test_rejects_a_mixture_of_matched_rows():
    """Two matched rows in one page, one of them missing."""
    model = Model(1)
    for hk in (b"user90", b"user91", b"user92"):
        for j in (8, 9):
            model.put(hk, b"f%d" % j, b"v", 0)
    compact.apply(model, ("t", {"user_specified_compaction":
                                json.dumps(RULES)}))
    keys = sorted(model.rows[0])
    full = [(k, b"v") for k in keys]
    ok_with, ok_without = full[:4], [full[0], full[2], full[4]]
    assert scan_rules.check(model, (0, keys[0], 4, None),
                            _reply(ok_with), NOW) is None
    assert scan_rules.check(model, (0, keys[0], 3, None),
                            _reply(ok_without), NOW) is None
    model.rules_state["first_without"].clear()
    mixture = [full[0], full[1], full[2], full[4]]  # user91's f9 left out
    assert "neither" in scan_rules.check(
        model, (0, keys[0], 4, None), _reply(mixture), NOW)


def test_rejects_a_matched_row_missing_before_the_trigger():
    model = _model(triggered=False)
    nine = _rows(model, b"user9", range(10))
    assert scan_rules.check(model, _args(b"user9", 10),
                            _reply(nine), NOW) is None
    assert "before any compaction" in scan_rules.check(
        model, _args(b"user9", 10), _reply(nine[:9], []), NOW)


def test_rejects_a_matched_row_after_the_latch():
    model = _model()
    nine = _rows(model, b"user9", range(10))
    assert scan_rules.check(model, _args(b"user9", 10),
                            _reply(nine[:9], [], call=5), NOW) is None
    # an earlier round of the same call may still have held it
    assert scan_rules.check(model, _args(b"user9", 10),
                            _reply(nine, call=5), NOW) is None
    assert "after one had come without" in scan_rules.check(
        model, _args(b"user9", 10), _reply(nine, call=6), NOW)


def test_mix_states_the_configs_envs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "ycsb_e_compact.p64r3")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"])
    op = next(o for o in traffic["ops"] if o["kind"] == "compact")
    assert op["app_envs"] == config["app_envs"]
    assert op["table"] == config["table"]
    assert [o["kind"] for o in traffic["ops"]] == [
        "scan_rules", "insert", "compact"]
    assert "trace_probe" not in traffic
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    base = load_json("configs", "ycsb_p64r3")
    for key in ("partitions", "replicas", "nodes", "records", "fields",
                "field_length", "expired_share", "chips"):
        assert config[key] == base[key]
    for key, text in base["guarantees"].items():
        assert config["guarantees"][key] == text    # none restated weaker
    args = compact.draw(None, None, 2, op, {})
    assert json.loads(args[0][1]["user_specified_compaction"]) \
        == config["app_envs"]["user_specified_compaction"]


def test_compact_kind_refuses_a_program_without_the_bound(monkeypatch):
    """On the PR's parent the pool does not exist: importing the kind,
    which the harness does before it builds the cluster, ends the run."""
    import importlib

    from pegasus_tpu.storage import compact_governor

    assert compact.BOUND_ENV in load_json(
        "configs", "ycsb_rules_p64r3")["app_envs"]
    with monkeypatch.context() as m:
        m.delattr(compact_governor, "MANUAL_COMPACT_POOL")
        with pytest.raises(SystemExit) as exc:
            importlib.reload(compact)
        assert exc.value.code not in (0, None)
        assert compact.BOUND_ENV in str(exc.value.code)
    importlib.reload(compact)
    assert compact.program_bounds_compactions()


def test_filter_roofline_reader(monkeypatch):
    from benchmarks.readers import filter_roofline
    from pegasus_tpu.ops.compaction import note_filter_program
    from pegasus_tpu.utils import tracing

    spec = load_json("layer_metrics", "compact_filter_roofline")
    before = filter_roofline.begin(spec)
    monkeypatch.setattr(tracing, "profiling", lambda: True)
    note_filter_program(16384, 16384 * (32 + 17) + 16384 // 8 + 4 * 16384)
    # a torn scan batch's mask program rides in the same busy time
    note_filter_program(1024, 1024 * 45 + 128, kind="mask")
    monkeypatch.setattr(tracing, "profiling", lambda: False)
    note_filter_program(16384, 10 ** 9)      # outside the session
    note_filter_program(1024, 10 ** 9, kind="mask")
    nbytes = (16384 * (32 + 17) + 16384 // 8 + 4 * 16384
              + 1024 * 45 + 128)
    trace = {"busy_s": 0.002, "window_s": 3.0,
             "device_ops": [["fusion.1", 0.002]]}
    monkeypatch.setattr(filter_roofline, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    got = filter_roofline.read(spec, before, {"trace": trace})
    assert got == pytest.approx(100.0 * nbytes / 0.002 / 819e9)
    assert 0 < got < 1
    # nothing to read: untraced, an idle slice, no program, an unknown chip
    assert filter_roofline.read(spec, before, {"trace": None}) is None
    idle = dict(trace, busy_s=0.0, device_ops=[])
    assert filter_roofline.read(spec, before, {"trace": idle}) is None
    assert filter_roofline.read(spec, filter_roofline.begin(spec),
                                {"trace": trace}) is None
    monkeypatch.undo()
    assert filter_roofline.read(spec, before, {"trace": trace}) is None


def test_program_counts_the_bytes_the_reader_states(tmp_path, monkeypatch):
    """One replica compacted on each path: `filter_bytes` grows by the
    reader's formula for the programs each path dispatches."""
    from benchmarks.readers.filter_roofline import program_bytes
    from pegasus_tpu.base.key_schema import generate_key
    from pegasus_tpu.server.partition_server import PartitionServer
    from pegasus_tpu.storage.engine import WriteBatchItem
    from pegasus_tpu.storage.wal import OP_PUT
    from pegasus_tpu.utils.metrics import METRICS

    def counted(kind="filter"):
        m = next(e["metrics"] for e in METRICS.snapshot()
                 if e["type"] == "engine" and e["id"] == "filter_programs")
        return tuple(m[f"{kind}_{what}"]["value"]
                     for what in ("programs", "rows", "bytes"))

    server = PartitionServer(str(tmp_path / "p0"))
    try:
        server.update_app_envs({"user_specified_compaction":
                                json.dumps(RULES)})

        def put(lo, hi):
            server.engine.write_batch(
                [WriteBatchItem(OP_PUT, generate_key(b"user%d" % i, b"f9"),
                                b"v", 0) for i in range(lo, hi)],
                server.engine.last_committed_decree + 1)

        def compacted():
            before = counted()
            server.manual_compact()
            return tuple(a - b for a, b in zip(counted(), before))

        # an overlay, and a pure L1 after it: the block path either way
        # (since PR 28), one fused program over a 4,096-row bucket
        put(0, 200)
        bulk = program_bytes("bulk", 4096, 32, want_ets=False)
        assert compacted() == (1, 4096, bulk)
        assert compacted() == (1, 4096, bulk)
        # the per-record merge, for a store the block path declines:
        # the rules' program, then the TTL filter's, 1,024 rows each
        put(200, 300)
        with monkeypatch.context() as m:
            m.setattr(server.engine.lsm, "bulk_compact_snapshot",
                      lambda *a, **kw: None)
            assert compacted() == (
                2, 2048, program_bytes("rules", 1024, 32)
                + program_bytes("ttl", 1024, 32))
        # the read path's static mask, one block and a stack of 16
        from pegasus_tpu.server.scan_coordinator import stacked_block_eval

        run = server.engine.lsm.l1_runs[0]
        dev = server._device_cached_block(
            (run.path, run.blocks[0].offset), run.read_block(0))
        m0 = counted("mask")
        list(stacked_block_eval([(0, dev, 0)], True, 0))
        m1 = counted("mask")
        assert tuple(a - b for a, b in zip(m1, m0)) == (
            1, 1024, program_bytes("mask", 1024, 32, hash_lo=True))
        list(stacked_block_eval([(0, dev, 0), (1, dev, 0)], True, 0))
        m2 = counted("mask")
        assert tuple(a - b for a, b in zip(m2, m1)) == (
            1, 16384, program_bytes("mask", 16384, 32, hash_lo=True,
                                    stacked=True))
    finally:
        server.close()


def test_compact_window_reader(monkeypatch):
    """A counter's delta cut to the window by when the pool's runs
    finished; the drained reading waits for the pool and reads the
    counter as it stands."""
    import threading
    import time

    from benchmarks.ops import scan_rules
    from benchmarks.readers import compact_window
    from pegasus_tpu.storage import compact_governor
    from pegasus_tpu.storage.compact_governor import ManualCompactPool
    from pegasus_tpu.utils.metrics import METRICS

    pool = ManualCompactPool("test-reader")
    monkeypatch.setattr(compact_governor, "MANUAL_COMPACT_POOL", pool)
    counter = METRICS.entity("engine", "test-reader-engine").counter(
        "compact_rows_dropped_rules")
    passes = load_json("layer_metrics", "compact_passes_in_window")
    dropped = load_json("layer_metrics", "compact_rules_dropped_rows")
    assert (passes["as_of"], dropped["as_of"]) == ("window_end", "drained")
    counter.increment(7)                # before the window: since start
    b_passes, b_dropped = (compact_window.begin(passes),
                           compact_window.begin(dropped))
    t0 = b_passes[0]
    finished0 = b_passes[1][0]
    for _ in range(3):
        pool.submit(object(), lambda: counter.increment(5), "r", limit=1)
    assert pool.wait_idle(10)
    # one of the three runs finished inside a window that closed before
    # the other two did
    at = [a for a, _took in pool.history]
    run = {"window_s": (at[0] + at[1]) / 2 - t0}
    assert compact_window.read(passes, b_passes, run) \
        == pytest.approx(3 / 192 * 1 / 3)
    assert compact_window.read(passes, b_passes, {"window_s": 1e9}) \
        == pytest.approx(3 / 192)
    # drained: waits for what still runs
    gate = threading.Event()
    pool.submit(object(), lambda: (gate.wait(10), counter.increment(5)),
                "late", limit=1)
    threading.Timer(0.2, gate.set).start()
    monkeypatch.setattr(scan_rules, "MATCHED_LIVE", 9)
    t_read = time.perf_counter()
    got = compact_window.read(dropped, b_dropped, run)
    assert time.perf_counter() - t_read >= 0.15
    total = sum(e["metrics"]["compact_rows_dropped_rules"]["value"]
                for e in METRICS.snapshot() if e["type"] == "engine"
                and "compact_rows_dropped_rules" in e["metrics"])
    assert got == total and total >= 27
    assert finished0 >= 0
    # a program without the pool (the PR's parent) has nothing to read
    monkeypatch.delattr(compact_governor, "MANUAL_COMPACT_POOL")
    assert compact_window.read(passes, b_passes, run) is None


@pytest.mark.parametrize("fault", [None, "lost_write"])
def test_rehearsal_of_every_cell(fault):
    """rehearse.py, unedited, takes the new cell from BENCHMARK.json:
    correct when sound, not correct under lost_write (exit 0 says each
    cell came out as it should)."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py")]
    if fault:
        cmd += ["--fault", fault]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stderr.splitlines()
                if ln.startswith("[rehearse] ycsb_e_compact.p64r3"))
    assert f"correct={fault is None}" in line


@contextlib.contextmanager
def _time_limit(seconds):
    """A test's own time limit: SIGALRM raises in the main thread."""
    def ring(signum, frame):
        raise TimeoutError(f"the test ran past {seconds} s")

    old = signal.signal(signal.SIGALRM, ring)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.mark.parametrize("seed", [3_500_000_011, 3_500_000_012, 7])
def test_a_run_ends_in_a_result_with_the_pool_at_work(seed, monkeypatch):
    """The stretch between the window's end and run_cell's return,
    crossed at the rehearsal's size while the pool compacts: the walk of
    the work directory, the verification, the trace's reduction, every
    reader, the close and the removal of the directory. The walk's
    wrapper samples the pool where the harness calls it, just after the
    window closed; a run of this test in which the pool was idle there
    has not crossed the stretch that PRs 31, 33 and 34 fell on."""
    from benchmarks import harness
    from benchmarks.rehearse import rehearse_cell
    from pegasus_tpu.storage.compact_governor import MANUAL_COMPACT_POOL

    walks, workdir_bytes = [], harness.workdir_bytes

    def walk(path):
        with MANUAL_COMPACT_POOL._cv:
            at_work = (MANUAL_COMPACT_POOL.running,
                       len(MANUAL_COMPACT_POOL._waiting))
        n = workdir_bytes(path)
        walks.append((time.perf_counter(), at_work, n))
        return n

    monkeypatch.setattr(harness, "workdir_bytes", walk)
    # the sim's timers, which carry a trigger to the replicas, fire
    # every 3 s: the rehearsal's 2 s window would close before the
    # first trigger arrived, so this one lasts 4 s. And 400 records
    # compact in a few ms a replica, where a pass over the 192 replicas
    # takes ~100 s at the cell's size: 20 ms before each run make a
    # pass (3.8 s) outlast what is left of the window
    submit = MANUAL_COMPACT_POOL.submit

    def slow_submit(owner, fn, name, **kw):
        submit(owner, lambda: (time.sleep(0.02), fn()), name, **kw)

    monkeypatch.setattr(MANUAL_COMPACT_POOL, "submit", slow_submit)
    with _time_limit(300):
        res, line = rehearse_cell("ycsb_e_compact.p64r3", seed, True,
                                  seconds=4.0)
    assert res["correct"], line["checks"]
    assert all(c["value"] == 0 for c in line["checks"].values())
    assert res["attempted"] > 0
    (walked_at, (running, waiting), on_disk), = walks
    print(f"at the walk: {running} compaction(s) running, {waiting} "
          f"waiting, {on_disk} bytes under the work directory")
    assert running >= 1, "no compaction ran when the window closed"
    assert on_disk == res["info"]["workdir_bytes_after_window"] > 0
    assert any(at > walked_at for at, _took in MANUAL_COMPACT_POOL.history), \
        "no compaction finished after the walk"
    assert {"compact_passes_in_window", "compact_rules_dropped_rows",
            "compact_in_MB_s"} <= set(line["metrics"])
    # the cluster's close drained the pool: nothing was writing under
    # the work directory while it was removed
    assert MANUAL_COMPACT_POOL.wait_idle(0)
