"""trace_layers.idle_by_layer on hand-built traces, and the reader
`idle_by_layer` on a profile recorded on the CPU. Run by hand:

    python3 -m pytest benchmarks/tests/test_trace_layers.py -q -p no:cacheprovider
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.trace_layers import KEYS, idle_by_layer  # noqa: E402
from benchmarks.trace_reduce import reduce_trace  # noqa: E402

MS = 1_000_000  # ns


def threads(**lines):
    """A host plane of one line a thread: (name, start ms, length ms)."""
    return ("/host:CPU", [(name, [(n, s * MS, d * MS) for n, s, d in evs])
                          for name, evs in lines.items()])


def chip(i, *ops):
    return (f"/device:TPU:{i}", [("XLA Ops", [(n, s * MS, d * MS)
                                              for n, s, d in ops])])


def split(planes):
    r = idle_by_layer(planes)
    return r, {k: s for k, s in r["idle_by_layer"] if s}


def test_the_innermost_event_takes_the_idle_time():
    main = [("bench.slice", 0, 1000), ("bench.send.scan_prefix", 0, 900),
            ("pegasus.client.scan", 0, 900),
            ("pegasus.rpc.deliver", 100, 700),
            ("pegasus.dispatch.wave", 200, 400),
            ("pegasus.dispatch.fetch", 300, 300)]
    planes = [threads(main=main), chip(0, ("fusion", 400, 100))]
    r, idle = split(planes)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["idle_s"] == pytest.approx(0.9)
    # [0,100] + [800,900] client, [100,200] + [600,800] rpc, [200,300]
    # the wave, [300,400] + [500,600] the fetch, [900,1000] no program
    # event
    assert idle == {"client": pytest.approx(0.2), "rpc": pytest.approx(0.3),
                    "dispatch": pytest.approx(0.3),
                    "outside_program": pytest.approx(0.1)}
    assert [k for k, _s in r["idle_by_layer"]] == list(KEYS)
    # busy + idle by layer is the slice, and the harness's own
    # reduction of the same trace is what it was
    old = reduce_trace(planes)
    assert old["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])
    assert old["window_s"] == r["window_s"]
    assert old["idle_gaps"] == [["bench.send.scan_prefix",
                                 pytest.approx(0.8)],
                                ["between_spans", pytest.approx(0.1)]]


def test_a_stage_event_is_keyed_by_its_stage():
    main = [("bench.slice", 0, 100), ("pegasus.client.scan", 0, 100),
            ("pegasus.client_scan_multi", 10, 80),
            ("pegasus.stage.plan", 10, 20), ("pegasus.stage.decode", 30, 40),
            ("pegasus.overlay.snapshot", 40, 10)]
    _r, idle = split([threads(main=main)])
    assert idle == {"client": pytest.approx(0.02), "rpc": pytest.approx(0.02),
                    "coord": pytest.approx(0.02),
                    "decode": pytest.approx(0.03),
                    "overlay": pytest.approx(0.01)}


def test_only_the_thread_that_holds_the_slice_is_swept():
    planes = [threads(main=[("bench.slice", 0, 100),
                            ("pegasus.client.scan", 0, 50)],
                      pool=[("pegasus.compact.run", 0, 100),
                            ("pegasus.stage.compact_merge", 0, 100)]),
              chip(0, ("fusion", 60, 10))]
    r, idle = split(planes)
    assert idle == {"client": pytest.approx(0.05),
                    "outside_program": pytest.approx(0.04)}
    assert reduce_trace(planes)["busy_s"] + r["idle_s"] == pytest.approx(0.1)


def test_the_busiest_chip_is_the_one_swept():
    planes = [threads(main=[("bench.slice", 0, 100),
                            ("pegasus.dispatch.wave", 0, 100)]),
              chip(0, ("a", 0, 10)), chip(1, ("a", 0, 30), ("b", 20, 30))]
    r, idle = split(planes)
    assert idle == {"dispatch": pytest.approx(0.05)}
    # busy averaged over both chips, as before: (10 + 50) / 2 ms
    assert reduce_trace(planes, n_chips=2)["idle_share"] == pytest.approx(
        70.0)


def test_a_trace_without_the_slice_is_an_error():
    with pytest.raises(ValueError):
        idle_by_layer([threads(main=[("pegasus.client.scan", 0, 10)])])


def test_the_reader_finds_the_run_profile_and_its_stage_names(
        tmp_path, monkeypatch):
    """The program's own frames in a CPU profile, where the harness
    keeps it: the reader picks the profile out by the slice's length,
    names the stage intervals, and all of the slice is idle."""
    import tempfile

    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    from benchmarks.readers import idle_by_layer as reader
    from benchmarks.trace_reduce import load_xplane
    from pegasus_tpu.utils import tracing

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    trace_dir = tmp_path / "pegasus_bench_x" / "trace"
    spec = {"layers": ["coord"]}
    before = reader.begin(spec)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        assert tracing.profiling()      # what a client call asks first
        with TraceAnnotation("bench.slice"):
            ring = tracing.ring_for("reader-test")
            root = ring.start("client.scan", profiled=True)
            with tracing.activate(root):
                tracing.begin_stages()
                sum(range(200_000))
                tracing.mark("plan")
                with tracing.layer("dispatch.wave"):
                    sum(range(200_000))
    finally:
        jax.profiler.stop_trace()
    run = {"trace": reduce_trace(load_xplane(str(trace_dir)))}
    share = reader.read(spec, before, run)
    assert 0 < share < 100
    (r,) = [v for v in reader._READ.values()
            if v["window_s"] == run["trace"]["window_s"]]
    idle = dict(r["idle_by_layer"])
    assert idle["coord"] > 0 and idle["dispatch"] > 0
    assert sum(idle.values()) == pytest.approx(r["window_s"])
    assert reader.read({"layers": list(KEYS)}, before, run) == \
        pytest.approx(100.0)
    # nothing to read untraced, or where no profile is this run's
    assert reader.read(spec, before, {"trace": None}) is None
    assert reader.read(spec, before, {"trace": dict(
        run["trace"], window_s=run["trace"]["window_s"] + 1)}) is None
