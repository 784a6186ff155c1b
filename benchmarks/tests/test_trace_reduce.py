"""trace_reduce on a hand-built trace. Run by hand:

    python3 -m pytest benchmarks/tests/test_trace_reduce.py -q -p no:cacheprovider
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.trace_reduce import peaks_for, reduce_trace  # noqa: E402

MS = 1_000_000  # ns


def host(*spans):
    return ("/host:CPU", [("python3", [(n, s * MS, d * MS)
                                       for n, s, d in spans])])


def device(i, **lines):
    return (f"/device:TPU:{i}", [(name.replace("_", " "),
                                  [(n, s * MS, d * MS) for n, s, d in evs])
                                 for name, evs in lines.items()])


def test_overlapping_ops_count_once_and_gaps_are_labelled():
    planes = [
        host(("bench.slice", 0, 1000), ("bench.send.scan", 0, 600),
             ("bench.send.insert", 600, 300), ("other", 0, 1000)),
        # modules cover the same time again: not added to busy
        device(0, XLA_Ops=[("fusion.1", 100, 100), ("fusion.2", 150, 100),
                           ("copy", 700, 50), ("before_slice", -500, 100)],
               XLA_Modules=[("jit_f", 100, 150), ("jit_g", 700, 50)]),
    ]
    r = reduce_trace(planes)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.2)   # [100,250] + [700,750]
    assert r["idle_share"] == pytest.approx(80.0)
    ops = dict(r["device_ops"])
    assert ops == {"fusion.1": pytest.approx(0.1),
                   "fusion.2": pytest.approx(0.1),
                   "copy": pytest.approx(0.05)}
    gaps = dict(r["idle_gaps"])
    # idle: [0,100] + [250,600] under scan, [600,700] + [750,900] under
    # insert, [900,1000] under no span of the harness
    assert gaps["bench.send.scan"] == pytest.approx(0.45)
    assert gaps["bench.send.insert"] == pytest.approx(0.25)
    assert gaps["between_spans"] == pytest.approx(0.1)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_slice_without_a_device_op_reads_all_idle():
    r = reduce_trace([host(("bench.slice", 0, 500),
                           ("bench.send.get", 0, 500))])
    assert r["busy_s"] == 0
    assert r["idle_share"] == 100.0
    assert r["device_ops"] == []
    assert r["idle_gaps"] == [["bench.send.get", pytest.approx(0.5)]]


def test_busy_is_averaged_over_the_chips_used():
    planes = [host(("bench.slice", 0, 1000)),
              device(0, XLA_Ops=[("a", 0, 400)]),
              device(1, XLA_Ops=[("a", 0, 200)])]
    r = reduce_trace(planes, n_chips=2)
    assert r["busy_s"] == pytest.approx(0.3)
    assert r["idle_share"] == pytest.approx(70.0)


def test_modules_stand_in_where_a_plane_has_no_op_line():
    planes = [host(("bench.slice", 0, 1000)),
              device(0, XLA_Modules=[("jit_f", 0, 250)])]
    assert reduce_trace(planes)["busy_s"] == pytest.approx(0.25)


def test_a_trace_without_the_slice_span_is_an_error():
    with pytest.raises(ValueError):
        reduce_trace([host(("bench.send.get", 0, 10))])


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
