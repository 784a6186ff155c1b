"""The cell `prefix_scan.p64r3` reads the mask dispatch from inside: the
host time of its three steps a program (always on) and the device's
idle time under the program's layers (traced), on test_prefix_scan_cell's
CPU-traced run at a rehearsal's size:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_prefix_scan_steps.py -q -p no:cacheprovider
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))))

from benchmarks.readers import idle_by_layer  # noqa: E402
from benchmarks.run import result_line  # noqa: E402
from benchmarks.tests import pscan_later_metrics as later  # noqa: E402
from benchmarks.tests.test_prefix_scan_cell import (  # noqa: E402,F401
    pscan_traced,
)
from benchmarks.trace_layers import KEYS  # noqa: E402


def test_pscan_steps_fit_the_window_and_idle_shares_split_the_slice(
        pscan_traced):
    bench, cell, res = pscan_traced
    line = result_line(bench, cell, res, True, {"platform": "cpu-test"})
    values = {n: m["value"] for n, m in line["metrics"].items()}
    assert set(later.SHARES + later.COUNTS) <= set(values)
    # every program stacked, launched and fetched; all the window's
    # programs' steps fit inside the window, one after another
    steps_ms = sum(values[n] for n in later.COUNTS)
    assert all(values[n] > 0 for n in later.COUNTS), values
    programs = (values["pscan_mask_programs_per_op"]
                * res["info"]["ops_by_kind"]["scan_prefix"])
    assert programs * steps_ms < 1000.0 * res["info"]["window_s"]
    # a CPU has no device plane: the whole slice is idle, and every key
    # of the split together reads the device's idle share (the reader
    # kept the split of the run's profile, which went with its work
    # directory)
    dispatch = values["pscan_idle_under_dispatch_share"]
    paging = values["pscan_idle_under_paging_share"]
    assert dispatch > 0 and paging > 0 and dispatch + paging <= 100.0
    (split,) = [r for r in idle_by_layer._READ.values()
                if r["window_s"] == res["trace"]["window_s"]]
    assert [k for k, _s in split["idle_by_layer"]] == list(KEYS)
    whole = 100.0 * sum(s for _k, s in split["idle_by_layer"]) \
        / split["window_s"]
    assert abs(whole - values["pscan_device_idle_share"]) < 0.5
