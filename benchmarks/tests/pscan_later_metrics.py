"""The metrics of `prefix_scan.p64r3` added after the cell itself, joined
to the lists with which test_prefix_scan_cell.py names the cell's
metrics exactly (its SHARES are reported by a CPU-traced run and lie in
0-100 %, its COUNTS are reported by one). benchmarks/ grows by new
files only (README), so the lists grow here: `pscan_later_metrics` is
an autouse fixture, imported where that module's tests are collected
(benchmarks/tests/conftest.py, tests/test_benchmarks_own.py), that
extends them for the duration of each of its tests.
"""

import pytest

CELL = "prefix_scan.p64r3"
# the device's idle seconds under the program's layers (reader
# idle_by_layer): a CPU-traced run has no device, so all is idle there
SHARES = ("pscan_idle_under_dispatch_share",
          "pscan_idle_under_paging_share")
# the mask dispatch's three steps, host ms a program (counter_window)
COUNTS = ("pscan_mask_stack_ms_per_program",
          "pscan_mask_launch_ms_per_program",
          "pscan_mask_fetch_ms_per_program")


@pytest.fixture(autouse=True)
def pscan_later_metrics(request, monkeypatch):
    names = request.function.__globals__
    if names.get("CELL") == CELL and "SHARES" in names \
            and "COUNTS" in names:
        monkeypatch.setitem(names, "SHARES", names["SHARES"] + SHARES)
        monkeypatch.setitem(names, "COUNTS", names["COUNTS"] + COUNTS)
