"""The control and the planted faults make a run come out not correct;
a sound run comes out correct. Drives harness.run_cell (everything of a
run but run.py's look for a chip) on the CPU at a tiny size. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_faults.py -q -p no:cacheprovider
"""

import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.faults import FAULTS  # noqa: E402
from benchmarks.harness import run_cell  # noqa: E402
from benchmarks.run import load_cell  # noqa: E402

CELLS = ("ycsb_e.p64r3", "ycsb_c.p4r1")
# the check each fault has to fail in each cell (a cell without writes
# in its window loses its acknowledged writes in the load)
FAILS = {
    ("lost_write", "ycsb_e.p64r3"): "missing_readbacks",
    ("lost_write", "ycsb_c.p4r1"): "wrong_answers",
    ("half_batch", "ycsb_e.p64r3"): "wrong_answers",
    ("half_batch", "ycsb_c.p4r1"): "wrong_answers",
    ("altered_answer", "ycsb_e.p64r3"): "wrong_answers",
    ("altered_answer", "ycsb_c.p4r1"): "wrong_answers",
    ("lost_reply", "ycsb_e.p64r3"): "failed_ops",
    ("lost_reply", "ycsb_c.p4r1"): "failed_ops",
    # one replica has nobody to disagree with: not a fault of that cell
    ("no_group_check", "ycsb_e.p64r3"): "replica_decree_spread",
}


def tiny_run(cell_name, fault):
    _bench, cell, config, traffic = load_cell(cell_name)
    traffic = dict(traffic, warmup_windows=4)
    return run_cell(cell["name"], dict(config, records=400), traffic,
                    2_400_000_011, 1.0, False, time.perf_counter(),
                    fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = tiny_run(cell, None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_every_fault_is_planted_somewhere():
    assert {fault for fault, _cell in FAILS} == set(FAULTS)


@pytest.mark.parametrize("fault,cell", sorted(FAILS))
def test_fault_is_not_correct(fault, cell):
    res = tiny_run(cell, fault)
    assert not res["correct"], res["checks"]
    failed = {n for n, (value, limit) in res["checks"].items()
              if value > limit}
    assert FAILS[fault, cell] in failed
    if fault != "lost_reply":   # wrong answers, not missing ones
        assert "failed_ops" not in failed
