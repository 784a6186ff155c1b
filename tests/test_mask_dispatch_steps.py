"""The mask dispatch in three steps (server/scan_coordinator.py): inside
a traced root a wave of mask programs is a `dispatch.wave` frame with
`dispatch.stack`, `dispatch.launch` and `dispatch.fetch` children, and
with tracing off the always-on counters `engine`/`mask_stack_us`,
`mask_launch_us` and `mask_fetch_us` add up to the wall time the drift
audit records. Small blocks of 32 rows on the CPU, one partition.
"""

import threading

import jax
import numpy as np
import pytest

from pegasus_tpu.base.key_schema import generate_key
from pegasus_tpu.ops.predicates import FT_MATCH_PREFIX, FT_NO_FILTER
from pegasus_tpu.ops.record_block import build_record_block
from pegasus_tpu.server.scan_coordinator import (
    STACK_CHUNK,
    _eval_cross_partition_multi,
    stacked_block_eval,
)
from pegasus_tpu.utils import perf_context as perf
from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.metrics import METRICS

ROWS = 32
STEPS = ("stack", "launch", "fetch")
NODE = "mask-steps"


def _block(i):
    keys = [generate_key(b"user%04d" % (i * ROWS + r), b"f") for r in
            range(ROWS)]
    return jax.tree.map(jax.numpy.asarray,
                        build_record_block(keys, [0] * ROWS, capacity=ROWS))


@pytest.fixture(scope="module")
def blocks():
    return [(i, _block(i), 0) for i in range(STACK_CHUNK)]


def _engine():
    """mask programs and the three step counters, summed."""
    out = dict.fromkeys(("programs",) + STEPS, 0)
    for ent in METRICS.snapshot(entity_type="engine"):
        m = ent["metrics"]
        if "mask_programs" in m:
            out["programs"] += m["mask_programs"]["value"]
            for s in STEPS:
                out[s] += m[f"mask_{s}_us"]["value"]
    return out


def _layer(node):
    return {k: v["value"] for ent in METRICS.snapshot(entity_type="layer")
            if ent["id"] == node for k, v in ent["metrics"].items()}


def _wave(blocks):
    return [(tag, np.asarray(keep)) for tag, keep in stacked_block_eval(
        blocks, False, 0, filter_key=(FT_MATCH_PREFIX, b"user00",
                                      FT_NO_FILTER, b""))]


@pytest.mark.parametrize("height", [STACK_CHUNK, 1])
def test_a_wave_is_three_child_frames_of_dispatch_wave(blocks, height):
    _wave(blocks[:height])                  # compiled outside the root
    ring = tracing.ring_for(NODE)
    ring.clear()
    before = _layer(NODE)
    root = ring.start("client.scan")
    with tracing.activate(root):
        masks = _wave(blocks[:height])
    root.finish()
    assert [tag for tag, _m in masks] == list(range(height))
    spans = ring.dump(root.trace_id)
    by_id = {s["span"]: s for s in spans}
    (wave,) = [s for s in spans if s["name"] == "dispatch.wave"]
    assert wave["parent"] == root.span_id
    kids = sorted(s["name"] for s in spans if s["parent"] == wave["span"])
    want = ["dispatch.fetch", "dispatch.launch"]
    if height > 1:
        want.append("dispatch.stack")       # a single block is not stacked
    assert kids == sorted(want)
    assert {by_id[s["parent"]]["name"] for s in spans
            if s["name"].startswith("dispatch.") and s is not wave} \
        == {"dispatch.wave"}
    # the steps move time inside `dispatch`, never out of it: every
    # nanosecond of the root is some frame's self time, whole
    # microseconds differ by the remainders the counters hold back
    d = {k: v - before.get(k, 0) for k, v in _layer(NODE).items()}
    traced = d.pop("traced_us")
    assert traced > 0
    assert abs(sum(d.values()) - traced) <= len(spans)
    assert d.get("other_self_us", 0) == 0
    assert d["dispatch_self_us"] > 0


@pytest.mark.parametrize("height", [STACK_CHUNK, 3, 1])
def test_the_step_counters_add_up_to_the_audited_wall(blocks, height):
    _wave(blocks[:height])
    assert not tracing.profiling() and tracing.frame_span() is None
    before = _engine()
    pc = perf.PerfContext("scan")
    with perf.activate(pc):
        _wave(blocks[:height])
    d = {k: v - before[k] for k, v in _engine().items()}
    assert d["programs"] == 1
    assert d["launch"] > 0 and d["fetch"] > 0
    if height > 1:
        assert d["stack"] > 0
    # one wave: each step's count drops under a microsecond
    wall_us = pc.measured_kernel_ms * 1000.0
    assert sum(d[s] for s in STEPS) <= wall_us <= sum(
        d[s] for s in STEPS) + len(STEPS)


class _Server:
    """What the multi-flavor wave asks of a partition server."""

    pidx = 0
    _mask_lock = threading.Lock()
    _warm_flavors = {}

    def __init__(self):
        self.stored = []

    def store_mask_for(self, ckey, validate, fkey, keep, computed_pv):
        self.stored.append((ckey, fkey))


def test_the_multi_flavor_wave_has_the_same_steps(blocks):
    flavors = {(FT_MATCH_PREFIX, p, FT_NO_FILTER, b""): None
               for p in (b"user00", b"user01")}

    def run():
        srv = _Server()
        states = [{"cached_keep": {}} for _f in flavors]
        _eval_cross_partition_multi(
            {fkey: [(srv, st, i, dev) for i, dev, _p in blocks[:4]]
             for fkey, st in zip(flavors, states)}, False, 0)
        return srv, states

    run()                                   # compiled outside the root
    ring = tracing.ring_for(NODE)
    ring.clear()
    before = _engine()
    root = ring.start("client.scan")
    with tracing.activate(root):
        srv, states = run()
    root.finish()
    assert len(srv.stored) == 2 * 4 and all(
        sorted(st["cached_keep"]) == [0, 1, 2, 3] for st in states)
    spans = ring.dump(root.trace_id)
    (wave,) = [s for s in spans if s["name"] == "dispatch.wave"]
    assert sorted(s["name"] for s in spans
                  if s["parent"] == wave["span"]) == [
        "dispatch.fetch", "dispatch.launch", "dispatch.stack"]
    d = {k: v - before[k] for k, v in _engine().items()}
    assert d["stack"] > 0 and d["launch"] > 0 and d["fetch"] > 0
