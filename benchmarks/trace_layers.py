"""From a profiler trace to the device's idle seconds by the program's
own layer.

In a profiler session every frame of the program's span tree is a
`TraceAnnotation("pegasus.<span>")` and every stage interval an event
`pegasus.stage` with the stage as its `stage` stat
(pegasus_tpu/utils/tracing.py), on the profile's own clock beside the
device's operations. `idle_by_layer` gives each idle second of the
busiest chip inside the harness's `bench.slice` to the innermost such
event of the thread that holds the slice, the one that drives the
client: the event with the latest start of those that cover it, keyed
by `tracing.layer_of` of its span's or its stage's name. An idle second
under none goes to `outside_program`. Other threads (a compaction
pool's) are not swept: they do not stand between the client and the
chip.

`load_program_trace` reads a .xplane.pb into trace_reduce's plain lists,
except that a `pegasus.stage` event is named `pegasus.stage.<stage>`
and one without a stage (the interval after a frame's last stage point,
which is its frame's) is left out; `idle_by_layer` works on those lists
alone, so tests/test_trace_layers.py checks it on a hand-built trace.
"""

from __future__ import annotations

from benchmarks.trace_reduce import (DEVICE_PLANE_PREFIXES, MODULE_LINES,
                                     OP_LINES, SLICE_SPAN, _clip, _union)
from pegasus_tpu.utils.tracing import LAYER_KEYS, layer_of

PROGRAM_PREFIX = "pegasus."
STAGE_EVENT = "pegasus.stage"
OUTSIDE = "outside_program"
KEYS = LAYER_KEYS + (OUTSIDE,)


def load_program_trace(path: str):
    """The .xplane.pb at `path` as [(plane, [(line, [(name, start_ns,
    dur_ns)])])], stage events named by their stage."""
    from jax.profiler import ProfileData

    def events(line):
        out = []
        for ev in line.events:
            name = ev.name
            if name == STAGE_EVENT:
                stage = dict(ev.stats).get("stage")
                if not stage:
                    continue
                name = f"{STAGE_EVENT}.{stage}"
            out.append((name, int(ev.start_ns), int(ev.duration_ns)))
        return out

    data = ProfileData.from_file(path)
    return [(plane.name, [(line.name, events(line)) for line in plane.lines])
            for plane in data.planes]


def _key(name: str) -> str:
    name = name[len(PROGRAM_PREFIX):]
    if name.startswith("stage."):
        name = name[len("stage."):]
    return layer_of(name)


def _innermost(events):
    """[(start, end, key)] of nested events -> [(start, end, key)]
    pieces, sorted and disjoint, each time covered by the event with the
    latest start of those covering it."""
    pieces, stack, at = [], [], None     # stack: (end, key), outer first
    for s, e, k in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, key = stack.pop()
            if end > at:
                pieces.append((at, end, key))
                at = end
        if stack and s > at:
            pieces.append((at, s, stack[-1][1]))
        at = s
        stack.append((e, k))
    while stack:
        end, key = stack.pop()
        if end > at:
            pieces.append((at, end, key))
            at = end
    return pieces


def idle_by_layer(planes) -> dict:
    """window_s (the slice), idle_s (the busiest chip's idle seconds in
    it), idle_by_layer [[key, s]] over tracing.LAYER_KEYS and
    `outside_program`, which sums to idle_s; program_events (the
    swept thread's frames and stage intervals) and harness_calls (its
    `bench.send.*` spans) in the profile."""
    device, slice_line = [], None
    for plane_name, lines in planes:
        if plane_name.startswith(DEVICE_PLANE_PREFIXES):
            ops = [e for ln, evs in lines if ln in OP_LINES for e in evs]
            mods = [e for ln, evs in lines if ln in MODULE_LINES for e in evs]
            device.append(ops or mods)
            continue
        for _ln, evs in lines:
            if any(n == SLICE_SPAN for n, _s, _d in evs):
                slice_line = evs
    if slice_line is None:
        raise ValueError(f"the trace holds no {SLICE_SPAN!r} span")
    slices = [(s, s + d) for n, s, d in slice_line if n == SLICE_SPAN]
    lo, hi = min(s for s, _e in slices), max(e for _s, e in slices)

    busiest = []
    for events in device:
        merged = _union(_clip([(s, s + d) for _n, s, d in events], lo, hi))
        if sum(e - s for s, e in merged) >= sum(e - s for s, e in busiest):
            busiest = merged
    gaps, at = [], lo
    for s, e in busiest:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))

    program = [(s, s + d, _key(n)) for n, s, d in slice_line
               if n.startswith(PROGRAM_PREFIX)]
    pieces = _innermost(program)
    idle = dict.fromkeys(KEYS, 0)
    i = 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        covered, j = 0, i
        while j < len(pieces) and pieces[j][0] < ge:
            cs, ce = max(gs, pieces[j][0]), min(ge, pieces[j][1])
            if ce > cs:
                idle[pieces[j][2]] += ce - cs
                covered += ce - cs
            j += 1
        idle[OUTSIDE] += ge - gs - covered
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": sum(ge - gs for gs, ge in gaps) / 1e9,
            "idle_by_layer": [[k, ns / 1e9] for k, ns in idle.items()],
            "program_events": len(program),
            "harness_calls": sum(1 for n, _s, _d in slice_line
                                 if n.startswith("bench.send."))}
