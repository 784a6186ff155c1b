"""From a profiler trace to device busy/idle time, per-op device time
and the idle gaps labelled by what the harness was doing.

`load_xplane` turns the profiler's .xplane.pb into plain lists;
`reduce_trace` works on those lists alone, so tests/test_trace_reduce.py
checks it on a hand-built trace.

    planes = [(plane_name, [(line_name, [(event_name, start_ns, dur_ns)])])]
"""

from __future__ import annotations

import glob
import json
import os

DEVICE_PLANE_PREFIXES = ("/device:TPU:", "/device:GPU:")
# the line of a device plane that holds one event per executed operation;
# the others (steps, modules, framework names) cover the same time again
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SPAN_PREFIX = "bench."          # the harness's own TraceAnnotations
SLICE_SPAN = "bench.slice"      # wraps the traced part of the window


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(peaks)})")
    return peaks[device_kind]


def load_xplane(trace_dir: str):
    """The newest .xplane.pb under trace_dir as plain lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [(plane.name,
             [(line.name, [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events])
              for line in plane.lines])
            for plane in data.planes]


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_trace(planes, n_chips: int = 1) -> dict:
    """busy_s and window_s (seconds, busy averaged over the chips used),
    idle_share (%), device_ops [[name, s]] and idle_gaps [[span, s]],
    ten of each at most.

    The window is the harness's `bench.slice` span; device events are
    clipped to it. A slice in which no device operation ran reads
    busy_s 0 and idle_share 100."""
    spans = []          # (name, start, end) of the harness's annotations
    device = []         # per device plane: (ops events, module events)
    for plane_name, lines in planes:
        if plane_name.startswith(DEVICE_PLANE_PREFIXES):
            ops = [e for ln, evs in lines if ln in OP_LINES for e in evs]
            mods = [e for ln, evs in lines if ln in MODULE_LINES for e in evs]
            device.append(ops or mods)
        else:
            spans += [(n, s, s + d) for _ln, evs in lines
                      for n, s, d in evs if n.startswith(SPAN_PREFIX)]
    slices = [(s, e) for n, s, e in spans if n == SLICE_SPAN]
    if not slices:
        raise ValueError(f"the trace holds no {SLICE_SPAN!r} span")
    lo, hi = min(s for s, _e in slices), max(e for _s, e in slices)
    window_s = (hi - lo) / 1e9

    busy_ns = 0
    op_seconds = {}
    busiest = []
    for events in device:
        merged = _union(_clip([(s, s + d) for _n, s, d in events], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        if sum(e - s for s, e in merged) >= sum(e - s for s, e in busiest):
            busiest = merged
        for n, s, d in events:
            for cs, ce in _clip([(s, s + d)], lo, hi):
                op_seconds[n] = op_seconds.get(n, 0.0) + (ce - cs) / 1e9
    busy_s = busy_ns / 1e9 / max(1, n_chips)

    # idle gaps of the busiest chip, each second of them given to the
    # innermost harness span that covers it
    gaps, at = [], lo
    for s, e in busiest:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    # the harness's spans do not nest (one per client call, one per
    # bookkeeping step), so one sweep gives each gap its spans
    flat = sorted((s, e, n) for n, s, e in spans if n != SLICE_SPAN)
    gap_seconds = {}
    i = 0
    for gs, ge in gaps:
        while i < len(flat) and flat[i][1] <= gs:
            i += 1
        covered, j = 0, i
        while j < len(flat) and flat[j][0] < ge:
            cs, ce = max(gs, flat[j][0]), min(ge, flat[j][1])
            if ce > cs:
                n = flat[j][2]
                gap_seconds[n] = gap_seconds.get(n, 0.0) + (ce - cs) / 1e9
                covered += ce - cs
            j += 1
        rest = (ge - gs - covered) / 1e9
        if rest > 0:
            gap_seconds["between_spans"] = (
                gap_seconds.get("between_spans", 0.0) + rest)

    def top(d):
        return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:10]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": top(op_seconds), "idle_gaps": top(gap_seconds)}
