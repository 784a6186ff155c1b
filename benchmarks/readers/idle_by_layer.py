"""Reader `idle_by_layer`: the share of the traced slice, in %, in which
the busiest chip was idle while the thread that drives the client was
inside one of the program's layers `layers` (`tracing.LAYER_KEYS`, or
`outside_program`): 100 x their idle seconds / window_s, from
trace_layers.idle_by_layer.

  "layers": [key, ...]

The harness hands a reader the reduced trace and not the profile, which
lies in the run's work directory (harness.run_cell: `pegasus_bench_*`
under the temp directory, `trace/`): of the profiles written there
since `begin`, this run's is the one whose slice has the reduced
trace's `window_s`. The whole split is logged once a profile. Nothing
to read: an untraced run, no such profile.
"""

import glob
import os
import sys
import tempfile
import time

from benchmarks.trace_layers import idle_by_layer, load_program_trace

_READ = {}          # profile path -> idle_by_layer's result


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:         # another run's, removed meanwhile
        return 0.0


def _profile(since: float, window_s: float):
    paths = glob.glob(os.path.join(
        tempfile.gettempdir(), "pegasus_bench_*", "trace", "plugins",
        "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=_mtime, reverse=True):
        if _mtime(path) < since:
            break
        if path not in _READ:
            try:
                _READ[path] = idle_by_layer(load_program_trace(path))
            except OSError:
                continue
            r = _READ[path]
            split = " ".join(f"{k}={s:.6f}" for k, s in r["idle_by_layer"]
                             if s)
            print(f"[idle_by_layer] slice {r['window_s']:.6f} s, "
                  f"{r['harness_calls']} harness calls, "
                  f"{r['program_events']} program events, idle "
                  f"{r['idle_s']:.6f} s: {split}", file=sys.stderr,
                  flush=True)
        if _READ[path]["window_s"] == window_s:
            return _READ[path]
    return None


def begin(spec):
    return time.time()


def read(spec, before, run):
    if run["trace"] is None:
        return None
    found = _profile(before, run["trace"]["window_s"])
    if found is None:
        return None
    idle = dict(found["idle_by_layer"])
    return 100.0 * sum(idle[k] for k in spec["layers"]) / found["window_s"]
