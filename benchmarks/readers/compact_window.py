"""Reader `compact_window`: counter_window for the counters that the
manual compaction pool's runs move, read as of a moment the harness
does not read at.

The harness reads a per-layer metric after it has verified the
window's answers: half a minute in which the pool's queue runs on with
no foreground beside it, so a counter's delta holds the window and
that. The pool keeps when each run finished
(`ManualCompactPool.history`, on time.perf_counter), and `begin` is
called as the window opens:

  "as_of": "window_end"  the delta times the share of the runs
                         finished since `begin` that finished before
                         begin + window_s: exact for a count of runs,
                         and for their bytes and rows as far as one
                         replica's compaction is like another's
                         (the partitions of one table)
           "drained"     wait (outside the window, DRAIN_S at most)
                         until nothing runs or waits in the pool, then
                         read: every replica that heard a trigger has
                         compacted. Logs the count beside the
                         reference's (ops/scan_rules.py: 3 replicas x
                         the live rows a delete rule matches), which
                         G4 holds it to.

Otherwise counter_window's spec. Nothing to read from a program
without the pool (the parent of the PR that brought it).
"""

import sys
import time

from benchmarks.readers import counter_window

DRAIN_S = 240.0


def _pool():
    from pegasus_tpu.storage import compact_governor

    return getattr(compact_governor, "MANUAL_COMPACT_POOL", None)


def begin(spec):
    return time.perf_counter(), counter_window.begin(spec)


def read(spec, before, run):
    t0, sums = before
    pool = _pool()
    if pool is None:
        return None
    if spec["as_of"] == "drained":
        t_wait = time.perf_counter()
        idle = pool.wait_idle(DRAIN_S)
        value = counter_window.read(spec, sums, run)
        from benchmarks.ops import scan_rules

        print(f"[compact_window] {spec['name']}: {value} once the pool "
              f"{'had drained' if idle else 'was given up on'} "
              f"({time.perf_counter() - t_wait:.1f}s after the "
              f"verification); the reference: 3 x "
              f"{scan_rules.MATCHED_LIVE} live rows a delete rule matches",
              file=sys.stderr, flush=True)
        return value
    value = counter_window.read(spec, sums, run)
    if value is None:
        return None
    done = [at for at, _took in list(pool.history) if at >= t0]
    inside = sum(1 for at in done if at <= t0 + run["window_s"])
    return value * inside / len(done) if done else value
