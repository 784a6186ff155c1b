"""Reader `filter_roofline`: the predicate programs' share of the
chip's memory roofline inside the traced slice, in %.

    bytes the programs were handed and gave back while the profiler
    session was on  /  the device's busy seconds in the slice
    /  the chip's HBM bytes per second (peaks.json)

The bytes are counted by the program where it dispatches one
(ops/compaction.py note_filter_program), by PERF.md's formula: padded
rows x (key-matrix row bytes + key_len, hashkey_len, expire_ts
[, pidx] at 4 B + valid) in, the mask (bit-packed on the bulk path)
and the rewritten expire_ts back: `engine`/`filter_bytes_traced`, the
compaction filter programs. The seconds are reduce_trace's `busy_s`,
all that ran on the device in the slice. This reader is for a mix
that sends no trace_probe; what else reaches the device there is the
read path's static mask program, for a scan batch whose plan a publish
tore, and the device's operation names do not tell it from a filter
program. So its bytes count too (`engine`/`mask_bytes_traced`, the
same formula without expire_ts, plus a stack's gather), and numerator
and denominator cover the same programs. Nothing to read: an untraced
run, a program without the counters, a slice without a device
operation or a dispatched program, a device kind peaks.json does not
know (the CPU rehearsal).
"""

import jax

from benchmarks.readers import counter_window
from benchmarks.trace_reduce import peaks_for

_BYTES = {"numerator": [["engine", "filter_bytes_traced"],
                        ["engine", "mask_bytes_traced"]],
          "per": {"constant": 1}}


def program_bytes(kind: str, rows: int, key_width: int,
                  hash_lo: bool = False, want_ets: bool = True,
                  stacked: bool = False) -> int:
    """The bytes one program over `rows` padded rows is handed and
    gives back: what note_filter_program has to have counted
    (tests/test_rules_cell.py holds the program to it). `kind`:
    "rules" (compaction_rules' program of the per-record path),
    "ttl" (compaction_filter_block after it: the rules' mask rides
    up too), "bulk" (the block path's fused program: a pidx column,
    the mask bit-packed) or "mask" (the read path's static mask: no
    expire_ts, the mask bit-packed; `stacked`: a pidx column, and the
    stack's six columns read and written once by the gather)."""
    if kind == "mask":
        return (rows * (key_width + 9 + 4 * hash_lo) + rows // 8
                + stacked * rows * (4 + 2 * (key_width + 17)))
    columns = {"rules": 13, "ttl": 14, "bulk": 17 + 4 * hash_lo}[kind]
    back = (rows // 8 + 4 * rows * want_ets if kind == "bulk"
            else 5 * rows)
    return rows * (key_width + columns) + back


def begin(spec):
    return counter_window.begin(_BYTES)


def read(spec, before, run):
    trace = run["trace"]
    if trace is None or not trace["device_ops"] or not trace["busy_s"]:
        return None
    nbytes = counter_window.read(_BYTES, before, run)
    if not nbytes:
        return None
    try:
        peak = peaks_for(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    except KeyError:
        return None
    return 100.0 * nbytes / trace["busy_s"] / peak
