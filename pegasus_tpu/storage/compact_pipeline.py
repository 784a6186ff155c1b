"""Staged bulk-compaction pipeline: read → filter → write, overlapped.

LUDA's result (PAPERS.md) is that GPU-offloaded LSM compaction wins by
RESTRUCTURING compaction into overlapped stages, not by faster
per-stage kernels — the same shape Pegasus' bulk path wants: block
reads are disk-bound, filter evaluation is accelerator- or CPU-bound
(device programs for ruleset batches, raw-column numpy for encoded
blocks, the GIL-free native subset kernel downstream), and the
compressed-write stage is CPU+disk-bound. Serially they add; staged
they hide behind the slowest one.

What selects (storage/engine.py `_manual_compact_bulk`, from what it
observes; no flag): a snapshot of more than one window
(PIPELINE_WINDOW entries) on a host of 4+ cores
(`stage_threads_enabled`) runs the stages below as threads; a smaller
snapshot, or fewer cores, runs the same three stage functions inline
on the calling thread, window by window: one window is read, then
filtered, then written, so threads would have nothing to overlap.

Topology (one threaded compaction = one pipeline; the inter-stage
queues are bounded so memory stays a few windows deep):

    READ thread    walks the snapshot's entries in key order (a block
                   that flows unchanged, or a splice of chain blocks
                   and overlay rows), reads the
                   raw/encoded block bytes (paced through the
                   CompactionGovernor token bucket — this is where
                   background IO meets the foreground-pressure
                   feedback), windows them
    FILTER thread  two-phase per window: submit the window's filter
                   programs (device or host XLA, per the placement
                   cost model; encoded blocks with key-free rulesets
                   evaluate host-direct off their raw predicate
                   columns), then drain the PREVIOUS window while this
                   one evaluates — the inline loop's one-window
                   device lookahead, kept inside the stage
    WRITE (caller) the consuming generator feeds
                   LSMStore.bulk_compact_rewrite unchanged: subset
                   kernel, async SST writers, threaded finish, and the
                   manifest-then-unlink publish ordering all stay
                   exactly where they were

Because the queues are FIFO and the stages preserve entry order, the
rewrite consumes the identical (block, drop-mask) stream the inline
loop would produce — the output is byte-identical by construction,
and the tests gate on a content digest to prove it stays that way.

Mesh-filtered mode: when the table's blocks are resident on the
device mesh (parallel/mesh_resident.py), the engine pre-computes the
WHOLE store's drop masks in one SPMD dispatch before the pipeline
starts; every window then arrives at the filter stage pre-served (no
in-flight program, eager-forwarded straight to WRITE), so the
pipeline degrades gracefully to read → write with the governor still
pacing reads. Same (block, mask) stream, same bytes.

Shutdown: any stage exception travels down the queues and re-raises in
the consumer; closing the consumer generator (writer failure) sets the
stop event, unblocks both queues, and joins the threads — no daemon
thread keeps reading a store whose compaction already failed.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

from pegasus_tpu.utils import tracing
from pegasus_tpu.utils.metrics import METRICS

# blocks per pipeline window (the unit the stages hand each other):
# bounds per-window memory and the filter batch size, and a snapshot
# of at most one window is what the engine runs inline
PIPELINE_WINDOW = 128
# windows each bounded inter-stage queue may hold: total in-flight
# memory is ~(2*depth + 2) windows
PIPELINE_DEPTH = 2


def pipeline_window() -> int:
    return PIPELINE_WINDOW


def pipeline_depth() -> int:
    return PIPELINE_DEPTH


def window_count(n_entries: int) -> int:
    """Windows a compaction over `n_entries` blocks will submit — the
    host filter stage pays one dispatch per window, which is the unit
    the mesh gate (ops/placement.mesh_compact_pays) weighs one
    whole-table SPMD dispatch against."""
    return max(1, -(-int(n_entries) // max(1, pipeline_window())))


def transform_workers() -> int:
    """Write-stage transform pool size: the subset kernel / gather
    work per block runs GIL-free, so the pipelined rewrite keeps up
    to cpu workers transforming ahead while the consumer thread
    appends in order (the consumer is mostly blocked on futures, so
    it does not need its own core)."""
    import os

    return max(2, min(4, os.cpu_count() or 2))


def stage_threads_enabled() -> bool:
    """Dedicated read/filter stage threads only pay when the box has
    cores for them: on a 2-core host the stage threads fight the
    GIL-free transform workers for the GIL slices they DO need
    (parse, mask numpy) and measurably slow the whole pipeline — the
    write-stage transform pool alone is the winning overlap there.
    4+ cores: full 3-stage topology."""
    import os

    return (os.cpu_count() or 2) >= 4


_ENT = METRICS.entity("storage", "node")
# stall = time a stage spent blocked on its neighbor's queue: the
# read stage stalls when write/filter are the bottleneck, the write
# stage stalls when disk reads are — together with the queue-depth
# gauges these say WHICH stage owns the critical path right now
_READ_STALL_MS = _ENT.relaxed_counter("compact_read_stall_ms")
_FILTER_STALL_MS = _ENT.relaxed_counter("compact_filter_stall_ms")
_WRITE_STALL_MS = _ENT.relaxed_counter("compact_write_stall_ms")
_READQ_DEPTH = _ENT.gauge("compact_readq_depth")
_FILTQ_DEPTH = _ENT.gauge("compact_filtq_depth")

_END = object()


class _StageError:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


class CompactPipeline:
    """One pipelined bulk compaction.

    `load(entry)` runs on the READ thread per entry and gives a list
    of items (one block, or the blocks a splice merges);
    `submit(items)` / `drain(token)` run on the FILTER thread per
    window (submit dispatches without waiting, drain materializes —
    the pipeline keeps one window submitted ahead). The `results()`
    generator yields drained outputs in entry order on the caller's
    (write) thread.
    """

    def __init__(self, entries: Sequence, load: Callable,
                 submit: Callable[[List], object],
                 drain: Callable[[object], List],
                 window: int, depth: int = 2,
                 eager: Optional[Callable[[object], bool]] = None,
                 span=None) -> None:
        self._entries = entries
        self._load = load
        self._submit = submit
        self._drain = drain
        # eager(token) True = this window has no asynchronously-
        # evaluating leg (all masks were computed at submit), so
        # holding it for the one-window device lookahead would only
        # starve the write stage — drain and forward it immediately
        self._eager = eager or (lambda _t: False)
        self._window = max(1, window)
        # the compaction's span where it is traced: the stage threads
        # frame their work as its children (tracing.adopt)
        self._span = span
        self._stop = threading.Event()
        self._q_read: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._q_filt: "queue.Queue" = queue.Queue(maxsize=max(1, depth))

    # ---- bounded-queue helpers that honor the stop event ---------------

    def _put(self, q: "queue.Queue", item, stall) -> bool:
        t0 = time.perf_counter()
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                waited = time.perf_counter() - t0
                if waited > 0.001:
                    stall.increment(int(waited * 1000))
                return True
            except queue.Full:
                continue
        return False

    def _get(self, q: "queue.Queue", stall):
        t0 = time.perf_counter()
        while not self._stop.is_set():
            try:
                item = q.get(timeout=0.05)
                waited = time.perf_counter() - t0
                if waited > 0.001:
                    stall.increment(int(waited * 1000))
                return item
            except queue.Empty:
                continue
        return _END

    # ---- stages ---------------------------------------------------------

    def _read_stage(self) -> None:
        with tracing.adopt(self._span, "compact.read"):
            self._read_stage_body()

    def _filter_stage(self) -> None:
        with tracing.adopt(self._span, "compact.filter"):
            self._filter_stage_body()

    def _read_stage_body(self) -> None:
        try:
            w = self._window
            for off in range(0, len(self._entries), w):
                if self._stop.is_set():
                    return
                items = [item for e in self._entries[off:off + w]
                         for item in self._load(e)]
                _READQ_DEPTH.set(self._q_read.qsize())
                if not self._put(self._q_read, items, _READ_STALL_MS):
                    return
            self._put(self._q_read, _END, _READ_STALL_MS)
        except BaseException as e:  # noqa: BLE001 - travels to consumer
            self._put(self._q_read, _StageError(e), _READ_STALL_MS)

    def _filter_stage_body(self) -> None:
        pending = None
        try:
            while not self._stop.is_set():
                items = self._get(self._q_read, _FILTER_STALL_MS)
                if isinstance(items, _StageError):
                    if pending is not None:
                        self._put(self._q_filt, self._drain(pending),
                                  _FILTER_STALL_MS)
                        pending = None
                    self._put(self._q_filt, items, _FILTER_STALL_MS)
                    return
                if items is _END:
                    break
                token = self._submit(items)
                if pending is not None:
                    _FILTQ_DEPTH.set(self._q_filt.qsize())
                    if not self._put(self._q_filt, self._drain(pending),
                                     _FILTER_STALL_MS):
                        return
                    pending = None
                if self._eager(token):
                    if not self._put(self._q_filt, self._drain(token),
                                     _FILTER_STALL_MS):
                        return
                else:
                    pending = token
            if pending is not None and not self._stop.is_set():
                self._put(self._q_filt, self._drain(pending),
                          _FILTER_STALL_MS)
            self._put(self._q_filt, _END, _FILTER_STALL_MS)
        except BaseException as e:  # noqa: BLE001 - travels to consumer
            self._put(self._q_filt, _StageError(e), _FILTER_STALL_MS)

    # ---- consumer --------------------------------------------------------

    def results(self) -> Iterator:
        """Yield (entry-order) filter outputs; re-raises any stage
        failure. Closing the generator stops and joins the stages."""
        t_read = threading.Thread(target=self._read_stage,
                                  name="compact-read", daemon=True)
        t_filt = threading.Thread(target=self._filter_stage,
                                  name="compact-filter", daemon=True)
        t_read.start()
        t_filt.start()
        try:
            while True:
                outs = self._get(self._q_filt, _WRITE_STALL_MS)
                if outs is _END:
                    return
                if isinstance(outs, _StageError):
                    raise outs.exc
                yield from outs
        finally:
            self._stop.set()
            # unblock producers stuck on a full queue, then join —
            # the threads must not outlive the compaction that owns
            # the run handles they read from
            for q in (self._q_read, self._q_filt):
                try:
                    while True:
                        q.get_nowait()
                except queue.Empty:
                    pass
            t_read.join(timeout=5.0)
            t_filt.join(timeout=5.0)
