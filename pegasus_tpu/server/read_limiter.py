"""Range-read iteration budget.

Parity: src/server/range_read_limiter.h:37 — a range read (multi_get/
sortkey_count/scan) stops early when it has examined
FLAGS_rocksdb_max_iteration_count records or spent
FLAGS_rocksdb_iteration_threshold_time_ms; the handler then reports an
incomplete result the client resumes from.
"""

from __future__ import annotations

import time

from pegasus_tpu.utils.flags import FLAGS, define_flag

define_flag("pegasus.server", "rocksdb_max_iteration_count", 1000,
            "max records examined by one ranged read", mutable=True)
define_flag("pegasus.server", "rocksdb_iteration_threshold_time_ms", 30_000,
            "max milliseconds for one ranged read (<=0: unlimited)",
            mutable=True)


class RangeReadLimiter:
    def __init__(self, max_iteration_count: int | None = None,
                 threshold_time_ms: int | None = None,
                 clock_ns=None) -> None:
        """`clock_ns`: nanosecond time source (default wall
        perf_counter_ns). Sim-hosted partitions thread their virtual
        clock here — the same threading scrub_tick/health_tick use —
        because a compressed sim schedule burns thousands of virtual
        seconds in milliseconds of wall (and vice versa a wall-stalled
        sim host could trip the budget with zero virtual time spent)."""
        self._max_count = (FLAGS.get("pegasus.server",
                                     "rocksdb_max_iteration_count")
                           if max_iteration_count is None
                           else max_iteration_count)
        self._threshold_ns = 1_000_000 * (
            FLAGS.get("pegasus.server", "rocksdb_iteration_threshold_time_ms")
            if threshold_time_ms is None else threshold_time_ms)
        self._clock_ns = (clock_ns if clock_ns is not None
                          else time.perf_counter_ns)
        self._count = 0
        self._start_ns = self._clock_ns()

    def add_count(self, n: int = 1) -> None:
        self._count += n

    @property
    def iteration_count(self) -> int:
        return self._count

    def count_exceeded(self, count: int | None = None) -> bool:
        """Whether the rows counted, or `count` rows, use up the
        budget."""
        n = self._count if count is None else count
        return self._max_count > 0 and n >= self._max_count

    def time_exceeded(self) -> bool:
        return (self._threshold_ns > 0 and
                self._clock_ns() - self._start_ns > self._threshold_ns)

    def valid(self) -> bool:
        return not self.count_exceeded() and not self.time_exceeded()
