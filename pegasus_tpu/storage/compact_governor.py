"""Background-compaction governor: the node half of the cluster-level
background-I/O scheduler.

RESYSTANCE's observation (PAPERS.md) is that uncontrolled background
bandwidth — not slow compaction — is what wrecks foreground p99: a
compactor running at disk speed steals exactly the IOPS the serving
path needs at the worst moment. The governor closes that loop on each
node:

- every byte the compaction pipeline reads passes through one
  process-wide token bucket (`acquire`), so background disk bandwidth
  has a single knob;
- the knob is driven by the PR 2 foreground-pressure counters
  (`deadline_expired_count` + `read_shed_count` on the rpc dispatch
  entity) with AIMD feedback: any growth since the last look halves
  the allowance (engaging a cap at half the measured recent rate when
  previously uncapped), quiet intervals recover it multiplicatively
  until the cap disengages — compaction always keeps the configured
  floor, so it makes forward progress even on a shedding node (a
  stalled compaction eventually hurts reads MORE via deep L0);
- the cluster half (meta/compaction_scheduler.CompactionCoordinator)
  staggers which nodes may run HEAVY (env-triggered manual)
  compactions concurrently: nodes report demand on the config-sync
  channel, meta replies with a leased grant, and an ungranted node
  simply defers its trigger to the next config-sync delivery —
  blocking nothing, fencing nothing, and degrading to "everyone may
  run" whenever no coordinator answers (standalone engines, tests,
  meta down: availability beats stagger).

Metrics (node storage entity): `compaction_bytes_per_s` (gauge, paced
read rate), `compact_throttle_mbps` (gauge, 0 = uncapped),
`compact_backoff_count`, `compact_throttle_stall_ms`,
`compact_defer_count` (heavy compactions deferred ungranted).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Optional

from pegasus_tpu.utils.flags import FLAGS, define_flag
from pegasus_tpu.utils.metrics import METRICS

define_flag("pegasus.storage", "compact_max_mbps", 0,
            "hard background-compaction read-bandwidth cap in MB/s; "
            "0 = uncapped until foreground pressure engages the AIMD "
            "backoff", mutable=True)
define_flag("pegasus.storage", "compact_min_mbps", 32,
            "floor the pressure backoff never throttles below — "
            "background compaction must keep making forward progress "
            "(a stalled compaction eventually hurts reads more than "
            "the bandwidth it frees)", mutable=True)
define_flag("pegasus.storage", "compact_feedback_interval_s", 1.0,
            "seconds between foreground-pressure samples driving the "
            "AIMD rate adaptation", mutable=True)
define_flag("pegasus.storage", "compact_grant_lease_s", 30.0,
            "seconds a meta-issued heavy-compaction grant stays valid "
            "without renewal (config-sync renews it every tick; a dead "
            "meta therefore releases the cluster stagger rather than "
            "wedging compaction)", mutable=True)


def _default_pressure() -> int:
    ent = METRICS.entity("rpc", "dispatch", {})
    return (ent.counter("deadline_expired_count").value()
            + ent.counter("read_shed_count").value())


class CompactionGovernor:
    """One per process (module singleton GOVERNOR); engines share it
    the way replicas share the node row cache."""

    # multiplicative recovery per quiet feedback interval, and the
    # throttle level (relative to the engage point) at which an
    # AIMD-engaged cap disengages back to uncapped
    RECOVER_FACTOR = 1.5
    UNCAP_FACTOR = 2.0

    def __init__(self,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 pressure_source: Callable[[], int] = _default_pressure,
                 ) -> None:
        self._clock = clock
        self._sleep = sleep
        self._pressure = pressure_source
        self._lock = threading.Lock()
        # throttle: MB/s currently enforced; 0 = uncapped. AIMD state
        # distinguishes an OPERATOR cap (compact_max_mbps, permanent)
        # from a PRESSURE-engaged cap (recovers to uncapped)
        self._throttle_mbps = 0.0
        self._engaged_at_mbps = 0.0  # rate when pressure first engaged
        self._tokens = 0.0
        self._tok_t = self._clock()
        self._pressure_last: Optional[int] = None
        self._feedback_t = self._clock()
        # measured recent read rate (1s windows -> gauge)
        self._win_t = self._clock()
        self._win_bytes = 0
        self._rate_bps = 0.0
        # heavy-compaction demand + cluster grant lease
        self.heavy_running = 0
        self._heavy_waiting = False
        self._grant: Optional[tuple] = None  # (granted, expires_at)
        ent = METRICS.entity("storage", "node")
        self._g_rate = ent.gauge("compaction_bytes_per_s")
        self._g_throttle = ent.gauge("compact_throttle_mbps")
        self._c_backoff = ent.counter("compact_backoff_count")
        self._c_stall_ms = ent.counter("compact_throttle_stall_ms")
        self._c_defer = ent.counter("compact_defer_count")

    # ---- pacing (called by the pipeline's read stage) ------------------

    def acquire(self, nbytes: int) -> None:
        """Account `nbytes` of background compaction IO, sleeping as
        needed to hold the current throttle. Uncapped mode costs two
        clock reads."""
        now = self._clock()
        sleep_s = 0.0
        with self._lock:
            self._feedback_locked(now)
            # rate window for the gauge
            self._win_bytes += nbytes
            dt = now - self._win_t
            if dt >= 1.0:
                self._rate_bps = self._win_bytes / dt
                self._g_rate.set(self._rate_bps)
                self._win_t = now
                self._win_bytes = 0
            rate = self._throttle_mbps
            if rate > 0:
                bps = rate * 1e6
                # token bucket with a 250ms burst allowance; debt is
                # allowed (a block is atomic) and paid off by sleeping
                self._tokens = min(self._tokens + (now - self._tok_t)
                                   * bps, bps * 0.25)
                self._tok_t = now
                self._tokens -= nbytes
                if self._tokens < 0:
                    sleep_s = -self._tokens / bps
                    self._tokens = 0.0
        if sleep_s > 0:
            self._c_stall_ms.increment(int(sleep_s * 1000))
            # a traced request stalled behind the governor (e.g. an
            # ingest riding the compaction pipeline) records WHERE the
            # time went; one attr check when untraced
            from pegasus_tpu.utils.tracing import annotate

            annotate("governor_stall")
            self._sleep(sleep_s)

    def _feedback_locked(self, now: float) -> None:
        interval = float(FLAGS.get("pegasus.storage",
                                   "compact_feedback_interval_s"))
        if now - self._feedback_t < interval:
            return
        self._feedback_t = now
        try:
            p = self._pressure()
        except Exception:  # noqa: BLE001 - a broken source never throttles
            return
        prev, self._pressure_last = self._pressure_last, p
        max_mbps = float(FLAGS.get("pegasus.storage",
                                   "compact_max_mbps"))
        min_mbps = float(FLAGS.get("pegasus.storage",
                                   "compact_min_mbps"))
        if self._throttle_mbps == 0 and max_mbps > 0:
            self._throttle_mbps = max_mbps  # operator cap always on
        if prev is None:
            return
        if p > prev:
            # foreground is shedding / expiring deadlines: halve the
            # allowance (engage a cap at half the measured recent rate
            # when previously uncapped)
            cur = self._throttle_mbps
            if cur == 0:
                cur = max(self._rate_bps / 1e6, min_mbps * 2)
                self._engaged_at_mbps = cur
            self._throttle_mbps = max(cur / 2, min_mbps)
            self._c_backoff.increment()
            self._g_throttle.set(self._throttle_mbps)
            return
        # quiet interval: multiplicative recovery toward the operator
        # cap, or toward disengaging a pressure-engaged cap
        cur = self._throttle_mbps
        if cur == 0:
            return
        cur *= self.RECOVER_FACTOR
        if max_mbps > 0:
            self._throttle_mbps = min(cur, max_mbps)
        elif self._engaged_at_mbps > 0 and \
                cur >= self._engaged_at_mbps * self.UNCAP_FACTOR:
            self._throttle_mbps = 0.0  # fully recovered: uncap
            self._engaged_at_mbps = 0.0
        else:
            self._throttle_mbps = cur
        self._g_throttle.set(self._throttle_mbps)

    def poke(self) -> None:
        """Run a feedback step if the interval elapsed (timer hook for
        nodes where no compaction is currently paying `acquire`)."""
        with self._lock:
            self._feedback_locked(self._clock())

    # ---- cluster stagger (grants ride config-sync) ---------------------

    def heavy_allowed(self) -> bool:
        """May an env-triggered (heavy) compaction start NOW? True
        when no coordinator has ever answered (standalone / tests /
        meta down — availability over stagger) or the lease is live
        and granted; an expired lease fails OPEN for the same reason."""
        g = self._grant
        if g is None:
            return True
        granted, expires = g
        if self._clock() > expires:
            return True
        return granted

    def set_cluster_grant(self, granted: bool) -> None:
        lease = float(FLAGS.get("pegasus.storage",
                                "compact_grant_lease_s"))
        self._grant = (bool(granted), self._clock() + lease)

    def note_deferred(self) -> None:
        """An env trigger found heavy_allowed() False and deferred to
        the next config-sync delivery: record the demand so the node's
        report asks the coordinator for a slot."""
        self._heavy_waiting = True
        self._c_defer.increment()

    def begin_heavy(self) -> None:
        self._heavy_waiting = False
        with self._lock:
            self.heavy_running += 1

    def end_heavy(self) -> None:
        with self._lock:
            self.heavy_running = max(0, self.heavy_running - 1)

    # ---- observability --------------------------------------------------

    def report(self) -> dict:
        """The node's compaction block in the config-sync report."""
        return {
            "running": self.heavy_running,
            "waiting": bool(self._heavy_waiting),
            "bytes_per_s": int(self._rate_bps),
        }

    def status(self) -> dict:
        g = self._grant
        return {
            "throttle_mbps": round(self._throttle_mbps, 1),
            "bytes_per_s": int(self._rate_bps),
            "heavy_running": self.heavy_running,
            "heavy_waiting": bool(self._heavy_waiting),
            "grant": (None if g is None else {
                "granted": g[0],
                "lease_remaining_s": round(g[1] - self._clock(), 1),
            }),
            "backoff_count": self._c_backoff.value(),
            "defer_count": self._c_defer.value(),
        }


GOVERNOR = CompactionGovernor()

# manual_compact.max_concurrent_running_count when the table env does
# not set it (client/table.compact_partitions_parallel's pool size)
MANUAL_COMPACT_MAX_RUNNING = 8


class ManualCompactPool:
    """Bounds the env-triggered manual compactions that run at once in
    this PROCESS (parity: pegasus_manual_compact_service's
    `manual_compact.max_concurrent_running_count`, which upstream
    counts per replica server: one process a node there, so the same
    thing; the nodes of an in-process SimCluster share this one pool as
    they share GOVERNOR, and the env's count then bounds the whole
    cluster). A trigger that finds every slot taken waits in FIFO order
    and starts when a slot frees, on the worker that freed it, not at
    the next config sync.

    Counters (entity `engine`, id `manual_compact_pool`):
    `compact_started`, `compact_finished`, `compact_deferred`; gauge
    `compact_running_peak`. `history` keeps when each of the newest
    runs finished and how long it took (time.perf_counter seconds),
    as upstream's query_compact_state tells an operator."""

    def __init__(self, name: str = "manual_compact_pool") -> None:
        self._cv = threading.Condition()
        self._waiting: collections.deque = collections.deque()
        self._owners: list = []     # of the runs on a worker now
        self.running_peak = 0
        self.history: collections.deque = collections.deque(maxlen=4096)
        ent = METRICS.entity("engine", name)
        self._c_started = ent.counter("compact_started")
        self._c_finished = ent.counter("compact_finished")
        self._c_deferred = ent.counter("compact_deferred")
        self._g_peak = ent.gauge("compact_running_peak")

    @property
    def running(self) -> int:
        return len(self._owners)

    def submit(self, owner, fn: Callable[[], None], name: str,
               limit: int = MANUAL_COMPACT_MAX_RUNNING) -> None:
        """Run `fn` for `owner` on a worker now, or when one of the
        `limit` slots frees (`limit` <= 0: no bound, as upstream)."""
        with self._cv:
            if 0 < limit <= self.running:
                self._waiting.append((owner, fn))
                self._c_deferred.increment()
                return
            self._owners.append(owner)
            if self.running > self.running_peak:
                self.running_peak = self.running
                self._g_peak.set(self.running)
        threading.Thread(target=self._work, args=(owner, fn),
                         daemon=True, name=name).start()

    def _work(self, owner, fn: Callable[[], None]) -> None:
        while True:
            self._c_started.increment()
            t0 = time.perf_counter()
            try:
                fn()
            except Exception:  # noqa: BLE001 - one failed compaction
                # must not take the slot's queue down with it; the
                # trigger stays unsatisfied (no finish time recorded)
                # and the next one compacts again
                import traceback

                traceback.print_exc()
            self._c_finished.increment()
            now = time.perf_counter()
            self.history.append((now, now - t0))
            with self._cv:
                self._owners.remove(owner)
                self._cv.notify_all()
                if not self._waiting:
                    return
                owner, fn = self._waiting.popleft()
                self._owners.append(owner)

    def wait_idle(self, timeout: float) -> bool:
        """Wait until nothing runs and nothing waits; False when
        `timeout` seconds did not see that."""
        with self._cv:
            return self._cv.wait_for(
                lambda: not self._owners and not self._waiting, timeout)

    def drain(self, owner) -> bool:
        """Forget what waits for `owner` and wait for what runs for it
        (the owner's engine is about to close). True when a waiting
        run was dropped."""
        with self._cv:
            kept = [e for e in self._waiting if e[0] is not owner]
            dropped = len(kept) != len(self._waiting)
            self._waiting = collections.deque(kept)
            while any(o is owner for o in self._owners):
                self._cv.wait()
        return dropped


MANUAL_COMPACT_POOL = ManualCompactPool()
