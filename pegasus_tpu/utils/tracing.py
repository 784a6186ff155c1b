"""Cluster-wide distributed tracing: trace context on every RPC,
tail-kept slow traces, cross-node stitching.

Parity/inspiration: the reference treats observability as a first-class
layer — every mutation carries an rDSN latency tracer whose stage chain
dumps when slow (src/utils/latency_tracer.h:94, replica_2pc.cpp:338-359).
This module extends that *per-process* stage chain into a *cross-process*
span tree:

- every sampled client op mints a ``(trace_id, span_id, flags)`` context
  that rides the RPC payload dict (key ``"trace"``) through BOTH
  transports (rpc/transport.py TCP and runtime/sim.py delivery);
- server-side, the transport dispatch opens a span per inbound request
  parented to the carried context; finer join points (per-op spans at
  the batching seams, 2PC per-peer prepare hops) parent to it; the
  already-present ``LatencyTracer`` stage points feed the bound span as
  annotations — one instrumentation layer, not two;
- spans land in a per-node bounded ring (drop-oldest). Sampling is
  head-based (``[pegasus.tracing] sample_ratio``, default 0 — zero spans,
  zero allocation) plus TAIL KEEP: a request that crosses
  ``slow_trace_ms`` pins its local spans out of the ring's churn and the
  keep decision rides the reply context upstream so every upstream hop
  pins too — slow traces are always whole;
- ``stitch()`` assembles dumps from many nodes into one rooted tree and
  aligns clocks per hop from the parent/child span endpoints (the
  send/recv pair observable at the transport), reporting a skew bound.

The span stack is thread-local: on the TCP transport the single
dispatcher thread owns it; in the sim everything nests on one thread and
push/pop order preserves correctness through recursive delivery.

Two clocks. ``start``/``end`` live on the ring's clock (sim time on a
SimCluster, so injected delays show and tail-keep sees them); beside
them every span carries the host's monotonic clock
(``time.perf_counter_ns``), and host SELF time is what the layer profile
is made of. Self time is kept per thread as a stack of FRAMES, the way a
profiler does it: ``activate``/``layer``/``deliver``/``enter`` push a
frame, a finished frame adds its whole duration to its parent frame's
child total, and a ``LatencyTracer`` stage point (``mark``) closes the
top frame's interval since the previous point, minus what child frames
covered — no tree walk. Each closed interval lands on
``METRICS.entity("layer", node)`` as ``<key>_self_us`` (``LAYER_OF``
maps span and stage names to keys); the outermost frame of a root span
adds its duration to ``traced_us``. On one thread Σ self = traced.

The switch. A client op mints a root when ``maybe_sample()`` says so or
while a ``jax.profiler`` session is active (``profiling()``); the
PROFILED bit rides the context, and every frame of such a trace is also
a ``TraceAnnotation("pegasus.<span>")`` (stage intervals: events named
``pegasus.stage`` with the stage as their ``stage`` stat — a TraceMe is
named when it opens, a stage when it closes), so the program's spans
sit in the device profile on the profile's own clock.
"""

from __future__ import annotations

import contextlib
import itertools
import random
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from pegasus_tpu.utils.flags import FLAGS, define_flag

define_flag("pegasus.tracing", "sample_ratio", 0.0,
            "head-based sampling probability for new client ops "
            "(0 disables tracing entirely: no spans, no allocation)",
            mutable=True)
define_flag("pegasus.tracing", "slow_trace_ms", 20.0,
            "a sampled request slower than this is tail-kept: its spans "
            "pin out of the ring and the keep decision propagates "
            "upstream on the reply so slow traces are always whole",
            mutable=True)
define_flag("pegasus.tracing", "ring_capacity", 2048,
            "per-node span ring size (drop-oldest)", mutable=True)
define_flag("pegasus.tracing", "kept_traces", 64,
            "tail-kept slow traces retained per node (drop-oldest)",
            mutable=True)

# context flag bits
SAMPLED = 1
KEEP = 2
PROFILED = 4  # minted inside a jax.profiler session: frames annotate it

# spans per kept trace (a runaway trace must not pin unbounded memory)
KEPT_SPAN_CAP = 1024

# message types that are replies/acks: their carried context pins
# tail-keep but never opens a dispatch span (a reply is the END of a
# hop, not a new one)
_REPLY_SUFFIXES = ("_reply", "_ack")


def is_reply_type(name: str) -> bool:
    return name.endswith(_REPLY_SUFFIXES)


# ---- ids -----------------------------------------------------------------

_lock = threading.Lock()
_rng = random.Random()
_prefix = _rng.getrandbits(32)
_trace_ids = itertools.count(1)
_span_ids = itertools.count(1)


def seed(n: int) -> None:
    """Deterministic ids + sampling draws (tests / sim replays)."""
    global _rng, _prefix, _trace_ids, _span_ids
    with _lock:
        _rng = random.Random(n)
        _prefix = _rng.getrandbits(32)
        _trace_ids = itertools.count(1)
        _span_ids = itertools.count(1)


def _new_trace_id() -> str:
    return f"{_prefix:08x}{next(_trace_ids):08x}"


def _new_span_id() -> int:
    return (_prefix << 24) | (next(_span_ids) & 0xFFFFFF)


def maybe_sample() -> bool:
    """One head-based sampling draw (client op mint)."""
    ratio = FLAGS.get("pegasus.tracing", "sample_ratio")
    if ratio <= 0.0:
        return False
    return ratio >= 1.0 or _rng.random() < ratio


# ---- the profile session as the switch -----------------------------------

_trace_me: Any = None  # jax.profiler.TraceAnnotation once jax is loaded


def profiling() -> bool:
    """True while a jax.profiler session is active in this process. Read
    once per client call; a process that never imported jax reads False
    without importing it."""
    global _trace_me
    tm = _trace_me
    if tm is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax.profiler import TraceAnnotation as tm
        except ImportError:
            tm = False
        _trace_me = tm
    return bool(tm) and tm.is_enabled()


# ---- layers --------------------------------------------------------------

LAYER_KEYS = ("client", "rpc", "gate", "coord", "overlay", "index",
              "decode", "dispatch", "repl", "engine", "compact", "atomic",
              "other")

# span or stage name -> layer key. A name is looked up whole, then by
# what stands before its first "." (client.<op>, prepare.<dst>,
# ack.<peer>, 2pc.<app>.<pidx>.d<decree>); a name in neither way falls
# to "other". (The per-op spans op.<kind>.<pidx> are never framed.)
LAYER_OF = {
    # client/cluster_client.py: the root's self time is routing,
    # grouping, PGT1 encode, reply decode and retry bookkeeping
    "client": "client",
    # runtime/sim.py, rpc/transport.py: delivery and dispatch; the
    # transports' dispatch spans are named by message type and their
    # self time is the stub handler's unpack, reply build and send
    "rpc": "rpc",
    "client_read": "rpc", "client_write": "rpc",
    "client_read_batch": "rpc", "client_write_batch": "rpc",
    "client_scan_multi": "rpc", "query_config": "rpc",
    "negotiate": "rpc",
    # replica/stub.py, server/tenancy.py: ACL, lease, follower gate,
    # tenant brownout/admit, read limiter
    "gate": "gate",
    # server/*_coordinator.py, partition_server.py
    "coord": "coord", "plan": "coord", "assemble": "coord",
    "finish": "coord",
    # memtable + L0 walked under a read
    "overlay": "overlay", "overlay_merge": "overlay",
    # row cache, bloom and perfect-hash probes
    "row_cache": "index", "bloom": "index", "phash_probe": "index",
    # storage/sstable.py: block fetch, codec, crc
    "block_probe": "decode", "block_scan": "decode", "decode": "decode",
    # device predicate programs: call to mask on the host
    "dispatch": "dispatch", "pushdown": "dispatch",
    # the steps of a wave of mask programs (dispatch.wave,
    # server/scan_coordinator.py): a chunk's blocks listed and its
    # per-block pidx vector built; the jitted call, which uploads the
    # vector and queues the program that concatenates the stack; the
    # wait for the wave's packed masks and their copy to the host
    "dispatch.stack": "dispatch", "dispatch.launch": "dispatch",
    "dispatch.fetch": "dispatch",
    # replica/: 2PC, plog, group commit
    "repl": "repl", "2pc": "repl", "prepare": "repl", "ack": "repl",
    "replica": "repl", "prepare_local": "repl", "append_plog": "repl",
    "plog_durable": "repl", "prepares_sent": "repl",
    "committed_applied": "repl", "replied": "repl",
    # storage/engine.py
    "engine": "engine",
    # server/write_service.py: the read before an atomic write (incr,
    # check_and_set, check_and_mutate) and its check, on every replica
    # at apply
    "atomic_check": "atomic",
    # an env-triggered manual compaction on its own thread: the root
    # compact.run (partition_server), the bulk path's stage threads
    # compact.read / compact.filter (compact_pipeline), and the stages
    # of both paths (storage/lsm.py, storage/engine.py)
    "compact": "compact", "compact_read": "compact",
    "compact_merge": "compact", "compact_filter_submit": "compact",
    "compact_filter_drain": "compact", "compact_write": "compact",
    "compact_publish": "compact",
}


def layer_of(name: str) -> str:
    key = LAYER_OF.get(name)
    if key is None:
        key = LAYER_OF.get(name.partition(".")[0], "other")
    return key


# ---- spans ---------------------------------------------------------------


class Span:
    __slots__ = ("ring", "trace_id", "span_id", "parent_id", "name",
                 "node", "start", "end", "annotations", "tags", "flags",
                 "host_start_ns", "host_end_ns", "host_self_ns",
                 "stage_ns")

    def __init__(self, ring: "SpanRing", trace_id: str, span_id: int,
                 parent_id: Optional[int], name: str,
                 flags: int = SAMPLED) -> None:
        self.ring = ring
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.node = ring.node
        self.flags = flags
        self.start = ring.clock()
        self.end: Optional[float] = None
        # the host's monotonic clock beside the ring's: open, finish,
        # self time summed over this span's frames, and the stage
        # intervals closed inside them
        self.host_start_ns = time.perf_counter_ns()
        self.host_end_ns: Optional[int] = None
        self.host_self_ns = 0
        self.stage_ns: Optional[Dict[str, int]] = None
        self.annotations: List[Tuple[str, float]] = []
        self.tags: Dict[str, Any] = {}

    def annotate(self, stage: str, at: Optional[float] = None) -> None:
        self.annotations.append(
            (stage, self.ring.clock() if at is None else at))

    def elapsed_ms(self) -> float:
        return (self.ring.clock() - self.start) * 1000.0

    def ctx(self) -> Tuple[str, int, int]:
        """The wire context. The KEEP bit is computed HERE, at send
        time: a reply stamped while the local request already crossed
        the slow threshold (or its trace was already pinned) carries the
        tail-keep decision upstream."""
        flags = self.flags
        if (self.ring.is_kept(self.trace_id)
                or self.elapsed_ms()
                >= FLAGS.get("pegasus.tracing", "slow_trace_ms")):
            flags |= KEEP
        return (self.trace_id, self.span_id, flags)

    def finish(self) -> None:
        if self.end is not None:
            return  # idempotent (error paths may double-finish)
        self.end = self.ring.clock()
        self.host_end_ns = time.perf_counter_ns()
        self.ring.record(self)

    def to_dict(self) -> Dict[str, Any]:
        host_end = (self.host_end_ns if self.host_end_ns is not None
                    else self.host_start_ns)
        d = {"trace": self.trace_id, "span": self.span_id,
             "parent": self.parent_id, "name": self.name,
             "node": self.node, "start": self.start,
             "end": self.end if self.end is not None else self.start,
             "host_ms": (host_end - self.host_start_ns) / 1e6,
             "host_self_ms": self.host_self_ns / 1e6,
             "ann": list(self.annotations),
             "tags": dict(self.tags)}
        if self.stage_ns:
            d["stage_us"] = {k: v / 1e3 for k, v in self.stage_ns.items()}
        return d


class SpanRing:
    """One node's span store: a drop-oldest ring of finished spans plus
    the pinned (tail-kept) slow traces, which survive ring churn."""

    def __init__(self, node: str, clock=time.time) -> None:
        from pegasus_tpu.utils.metrics import METRICS

        self.node = node
        self.clock = clock
        self._ring: "deque[dict]" = deque()
        self._kept: "OrderedDict[str, List[dict]]" = OrderedDict()
        self._lock = threading.RLock()
        ent = METRICS.entity("tracing", node)
        self.kept_count = ent.counter("kept_trace_count")
        self.drop_count = ent.counter("span_drop_count")
        self.span_count = ent.counter("span_count")
        # the layer profile: host self time per layer key, whole
        # microseconds (the nanoseconds left over wait in _ns_left)
        lay = METRICS.entity("layer", node)
        self._layer_us = {k: lay.counter(f"{k}_self_us")
                          for k in LAYER_KEYS}
        self._layer_us["traced"] = lay.counter("traced_us")
        self._ns_left: Dict[str, int] = {}

    def add_host_ns(self, key: str, ns: int) -> None:
        """Host time of one closed frame or stage interval, to its
        layer key's counter (or to "traced", a root's whole frame)."""
        us, self._ns_left[key] = divmod(ns + self._ns_left.get(key, 0),
                                        1000)
        if us:
            self._layer_us[key].increment(us)

    # -- recording --------------------------------------------------------

    def start(self, name: str, parent: Optional[Span] = None,
              parent_ctx: Optional[tuple] = None,
              trace_id: Optional[str] = None,
              profiled: bool = False) -> Span:
        """A new span; the caller already decided it is sampled. A
        child inherits the PROFILED bit; a root takes it from the
        session (`profiled`)."""
        flags = SAMPLED
        if parent is not None:
            trace_id, parent_id = parent.trace_id, parent.span_id
            flags |= parent.flags & PROFILED
        elif parent_ctx is not None:
            trace_id, parent_id = parent_ctx[0], parent_ctx[1]
            flags |= parent_ctx[2] & PROFILED
        else:
            trace_id, parent_id = trace_id or _new_trace_id(), None
            if profiled:
                flags |= PROFILED
        return Span(self, trace_id, _new_span_id(), parent_id, name,
                    flags)

    def record(self, span: Span) -> None:
        d = span.to_dict()
        pin_after = False
        with self._lock:
            self.span_count.increment()
            if span.trace_id in self._kept:
                kept = self._kept[span.trace_id]
                if len(kept) < KEPT_SPAN_CAP:
                    kept.append(d)
            else:
                self._ring.append(d)
                cap = FLAGS.get("pegasus.tracing", "ring_capacity")
                while len(self._ring) > cap:
                    self._ring.popleft()
                    self.drop_count.increment()
                # local tail-keep: this span alone crossed the slow
                # threshold -> pin its whole trace
                if (d["end"] - d["start"]) * 1000.0 >= FLAGS.get(
                        "pegasus.tracing", "slow_trace_ms"):
                    pin_after = True
        if pin_after:
            self.pin(span.trace_id)

    def pin(self, trace_id: str) -> None:
        """Tail keep: pull this trace's spans out of the churn ring into
        the kept store; spans recorded later join them directly."""
        with self._lock:
            if trace_id in self._kept:
                return
            mine = [d for d in self._ring if d["trace"] == trace_id]
            if mine:
                self._ring = deque(d for d in self._ring
                                   if d["trace"] != trace_id)
            self._kept[trace_id] = mine[:KEPT_SPAN_CAP]
            self.kept_count.increment()
            cap = FLAGS.get("pegasus.tracing", "kept_traces")
            while len(self._kept) > cap:
                self._kept.popitem(last=False)

    def is_kept(self, trace_id: str) -> bool:
        return trace_id in self._kept

    # -- read surfaces ----------------------------------------------------

    def dump(self, trace_id: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = []
            for spans in self._kept.values():
                out.extend(spans)
            out.extend(self._ring)
        if trace_id is not None:
            out = [d for d in out if d["trace"] == trace_id]
        return out

    def slow_roots(self, limit: int = 16) -> List[dict]:
        """Summaries of the tail-kept traces, newest last: the root (or
        earliest) span per trace — what `shell traces --slow` lists."""
        with self._lock:
            items = list(self._kept.items())[-limit:]
        out = []
        for tid, spans in items:
            if not spans:
                out.append({"trace": tid, "name": "?", "node": self.node,
                            "start": 0.0, "total_ms": 0.0})
                continue
            roots = [s for s in spans if s["parent"] is None]
            root = min(roots or spans, key=lambda s: s["start"])
            out.append({"trace": tid, "name": root["name"],
                        "node": root["node"], "start": root["start"],
                        "total_ms": round(
                            (root["end"] - root["start"]) * 1000.0, 3)})
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._kept.clear()


# ---- registry ------------------------------------------------------------

_rings: Dict[str, SpanRing] = {}
_rings_lock = threading.Lock()


def ring_for(node: str, clock=None) -> SpanRing:
    """The node's ring (created on first use). Passing `clock` (re)binds
    the ring's timebase — the sim cluster points every node at its
    virtual clock so span timelines live in sim time."""
    with _rings_lock:
        ring = _rings.get(node)
        if ring is None:
            ring = _rings[node] = SpanRing(node, clock or time.time)
        elif clock is not None:
            ring.clock = clock
        return ring


def dump_all(trace_id: Optional[str] = None) -> List[dict]:
    """Every local ring's spans (the shell process's own client ring
    joins the fan-out dumps this way)."""
    with _rings_lock:
        rings = list(_rings.values())
    out: List[dict] = []
    for r in rings:
        out.extend(r.dump(trace_id))
    return out


def slow_roots_all(limit: int = 16) -> List[dict]:
    with _rings_lock:
        rings = list(_rings.values())
    out: List[dict] = []
    for r in rings:
        out.extend(r.slow_roots(limit))
    return sorted(out, key=lambda d: d["start"])[-limit:]


def drop_ring(node: str) -> None:
    """Remove one node's ring (a closed sim cluster drops the rings it
    registered so its clock closures — and through them the whole dead
    cluster — are not pinned in the process-global registry)."""
    with _rings_lock:
        _rings.pop(node, None)


def reset() -> None:
    """Drop every ring (test isolation; sim clusters re-register)."""
    with _rings_lock:
        _rings.clear()


# ---- ambient span stack (server-side dispatch) ---------------------------

_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def push(span: Span) -> None:
    _stack().append(span)


def pop(span: Span) -> None:
    st = _stack()
    if st and st[-1] is span:
        st.pop()
    elif span in st:  # defensive: unwind past a mispaired frame
        st.remove(span)


def current_span() -> Optional[Span]:
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def current_ctx() -> Optional[tuple]:
    """The wire context of the ambient span (None when untraced) — what
    the transports stamp onto outbound payload dicts."""
    st = getattr(_tls, "stack", None)
    return st[-1].ctx() if st else None


def annotate(stage: str) -> None:
    """Annotate the ambient span; a single attr check when untraced."""
    st = getattr(_tls, "stack", None)
    if st:
        st[-1].annotate(stage)


# ---- host frames: self time per thread ------------------------------------


class _Frame:
    """One entry of a span on this thread's host clock."""

    __slots__ = ("span", "t0", "child_ns", "mark_ns", "mark_child_ns",
                 "ann", "stage_ann")

    def __init__(self, span: Span, now: int) -> None:
        self.span = span
        self.t0 = now
        self.child_ns = 0          # whole durations of finished children
        self.mark_ns = now         # the previous stage point (or entry)
        self.mark_child_ns = 0     # child_ns as it stood at that point
        self.ann = None            # TraceAnnotation of a PROFILED span
        self.stage_ann = None      # the open `pegasus.stage` interval


def _open_stage_ann(f: _Frame) -> None:
    f.stage_ann = a = _trace_me("pegasus.stage")
    a.__enter__()


def _close_interval(f: _Frame, name: str, now: int) -> int:
    """Close the frame's interval since its previous point: what child
    frames covered is theirs, the rest goes to `name`'s layer."""
    ns = (now - f.mark_ns) - (f.child_ns - f.mark_child_ns)
    f.mark_ns = now
    f.mark_child_ns = f.child_ns
    f.span.ring.add_host_ns(layer_of(name), ns)
    return ns


def enter(span: Optional[Span]) -> None:
    """Push a host frame for `span` on this thread (no-op for None).
    Frames are about time only; `push` is what makes a span ambient."""
    if span is None:
        return
    fr = getattr(_tls, "frames", None)
    if fr is None:
        fr = _tls.frames = []
    f = _Frame(span, time.perf_counter_ns())
    if span.flags & PROFILED and _trace_me:
        f.ann = _trace_me("pegasus." + span.name)
        f.ann.__enter__()
    fr.append(f)


def leave(span: Optional[Span]) -> None:
    """Pop `span`'s frame (scopes nest, so it is the top one): its self
    time to its layer, its whole duration to the parent frame, or to
    `traced_us` when it was a root's outermost."""
    if span is None:
        return
    fr = _tls.frames
    f = fr.pop()
    now = time.perf_counter_ns()
    _close_interval(f, span.name, now)
    dur = now - f.t0
    span.host_self_ns += dur - f.child_ns
    if fr:
        fr[-1].child_ns += dur
    elif span.parent_id is None:
        span.ring.add_host_ns("traced", dur)
    if f.stage_ann is not None:
        f.stage_ann.__exit__(None, None, None)
    if f.ann is not None:
        f.ann.__exit__(None, None, None)


def begin_stages() -> None:
    """A stage chain starts here (LatencyTracer with a span): what the
    top frame spent so far stays with its own span, and the chain's
    first interval opens."""
    fr = getattr(_tls, "frames", None)
    if fr:
        f = fr[-1]
        _close_interval(f, f.span.name, time.perf_counter_ns())
        if f.ann is not None and f.stage_ann is None:
            _open_stage_ann(f)


def mark(stage: str) -> None:
    """A stage point: the top frame's interval since the previous point
    is `stage`'s."""
    fr = getattr(_tls, "frames", None)
    if not fr:
        return
    f = fr[-1]
    ns = _close_interval(f, stage, time.perf_counter_ns())
    sp = f.span
    if sp.stage_ns is None:
        sp.stage_ns = {}
    sp.stage_ns[stage] = sp.stage_ns.get(stage, 0) + ns
    if f.ann is not None:
        if f.stage_ann is not None:
            f.stage_ann.set_metadata(stage=stage)
            f.stage_ann.__exit__(None, None, None)
        _open_stage_ann(f)


# the one shared no-op scope of every untraced `layer`/`deliver`
_NULL = contextlib.nullcontext()


class _Scope:
    """A span opened, framed and finished around one block."""

    __slots__ = ("_span",)

    def __init__(self, span: Span) -> None:
        self._span = span

    def __enter__(self) -> Span:
        enter(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        leave(self._span)
        self._span.finish()


def layer(name: str):
    """Scope of one layer's work at a per-batch boundary: a child of
    this thread's top frame, with a frame of its own. It is never
    ambient, so contexts on the wire and LatencyTracer annotations stay
    with the dispatch span. Untraced: one thread-local read."""
    return adopt(frame_span(), name)


def background_root(node: str, name: str):
    """Scope of a background thread's own work (a manual compaction):
    a root span with a frame on this thread, under the switch a client
    op's root has (sampling, or a jax.profiler session). Its frames'
    self time lands on the node's layer counters and its duration on
    `traced_us`, beside the client roots'."""
    profiled = profiling()
    if not profiled and not maybe_sample():
        return _NULL
    return _Scope(ring_for(node).start(name, profiled=profiled))


def frame_span() -> Optional[Span]:
    """The span of this thread's top frame (None when untraced): what
    a helper thread `adopt`s."""
    fr = getattr(_tls, "frames", None)
    return fr[-1].span if fr else None


def adopt(parent: Optional[Span], name: str):
    """A child span of `parent` with a frame on THIS thread (`_NULL`
    for None). On the parent's own thread that is `layer`; on a helper
    thread (a compaction's stage threads) its self time lands on the
    layer counters and its duration joins no parent frame and no
    `traced_us`: the parent's thread is covering the same wall time."""
    if parent is None:
        return _NULL
    return _Scope(parent.ring.start(name, parent=parent))


def deliver(node: str, msg_type: str, payload):
    """`rpc.deliver`: the scope of one message's delivery on `node`
    (sim loop or TCP dispatcher), request or reply.

    Inside a frame of this thread (the sim delivers inside the client
    call that pumps it) the delivery is that frame's child whatever it
    carries: its time is that call's. On a thread of its own (the TCP
    dispatcher) it is the child of the context it carries — a batch
    carrier (`trace: None`, per-item contexts) takes its first item's —
    except a `*_reply`: a reply's context names the remote hop for
    tail-keep, not a parent, and that hop's ring (a meta's) may be one
    no trace dump collects."""
    fr = getattr(_tls, "frames", None)
    if fr:
        top = fr[-1].span
        return _Scope(ring_for(node).start(
            "rpc.deliver",
            parent_ctx=(top.trace_id, top.span_id, top.flags)))
    if not isinstance(payload, dict):
        return _NULL
    ctx = payload.get("trace")
    if ctx is None:
        items = payload.get("items")
        if not items:
            return _NULL
        ctx = next((e[2] for e in items if len(e) > 2 and e[2]), None)
    if not ctx or not (ctx[2] & SAMPLED):
        return _NULL
    kind = payload.get("type") if msg_type == "replica" else msg_type
    if isinstance(kind, str) and kind.endswith("_reply"):
        return _NULL
    return _Scope(ring_for(node).start("rpc.deliver", parent_ctx=ctx))


class activate:
    """Context manager: make `span` ambient, with a host frame (no-op
    for None)."""

    __slots__ = ("_span",)

    def __init__(self, span: Optional[Span]) -> None:
        self._span = span

    def __enter__(self):
        if self._span is not None:
            push(self._span)
            enter(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            leave(self._span)
            pop(self._span)


def child_of(parent: Optional[Span], name: str) -> Optional[Span]:
    """A child span on the parent's ring (None-propagating)."""
    if parent is None:
        return None
    return parent.ring.start(name, parent=parent)


# ---- transport hooks -----------------------------------------------------


def on_inbound_ctx(node: str, ctx) -> None:
    """Process a carried context on ANY inbound message: a KEEP bit pins
    the trace locally (upstream hops of a slow request pin theirs when
    the decision rides back on the reply)."""
    if ctx and (ctx[2] & KEEP):
        ring_for(node).pin(ctx[0])


def start_server_span(node: str, name: str, ctx) -> Optional[Span]:
    """Dispatch join point: open a span for an inbound request carrying
    a sampled context (replies/acks only pin, never span)."""
    if not ctx or not (ctx[2] & SAMPLED):
        return None
    ring = ring_for(node)
    if ctx[2] & KEEP:
        ring.pin(ctx[0])
    return ring.start(name, parent_ctx=ctx)


# ---- stitching -----------------------------------------------------------


def stitch(spans: List[dict]) -> Optional[dict]:
    """Assemble span dumps (from any number of nodes) into ONE rooted
    tree with per-hop clock alignment.

    Each tree node is the span dict plus:
      - ``offset``: seconds added to this span's local clock to land it
        on the ROOT's timebase (cumulative down the tree);
      - ``skew_ms``: half-width of the per-hop offset interval — the
        alignment uncertainty from transport asymmetry;
      - ``rel_ms`` / ``dur_ms`` / ``self_ms``: aligned start relative to
        the root, duration, and self time (duration minus children),
        all on the rings' clock; ``host_ms`` / ``host_self_ms`` /
        ``stage_us`` ride each span as recorded, on the host's own;
      - ``children``: sorted by aligned start.

    Alignment derives from the send/recv pair the transports already
    observe: a child hop's span must START after its parent span started
    and END before the parent ended (request left after the parent span
    opened; reply arrived before it closed), so the child->parent clock
    offset lies in ``[p.start - c.start, p.end - c.end]``; the midpoint
    aligns, the half-width bounds the skew. Async children that outlive
    their parent clamp to start-alignment and report the overrun as
    skew.
    """
    if not spans:
        return None
    by_id: Dict[int, dict] = {}
    for s in spans:
        prev = by_id.get(s["span"])
        # dedupe (duplicated deliveries / overlapping dumps): keep the
        # longer record — it saw more of the span's life
        if prev is None or (s["end"] - s["start"]) > (
                prev["end"] - prev["start"]):
            by_id[s["span"]] = s
    nodes = {sid: dict(s, children=[]) for sid, s in by_id.items()}
    roots = []
    for sid, n in nodes.items():
        p = n.get("parent")
        if p is not None and p in nodes:
            nodes[p]["children"].append(n)
        else:
            roots.append(n)
    if len(roots) > 1:
        # orphans (ring-dropped parents): synthesize a root so the
        # result is still ONE tree
        t0 = min(r["start"] for r in roots)
        t1 = max(r["end"] for r in roots)
        root = {"trace": roots[0]["trace"], "span": 0, "parent": None,
                "name": "(stitched)", "node": "?", "start": t0,
                "end": t1, "ann": [], "tags": {},
                "children": sorted(roots, key=lambda r: r["start"])}
    else:
        root = roots[0]

    def local_extent(n: dict) -> Tuple[float, float]:
        """Interval covered by this span plus its SAME-NODE descendants
        (one shared clock, so no alignment needed): the true window of
        this hop's local work, even when an async child outlives the
        span that spawned it."""
        ext = n.get("_lex")
        if ext is None:
            s, e = n["start"], n["end"]
            for c in n["children"]:
                if c["node"] == n["node"]:
                    cs, ce = local_extent(c)
                    s, e = min(s, cs), max(e, ce)
            ext = n["_lex"] = (s, e)
        return ext

    def align(n: dict, offset: float) -> None:
        n["offset"] = offset
        n["skew_ms"] = n.get("skew_ms", 0.0)
        n["dur_ms"] = round((n["end"] - n["start"]) * 1000.0, 3)
        _ps, pe = local_extent(n)
        for c in n["children"]:
            if c["node"] == n["node"]:
                d, skew = 0.0, 0.0  # same clock: no per-hop estimation
            else:
                # the hop bound: the child's local work started after
                # the parent span opened (request sent) and ended
                # before the parent's local work closed (reply seen)
                cs, ce = local_extent(c)
                lo = n["start"] - cs
                hi = pe - ce
                if hi >= lo:
                    d, skew = (lo + hi) / 2.0, (hi - lo) / 2.0
                else:  # one-way hop (no reply observed): align starts
                    d, skew = lo, (lo - hi) / 2.0
            c["skew_ms"] = round(skew * 1000.0, 3)
            align(c, offset + d)
        n["children"].sort(key=lambda c: c["start"] + c["offset"])

    def extent(n: dict) -> Tuple[float, float]:
        """Aligned interval covered by this span's whole subtree (an
        async child may outlive its parent span)."""
        s = n["start"] + n["offset"]
        e = n["end"] + n["offset"]
        for c in n["children"]:
            cs, ce = extent(c)
            s, e = min(s, cs), max(e, ce)
        n["_ext"] = (s, e)
        return s, e

    def self_time(n: dict) -> None:
        """Self time = own interval minus the union of child SUBTREE
        intervals — parallel children overlap and async children spill
        past their own span, so a plain duration sum misattributes."""
        for c in n["children"]:
            self_time(c)
        extent_ = [c["_ext"] for c in n["children"]] if n["children"] \
            else []
        s0 = n["start"] + n["offset"]
        e0 = n["end"] + n["offset"]
        covered = 0.0
        last = s0
        for cs, ce in sorted(extent_):
            cs, ce = max(cs, last), min(ce, e0)
            if ce > cs:
                covered += ce - cs
                last = ce
        n["self_ms"] = round(max(0.0, (e0 - s0) - covered) * 1000.0, 3)

    align(root, 0.0)
    extent(root)
    self_time(root)
    for n in list(walk_dict(root)):
        n.pop("_ext", None)
        n.pop("_lex", None)
    t_root = root["start"]

    def rel(n: dict) -> None:
        n["rel_ms"] = round(
            (n["start"] + n["offset"] - t_root) * 1000.0, 3)
        for c in n["children"]:
            rel(c)

    rel(root)
    return root


def walk_dict(tree: dict):
    """Yield every node of a stitched tree (pre-order)."""
    yield tree
    for c in tree["children"]:
        yield from walk_dict(c)


def render(tree: Optional[dict], width: int = 48) -> str:
    """Text timeline of a stitched tree: one line per span with an
    aligned bar, duration and self time on the rings' clock, the same
    two on the host's clock, and the per-hop skew bound."""
    if tree is None:
        return "(no spans)"
    total = max(tree["dur_ms"], 1e-9)
    lines = [f"trace {tree['trace']}  total {tree['dur_ms']:.3f} ms"]

    def emit(n: dict, depth: int) -> None:
        left = int(n["rel_ms"] / total * width)
        bar_w = max(1, int(n["dur_ms"] / total * width))
        bar = " " * min(left, width - 1) + "#" * min(bar_w,
                                                     width - left)
        skew = (f" ±{n['skew_ms']:.3f}ms" if n.get("skew_ms") else "")
        ann = ""
        if n["ann"]:
            stages = ",".join(a[0] for a in n["ann"][:8])
            ann = f"  [{stages}]"
        host = ""
        if "host_ms" in n:
            host = (f"  host {n['host_ms']:.3f}ms "
                    f"(self {n['host_self_ms']:.3f}ms)")
        lines.append(
            f"{'  ' * depth}{n['name']} @{n['node']}  "
            f"{n['dur_ms']:.3f}ms (self {n['self_ms']:.3f}ms){skew}"
            f"{host}{ann}")
        if n.get("stage_us"):
            # the stage intervals closed in this span's frames, host
            # clock, children's time taken out
            lines.append(f"{'  ' * depth}  stages: " + " ".join(
                f"{k}={v / 1000.0:.3f}ms"
                for k, v in n["stage_us"].items()))
        pc = (n.get("tags") or {}).get("perf")
        if pc:
            # the op's PerfContext rode the span: counts, not just
            # durations (only the fields that moved; an all-zero
            # vector — a gate-rejected flush — prints nothing)
            moved = " ".join(
                f"{k}={v}" for k, v in pc.items()
                if k not in ("op", "placement")
                and v not in (0, 0.0, None))
            place = (f" [{pc['placement']}]"
                     if pc.get("placement") else "")
            if moved or place:
                lines.append(f"{'  ' * depth}  perf{place}: {moved}")
        lines.append(f"{'  ' * depth}|{bar:<{width}}|")
        for c in n["children"]:
            emit(c, depth + 1)

    emit(tree, 0)
    return "\n".join(lines)
