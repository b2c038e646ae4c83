"""Request-scoped tracing: the serving path's TIME observability.

PR 7's telemetry answers "where do the ROWS go" (observed Exchange/
Compact volumes fed back into the cost model); this module answers
"where does the TIME go". The paper's method is to measure phase-level
latency before reaching for a mechanism — allocator, placement, load
balancing. The tracer threads one trace id (the request id, or the
dispatch id for plan-level work) through every phase:

  queue.wait        admission -> dequeue (AdmissionQueue.take_batch)
  serve.round       one serving round: the dequeued requests' batching,
                    dispatch and the wait for the round's last share
  batch.group       plan-cache-key grouping + dedup (QueryBatcher)
  dispatch.build    compile_plan + scheduler submit for one share
  retry.backoff     the sleep between failed dispatch attempts
  morsel.run        one morsel on one pool's worker (pid=pool, tid=worker)
  morsel.steal      instant: a pool stole the tail of another's backlog
  plan.dispatch     one executable call up to its return: join-index
                    lookups and the enqueue, not the device's work; args
                    ``exchange_bytes`` and ``exchanges``, the plan's
                    inter-chip wire volume (planner.exchange_wire; 0
                    without a mesh)
  plan.device_wait  block_until_ready on what a plan.dispatch enqueued
  merge.partials    morsel-order partial merge (QueryTask._finish)
  result.deliver    terminal-result fan-out (_record)
  plan.lower        plan-cache miss: lowering + jit construction (the
                    XLA compile happens at the first call)
  runtime.gc        one Python garbage collection of >= 1 ms; shorter
                    ones are only counted (``Tracer.gc_short``)

Every stamp is ``time.perf_counter``: the clock the benchmark's window
uses, and the one a profiler trace is anchored to.

Discipline mirrors ``telemetry.StatsRegistry``:

  * one module-level flag; every instrumentation site is behind
    ``if tracing_enabled():`` — disabled (the default), the hot path
    performs ONE module-attribute read and allocates nothing
    (``Tracer.created`` counts every span/instant allocated, so the
    zero-overhead contract is assertable, and scripts/trace_gate.py
    asserts it). The flag is on while ``enable_tracing`` / ``tracing()``
    asks for it, or while a JAX profiler trace runs and the serving loop
    has called ``follow_profiler()``; the garbage-collector hook is
    registered only while it is on;
  * the span ring is BOUNDED (``maxlen``) and thread-safe — an
    always-on service cannot grow it without bound;
  * service-level spans are recorded host-side only and the flag is NOT
    part of the plan-cache key — only telemetry's ``record`` flag
    re-jits, because only it adds traced operations.

Exports:

  * ``Trace.to_chrome_trace()`` — Chrome trace-event JSON (perfetto-
    loadable): ``ph:"X"`` complete events with pid/tid lanes per
    pool/worker plus ``ph:"M"`` metadata naming the lanes;
  * ``render_timeline()`` — a deterministic text timeline (golden-
    snapshotted like ``explain_analyze``);
  * ``FlightRecorder`` — a bounded ring of postmortem dumps: the recent
    span window snapshotted at the moment a fault trips (injector build
    fail / wait poison / pool kill, scheduler quarantine, overload shed,
    WorkerLeakError), so every injected chaos-grid fault yields an
    artifact.

Leaf-level: planner/service import this module, never the reverse; JAX
is imported only to ask whether its profiler is tracing.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# enable flag (the telemetry.py discipline)
# ---------------------------------------------------------------------------
_ENABLED = False        # what every instrumentation site reads
_EXPLICIT = False       # enable_tracing / tracing()
_FOLLOWED = False       # a JAX profiler trace is running (follow_profiler)
_ENABLE_LOCK = threading.Lock()
_PROFILER_ACTIVE = None     # jax.profiler.TraceAnnotation.is_enabled
GC_SPAN_MIN_S = 1e-3        # shorter collections are counted, not spanned


def tracing_enabled() -> bool:
    return _ENABLED


def _apply_locked() -> None:
    """Recompute the flag; the gc hook is registered exactly while it is
    on (call under ``_ENABLE_LOCK``)."""
    global _ENABLED
    on = _EXPLICIT or _FOLLOWED
    if on and not _ENABLED:
        gc.callbacks.append(_on_gc)
    elif not on and _ENABLED:
        gc.callbacks.remove(_on_gc)
    _ENABLED = on


def enable_tracing() -> None:
    global _EXPLICIT
    with _ENABLE_LOCK:
        _EXPLICIT = True
        _apply_locked()


def disable_tracing() -> None:
    global _EXPLICIT
    with _ENABLE_LOCK:
        _EXPLICIT = False
        _apply_locked()


@contextmanager
def tracing():
    """Enable tracing for the duration of a block (not reference counted:
    nested blocks share the one global flag)."""
    prev = _EXPLICIT
    enable_tracing()
    try:
        yield tracer()
    finally:
        if not prev:
            disable_tracing()


def follow_profiler() -> bool:
    """Trace while a JAX profiler trace runs, so that a profile always
    carries the program's spans; called by the serving loop before each
    dequeue, which makes a round traced or untraced as a whole. Returns
    the flag. Costs one call into the profiler when nothing changes."""
    global _FOLLOWED, _PROFILER_ACTIVE
    if _PROFILER_ACTIVE is None:
        try:
            from jax.profiler import TraceAnnotation
            _PROFILER_ACTIVE = TraceAnnotation.is_enabled
        except ImportError:
            _PROFILER_ACTIVE = bool
    active = bool(_PROFILER_ACTIVE())
    if active != _FOLLOWED:
        with _ENABLE_LOCK:
            _FOLLOWED = active
            _apply_locked()
    return _ENABLED


_LOCAL = threading.local()


@contextmanager
def working_for(trace_id: int):
    """Mark the calling thread as working for request ``trace_id``: a span
    recorded below, by code that cannot know the request (a plan's
    dispatch), takes it from ``current_trace_id``."""
    prev = getattr(_LOCAL, "trace_id", -1)
    _LOCAL.trace_id = trace_id
    try:
        yield
    finally:
        _LOCAL.trace_id = prev


def current_trace_id() -> int:
    return getattr(_LOCAL, "trace_id", -1)


_GC_T0 = [0.0]          # collections are serialized: one start stamp


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        _GC_T0[0] = time.perf_counter()
    else:
        _TRACER.note_gc(_GC_T0[0], time.perf_counter(),
                        info.get("generation", -1),
                        info.get("collected", 0))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Span:
    """One finished span: a named [t0, t0+dur) interval on a (pid, tid)
    lane, tied to a request (``trace_id``) and optionally nested under a
    parent span. ``dur == 0.0`` marks an instant event."""

    name: str
    cat: str                      # phase family: queue|batch|service|...
    t0: float                     # time.perf_counter seconds
    dur: float
    trace_id: int = -1            # request/dispatch id; -1 = unscoped
    span_id: int = -1
    parent_id: int = -1
    pid: str = "service"          # process lane (pool / service / plan)
    tid: str = "main"             # thread lane (worker name)
    args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def t1(self) -> float:
        return self.t0 + self.dur

    @property
    def instant(self) -> bool:
        return self.dur == 0.0


@dataclass
class FlightDump:
    """One postmortem artifact: the recent-span window at the moment a
    fault tripped, plus whatever the trip site wanted on record."""

    reason: str
    at: float                     # time.perf_counter of the trip
    args: Dict[str, Any] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)


class FlightRecorder:
    """Bounded ring of FlightDumps (thread-safe). The tracer owns one;
    trip sites call ``tracer().flight_dump(reason, **args)``."""

    def __init__(self, max_dumps: int = 64):
        self._lock = threading.Lock()
        self._dumps: "deque[FlightDump]" = deque(maxlen=max_dumps)

    def add(self, dump: FlightDump) -> None:
        with self._lock:
            self._dumps.append(dump)

    def dumps(self) -> List[FlightDump]:
        with self._lock:
            return list(self._dumps)

    def clear(self) -> None:
        with self._lock:
            self._dumps.clear()


class _OpenSpan:
    __slots__ = ("name", "cat", "t0", "trace_id", "span_id", "parent_id",
                 "pid", "tid", "args")

    def __init__(self, name, cat, t0, trace_id, span_id, parent_id, pid,
                 tid, args):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.pid = pid
        self.tid = tid
        self.args = args


class Tracer:
    """Thread-safe bounded span collector.

    Three entry styles, chosen by what the call site can know:

      * ``begin()`` / ``end()`` — spans opened and closed by the SAME
        logical operation (possibly on different threads; the span id is
        the handle). Unclosed spans stay visible in ``open_spans()`` —
        the trace gate fails on any.
      * ``add_complete()`` — retrospective spans synthesized from stamps
        that already exist (``QueryRequest.submit_t`` / ``dispatch_t``,
        ``QueryTask.submit_t`` / ``done_t``): no cross-thread open-span
        bookkeeping, no chance of a leak.
      * ``instant()`` — point events (steals, quarantines).

    ``created`` counts every span/instant ever allocated — the
    zero-overhead-when-disabled guard: a round served with tracing off
    must leave it unchanged.

    ``runtime.gc`` spans come from the garbage collector's callback,
    which may fire while this thread holds the tracer's lock: they go to
    a ring of their own, appended without the lock, and ``spans()``
    merges it in. ``gc_short`` counts the collections too short to span
    (count, seconds); ``gc_dropped`` that ring's evictions.
    """

    def __init__(self, max_spans: int = 32768, flight_window: int = 128,
                 max_dumps: int = 64, max_gc_spans: int = 4096):
        self._lock = threading.Lock()
        self._spans: "deque[Span]" = deque(maxlen=max_spans)
        self._gc: "deque[Span]" = deque(maxlen=max_gc_spans)
        self.gc_short = [0, 0.0]
        self.gc_dropped = 0
        self._open: Dict[int, _OpenSpan] = {}
        self._next_id = 0
        self.flight_window = flight_window
        self.flight = FlightRecorder(max_dumps)
        self.created = 0              # spans+instants allocated, ever
        self.dropped = 0              # ring evictions

    # -- recording ----------------------------------------------------------
    def begin(self, name: str, cat: str, *, trace_id: int = -1,
              parent_id: int = -1, pid: str = "service",
              tid: Optional[str] = None, **args) -> int:
        t0 = time.perf_counter()
        tid = tid or threading.current_thread().name
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            self._open[sid] = _OpenSpan(name, cat, t0, trace_id, sid,
                                        parent_id, pid, tid,
                                        tuple(args.items()))
        return sid

    def end(self, span_id: int, **args) -> Optional[Span]:
        t1 = time.perf_counter()
        with self._lock:
            op = self._open.pop(span_id, None)
            if op is None:
                return None
            span = Span(op.name, op.cat, op.t0, max(0.0, t1 - op.t0),
                        op.trace_id, op.span_id, op.parent_id, op.pid,
                        op.tid, op.args + tuple(args.items()))
            self._append_locked(span)
        return span

    def add_complete(self, name: str, cat: str, t0: float, t1: float, *,
                     trace_id: int = -1, parent_id: int = -1,
                     pid: str = "service", tid: Optional[str] = None,
                     **args) -> Span:
        """Record a retrospective span from existing perf_counter stamps."""
        tid = tid or threading.current_thread().name
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            span = Span(name, cat, t0, max(0.0, t1 - t0), trace_id, sid,
                        parent_id, pid, tid, tuple(args.items()))
            self._append_locked(span)
        return span

    def instant(self, name: str, cat: str, *, trace_id: int = -1,
                pid: str = "service", tid: Optional[str] = None,
                **args) -> Span:
        now = time.perf_counter()
        tid = tid or threading.current_thread().name
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            span = Span(name, cat, now, 0.0, trace_id, sid, -1, pid, tid,
                        tuple(args.items()))
            self._append_locked(span)
        return span

    def note_gc(self, t0: float, t1: float, generation: int,
                collected: int) -> None:
        """One finished collection (from the gc callback: takes no
        lock)."""
        if t1 - t0 < GC_SPAN_MIN_S:
            self.gc_short[0] += 1
            self.gc_short[1] += t1 - t0
            return
        if len(self._gc) == self._gc.maxlen:
            self.gc_dropped += 1
        self._gc.append(Span("runtime.gc", "runtime", t0, t1 - t0,
                             pid="runtime", tid="gc",
                             args=(("generation", generation),
                                   ("collected", collected))))

    def _append_locked(self, span: Span) -> None:
        if len(self._spans) == self._spans.maxlen:
            self.dropped += 1
        self._spans.append(span)
        self.created += 1

    # -- flight recorder ----------------------------------------------------
    def flight_dump(self, reason: str, **args) -> FlightDump:
        """Snapshot the recent span window (finished ring tail + every
        still-open span, rendered open-ended) as a postmortem artifact."""
        now = time.perf_counter()
        with self._lock:
            recent = list(self._spans)[-self.flight_window:]
            for op in self._open.values():
                recent.append(Span(op.name, op.cat, op.t0,
                                   max(0.0, now - op.t0), op.trace_id,
                                   op.span_id, op.parent_id, op.pid, op.tid,
                                   op.args + (("open", True),)))
        dump = FlightDump(reason, now, dict(args), recent)
        self.flight.add(dump)
        return dump

    # -- lookups ------------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans) + list(self._gc)

    def open_spans(self) -> List[_OpenSpan]:
        with self._lock:
            return list(self._open.values())

    def trace(self) -> "Trace":
        return Trace(self.spans())

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._open.clear()
            self._gc.clear()
            self.gc_short = [0, 0.0]
            self.gc_dropped = 0
            self.dropped = 0
        self.flight.clear()


# ---------------------------------------------------------------------------
# export: chrome trace events + text timeline
# ---------------------------------------------------------------------------
class Trace:
    """An immutable snapshot of spans with the two export renderings."""

    def __init__(self, spans: List[Span]):
        self.spans = sorted(spans, key=lambda s: (s.t0, s.span_id))

    def phase_names(self) -> List[str]:
        return sorted({s.name for s in self.spans})

    def lanes(self) -> List[Tuple[str, str]]:
        return sorted({(s.pid, s.tid) for s in self.spans})

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (load in perfetto / chrome://tracing).

        pid/tid labels (pool / worker names) become small integers with
        ``ph:"M"`` process_name / thread_name metadata naming the lanes;
        timestamps are microseconds relative to the earliest span."""
        pids: Dict[str, int] = {}
        tids: Dict[Tuple[str, str], int] = {}
        events: List[Dict[str, Any]] = []
        base = self.spans[0].t0 if self.spans else 0.0
        for s in self.spans:
            if s.pid not in pids:
                pids[s.pid] = len(pids) + 1
                events.append({"ph": "M", "name": "process_name",
                               "pid": pids[s.pid], "tid": 0,
                               "args": {"name": s.pid}})
            lane = (s.pid, s.tid)
            if lane not in tids:
                tids[lane] = len(tids) + 1
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pids[s.pid], "tid": tids[lane],
                               "args": {"name": s.tid}})
            args = {k: v for k, v in s.args}
            if s.trace_id >= 0:
                args["trace_id"] = s.trace_id
            ev = {"name": s.name, "cat": s.cat,
                  "ph": "i" if s.instant else "X",
                  "ts": round((s.t0 - base) * 1e6, 3),
                  "pid": pids[s.pid], "tid": tids[lane], "args": args}
            if s.instant:
                ev["s"] = "t"          # thread-scoped instant
            else:
                ev["dur"] = round(s.dur * 1e6, 3)
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def render_timeline(self, width: int = 40) -> str:
        """Deterministic text timeline: one row per span (start order),
        a bar over a [first span start, last span end] axis, and the
        lane + name + relative times. Deterministic for fixed span
        inputs, so golden-snapshotable (tests/fixtures/
        trace_timeline.txt)."""
        if not self.spans:
            return "trace: empty"
        t_lo = min(s.t0 for s in self.spans)
        t_hi = max(s.t1 for s in self.spans)
        extent = max(t_hi - t_lo, 1e-9)
        lane_w = max(len(f"{s.pid}/{s.tid}") for s in self.spans)
        name_w = max(len(s.name) for s in self.spans)
        lines = [f"trace {len(self.spans)} spans "
                 f"{len(self.lanes())} lanes "
                 f"span={extent * 1e3:.2f}ms"]
        for s in self.spans:
            lo = int((s.t0 - t_lo) / extent * width)
            hi = int((s.t1 - t_lo) / extent * width)
            lo = min(lo, width - 1)
            hi = min(max(hi, lo + 1), width)
            bar = "." * lo + ("|" if s.instant else "#" * (hi - lo))
            bar = bar.ljust(width, ".")
            rid = f" req={s.trace_id}" if s.trace_id >= 0 else ""
            lines.append(
                f"[{bar}] {f'{s.pid}/{s.tid}':<{lane_w}} "
                f"{s.name:<{name_w}} "
                f"{(s.t0 - t_lo) * 1e3:8.2f}ms "
                f"+{s.dur * 1e3:.2f}ms{rid}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the process tracer
# ---------------------------------------------------------------------------
_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER
