"""The program's own spans beside the device trace.

The program's tracer (``repro.analytics.tracing``) stamps its spans on
``time.perf_counter`` and records them while a JAX profiler trace runs, so
a ``--trace 1`` window carries them; a profile stamps its events in
nanoseconds from its own start. Two anchors put the spans on the profile's
clock: ``anchor()`` opens a profiler annotation named ``ANCHOR`` and reads
``perf_counter`` inside it, and ``clock`` fits the offset and the rate from
the two (``load`` finds the annotations in the profile).

``load`` extends ``devtrace``'s plain form with two keys::

    "modules": {device: [[hlo_module, start_ns, duration_ns], ...]},
    "anchors": [[start_ns, duration_ns], ...]

``modules`` holds the intervals of each chip's "XLA Modules" line, or
where a chip has none, each op under its ``hlo_module`` stat.
``devtrace.reduce`` reads neither. ``op_stats_sample`` shows what stats a
profile gives per op, for a look by hand.

Then, in the window: ``idle_by_span`` names each idle gap of 100 us or
more by the innermost program span open over it (the one overlapping it
most, the shortest on a tie), else by the runtime event ``devtrace``
names it by; ``busy_by_plan`` splits busy time by XLA module;
``idle_under`` gives the idle time while given spans are open; ``execute_split`` splits a request's execute phase into
pickup, dispatch, device wait and garbage collection. ``request_spans`` is
what the per-layer readers take from the tracer.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import devtrace

ANCHOR = "bench.spans.anchor"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


# ---------------------------------------------------------------------------
# the clock
# ---------------------------------------------------------------------------
def anchor() -> float:
    """A ``perf_counter`` reading inside a profiler annotation ``ANCHOR``:
    the pair ties the two clocks together."""
    import jax
    with jax.profiler.TraceAnnotation(ANCHOR):
        return time.perf_counter()


def clock(perf: Sequence[float], trace_ns: Sequence[float]
          ) -> Callable[[float], float]:
    """ns = a + b * perf_counter seconds, from the first and the last of
    matched anchors (one anchor: rate 1)."""
    if not perf or len(perf) != len(trace_ns):
        raise ValueError(f"{len(perf)} perf_counter anchors against "
                         f"{len(trace_ns)} in the profile")
    p0, n0 = perf[0], trace_ns[0]
    if len(perf) == 1 or perf[-1] == p0:
        rate = 1e9
    else:
        rate = (trace_ns[-1] - n0) / (perf[-1] - p0)
    return lambda t: n0 + (t - p0) * rate


def anchor_points(trace: dict) -> List[float]:
    """The profile's anchor annotations, each by its midpoint (the
    ``perf_counter`` reading lies inside the annotation)."""
    return [s + d / 2 for s, d in sorted(trace["anchors"])]


def map_spans(spans, to_ns: Callable[[float], float], lo: int, hi: int
              ) -> List[list]:
    """[name, start_ns, duration_ns, trace_id] of each program span on the
    profile's clock, clipped to [lo, hi); spans outside it are left out."""
    out = []
    for s in spans:
        a, b = max(lo, to_ns(s.t0)), min(hi, to_ns(s.t0 + s.dur))
        if b > a or (s.dur == 0 and lo <= a < hi):
            out.append([s.name, int(a), int(b - a), s.trace_id])
    return out


# ---------------------------------------------------------------------------
# the profile
# ---------------------------------------------------------------------------
def load(log_dir: str) -> dict:
    """``devtrace.load_xplane``'s plain form plus modules and anchors (see
    the module docstring)."""
    from jax.profiler import ProfileData

    trace = devtrace.load_xplane(log_dir)
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    modules: Dict[str, list] = {}
    anchors: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(devtrace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if MODULES_LINE in lines:
                mods = [[_MODULE_ID.sub("", e.name), int(e.start_ns),
                         int(e.duration_ns)]
                        for e in lines[MODULES_LINE].events]
            else:
                mods = []
                ops = lines.get(devtrace.OPS_LINE)
                for e in (ops.events if ops is not None else []):
                    module = dict(e.stats).get("hlo_module")
                    if module is not None:
                        mods.append([str(module), int(e.start_ns),
                                     int(e.duration_ns)])
            modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                anchors.extend([int(e.start_ns), int(e.duration_ns)]
                               for e in line.events if e.name == ANCHOR)
    return dict(trace, modules=modules, anchors=anchors)


def op_stats_sample(log_dir: str, n: int = 5) -> List[dict]:
    """The stats of the first ``n`` ops of the first chip's ops line, for
    a look by hand at what a profile gives per op."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(devtrace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != devtrace.OPS_LINE:
                continue
            for e in line.events:
                out.append({"name": e.name[:160],
                            "stats": {k: str(v)[:160]
                                      for k, v in dict(e.stats).items()}})
                if len(out) >= n:
                    return out
    return out


# ---------------------------------------------------------------------------
# breakdowns
# ---------------------------------------------------------------------------
def _intersect(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _busy(trace: dict) -> Dict[str, List[Interval]]:
    lo, hi = trace["window"]
    return {dev: devtrace.union([(s, s + d) for _, s, d in ops], lo, hi)
            for dev, ops in sorted(trace["devices"].items())}


def idle_by_span(trace: dict, spans: Sequence[list], top: int = 10
                 ) -> List[list]:
    """Idle gaps (no chip busy) of ``devtrace.SHORT_GAP_NS`` or more by
    the innermost program span over them (``spans`` from ``map_spans``),
    else by ``devtrace``'s runtime label; shorter gaps counted apart.
    Entries ``[label (xcount), seconds]``, the longest first."""
    lo, hi = trace["window"]
    busy = devtrace.union([iv for b in _busy(trace).values() for iv in b],
                          lo, hi)
    program = devtrace._HostIndex([[n, s, d] for n, s, d, _ in spans])
    runtime = devtrace._HostIndex(trace["host"])
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for gap in devtrace.gaps(busy, lo, hi):
        if gap[1] - gap[0] < devtrace.SHORT_GAP_NS:
            label = f"gaps under {devtrace.SHORT_GAP_NS // 1000} us"
        else:
            label = program.label(gap)
            if label == "no host event":
                label = runtime.label(gap)
        out[label][0] += 1
        out[label][1] += (gap[1] - gap[0]) / 1e9
    ranked = sorted(out.items(), key=lambda kv: -kv[1][1])[:top]
    return [[f"{label} (x{n})", secs] for label, (n, secs) in ranked]


def idle_under(trace: dict, spans: Sequence[list], names) -> float:
    """Seconds of the window in which no chip is busy while a program
    span named in ``names`` is open (``spans`` from ``map_spans``)."""
    lo, hi = trace["window"]
    busy = devtrace.union([iv for b in _busy(trace).values() for iv in b],
                          lo, hi)
    under = devtrace.union([(s, s + d) for n, s, d, _ in spans
                            if n in names], lo, hi)
    return _intersect(devtrace.gaps(busy, lo, hi), under) / 1e9


def program_share(idle: Sequence[list], names) -> float:
    """Share of the idle time in named gaps (100 us or more) that lies
    under one of the program span ``names``."""
    named = [(lab.rsplit(" (x", 1)[0], s) for lab, s in idle
             if not lab.startswith("gaps under")]
    total = sum(s for _, s in named)
    return (sum(s for lab, s in named if lab in set(names)) / total
            if total else 0.0)


def busy_by_plan(trace: dict, top: int = 20) -> List[list]:
    """Busy seconds per XLA module, the mean over chips; busy time under
    no module is ``(no module)``. The entries sum to ``reduce``'s
    ``busy_s``."""
    lo, hi = trace["window"]
    busy = _busy(trace)
    out: Dict[str, float] = defaultdict(float)
    for dev, b in busy.items():
        per: Dict[str, list] = defaultdict(list)
        for name, s, d in trace["modules"].get(dev, []):
            per[name].append((s, s + d))
        left = devtrace._total(b)
        for name, ivs in per.items():
            ns = _intersect(b, devtrace.union(ivs, lo, hi))
            out[name] += ns
            left -= ns
        if left > 0:
            out["(no module)"] += left
    n = max(1, len(busy))
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9 / n] for name, ns in ranked]


# ---------------------------------------------------------------------------
# program spans of a run's requests
# ---------------------------------------------------------------------------
def program_spans() -> list:
    """The program tracer's spans; empty where the program has no tracer
    or recorded nothing."""
    try:
        from repro.analytics import tracing
    except ImportError:
        return []
    tr = tracing.tracer()
    dropped = getattr(tr, "dropped", 0) + getattr(tr, "gc_dropped", 0)
    if dropped:
        print(f"bench: the program tracer dropped {dropped} spans",
              file=sys.stderr)
    return tr.spans()


def request_spans(run, names: Sequence[str]) -> Optional[Dict[int, list]]:
    """Spans named ``names`` of each request the window completed, by
    request id; None where the program recorded none of them."""
    rids = {r.rid for r in run.completed if r.rid is not None}
    by: Dict[int, list] = {rid: [] for rid in rids}
    found = False
    for s in program_spans():
        if s.name in names and s.trace_id in by:
            by[s.trace_id].append(s)
            found = True
    return by if found else None


def covered(t0: float, t1: float, intervals: Sequence[Tuple[float, float]]
            ) -> float:
    """Seconds of [t0, t1] that ``intervals`` (disjoint) cover."""
    return sum(max(0.0, min(t1, b) - max(t0, a)) for a, b in intervals)


def execute_split(phases: Dict[str, float], own: Sequence,
                  gc_spans: Sequence) -> dict:
    """One request's execute phase split by its own spans: ``pickup``
    from the scheduler submit (the end of ``dispatch.build``) to the first
    ``morsel.run``, the summed ``plan.dispatch`` and ``plan.device_wait``,
    and ``gc``, the time of ``gc_spans`` (``runtime.gc``, of no request)
    inside [submit, last morsel end]."""
    build = [s for s in own if s.name == "dispatch.build"]
    runs = [s for s in own if s.name == "morsel.run"]
    out = {"execute": phases.get("execute", float("nan"))}
    if not build or not runs:
        return out
    t_submit = max(s.t0 + s.dur for s in build)
    t_end = max(s.t0 + s.dur for s in runs)
    out.update(
        pickup=min(s.t0 for s in runs) - t_submit,
        dispatch=sum(s.dur for s in own if s.name == "plan.dispatch"),
        device_wait=sum(s.dur for s in own if s.name == "plan.device_wait"),
        gc=covered(t_submit, t_end,
                   [(s.t0, s.t0 + s.dur) for s in gc_spans]))
    return out
