"""Reduce a profiler trace of a run's window to device metrics.

A trace is first put in a plain form that the reduction and its test
fixture share::

    {"window": [start_ns, end_ns],
     "devices": {device: [[op, start_ns, duration_ns], ...]},
     "host": [[name, start_ns, duration_ns], ...]}

``devices`` holds the ops of each chip's "XLA Ops" line, each named by
``op_label`` (instruction, opcode and result shape, without operands, whose
names would otherwise make a fusion that reads an all-to-all's result look
like a collective), ``host`` the host threads' events, and ``window`` the host
span ``bench.window`` that the harness opens around its measured window. ``reduce`` then gives, per chip
and averaged over the chips: busy time (the union of op intervals inside
the window), the part of it spent in collective ops, the ops that took the
most time, and the longest idle gaps (no chip busy) named by the host event
that overlaps each gap the most.
"""
from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute")
_OPCODE = re.compile(r"(?:^|[\s)}])([a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
SHORT_GAP_NS = 100_000      # shorter idle gaps are counted, not named

Interval = Tuple[int, int]


def load_xplane(log_dir: str) -> dict:
    """The plain form of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, list] = {}
    host: List[list] = []
    window = None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_label(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = [int(e.start_ns),
                                  int(e.start_ns + e.duration_ns)]
                    elif e.duration_ns > 0:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return {"window": window, "devices": devices, "host": host}


def union(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged, sorted intervals clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _total(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of sorted disjoint ``busy`` within [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(hlo: str) -> str:
    """``%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop`` ->
    ``fusion.2: fusion -> f32[8]``; text that is no HLO instruction stays."""
    lhs, eq, rhs = hlo.partition(" = ")
    m = _OPCODE.search(rhs)
    if not eq or not m:
        return hlo
    shape = _LAYOUT.sub("", rhs[:m.start(1)]).strip()
    target = _TARGET.search(rhs[m.end():])
    opcode = m.group(1) + (f" {target.group(1)}" if target else "")
    return f"{lhs.lstrip('%')}: {opcode} -> {shape}"


class _HostIndex:
    """Host events, searchable by the interval they overlap."""

    LONG_NS = 1_000_000_000

    def __init__(self, host: Sequence[list]):
        short = sorted((s, d, n) for n, s, d in host if d <= self.LONG_NS)
        self.long = [(s, d, n) for n, s, d in host if d > self.LONG_NS]
        self.short = short
        self.starts = [s for s, _, _ in short]

    def label(self, gap: Interval) -> str:
        """The event overlapping ``gap`` the most (then the shortest)."""
        lo = bisect.bisect_left(self.starts, gap[0] - self.LONG_NS)
        hi = bisect.bisect_left(self.starts, gap[1])
        best, best_key = "no host event", (0, 0)
        for s, d, name in itertools.chain(self.short[lo:hi], self.long):
            overlap = min(gap[1], s + d) - max(gap[0], s)
            if overlap > 0 and (overlap, -d) > best_key:
                best, best_key = name, (overlap, -d)
        return best


def reduce(trace: dict, top: int = 10) -> dict:
    """Device metrics of the window; times in seconds."""
    lo, hi = trace["window"]
    window_ns = hi - lo
    devices = trace["devices"]
    per_device, op_ns = {}, defaultdict(int)
    all_busy: List[Interval] = []
    for dev, ops in sorted(devices.items()):
        spans = [(s, s + d) for _, s, d in ops]
        busy = union(spans, lo, hi)
        coll = union([(s, s + d) for n, s, d in ops if COLLECTIVE.search(n)],
                     lo, hi)
        for name, s, d in ops:
            clipped = min(hi, s + d) - max(lo, s)
            if clipped > 0:
                op_ns[name] += clipped
        per_device[dev] = {"busy_s": _total(busy) / 1e9,
                           "collective_s": _total(coll) / 1e9}
        all_busy.extend(busy)
    n = max(1, len(per_device))
    idle_by_label: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    host = _HostIndex(trace["host"])
    for gap in gaps(union(all_busy, lo, hi), lo, hi):
        label = (host.label(gap) if gap[1] - gap[0] >= SHORT_GAP_NS
                 else f"gaps under {SHORT_GAP_NS // 1000} us")
        entry = idle_by_label[label]
        entry[0] += 1
        entry[1] += (gap[1] - gap[0]) / 1e9
    busy_s = sum(d["busy_s"] for d in per_device.values()) / n
    collective_s = sum(d["collective_s"] for d in per_device.values()) / n
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle_by_label.items(), key=lambda kv: -kv[1][1])[:top]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "collective_s": collective_s,
        "per_device": per_device,
        "device_ops": [[name, ns / 1e9 / n] for name, ns in ops],
        "idle_gaps": [[f"{label} (x{count})", secs]
                      for label, (count, secs) in idle],
    }
