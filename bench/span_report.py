#!/usr/bin/env python3
"""Traced windows of one benchmark cell, read with the program's own spans.

    python3 bench/span_report.py --workload tpch_sf1.join --seeds 7 8 9 \\
        --seconds 30 --overhead-pairs 3

One process: the cell's set-up as ``bench/run.py`` does it, then one
profiled window per seed (the program's tracer follows the profiler), each
read with ``bench/spans.py``: the per-layer metrics, idle gaps by program
span, busy time by plan, each query's longest execute
phase split into pickup, dispatch, device wait and garbage collection,
and the tracer's own checks. Then ``--overhead-pairs`` pairs of windows
without the profiler, one with the tracer off and one with it on, for what
tracing costs. No answer is checked against the reference here: that is
``bench/run.py``'s job.

One JSON object per window goes to ``--out`` (one file per window) and, in
short, to standard output. ``--scale`` runs the cell at another scale on
whatever devices JAX finds, to try the tool on the CPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
READERS = ("serving.wait_ms", "serving.round_block_ms",
           "executor.execute_ms", "executor.dispatch_ms",
           "executor.device_wait_ms", "device.idle_share",
           "kernel.hash_aggregate_share")


def anchors_during(seconds: float):
    """(thread, anchors): the thread takes one clock anchor as soon as the
    profiler runs and one shortly before the window ends."""
    from jax.profiler import TraceAnnotation
    from bench import spans
    out: list = []

    def take():
        t_end = time.perf_counter() + 600.0
        while not TraceAnnotation.is_enabled():
            if time.perf_counter() > t_end:
                return
            time.sleep(0.001)
        out.append(spans.anchor())
        time.sleep(max(0.0, seconds - 0.5))
        if TraceAnnotation.is_enabled():
            out.append(spans.anchor())

    thread = threading.Thread(target=take, daemon=True, name="span-anchor")
    thread.start()
    return thread, out


def checks(program, completed, metrics, by_plan, idle, reduced,
           tracer) -> dict:
    """The tracer's contract over one window."""
    from bench import spans
    rids = {r.rid for r in completed}
    waits = {rid: 0 for rid in rids}
    for s in program:
        if s.name == "queue.wait" and s.trace_id in waits:
            waits[s.trace_id] += 1
    builds = {s.trace_id for s in program if s.name == "dispatch.build"
              and s.trace_id in rids and dict(s.args).get("morsels")}
    disp = {s.trace_id for s in program if s.name == "plan.dispatch"}
    wait = {s.trace_id for s in program if s.name == "plan.device_wait"}
    split = (metrics.get("executor.dispatch_ms") or 0.0) + (
        metrics.get("executor.device_wait_ms") or 0.0)
    plan_sum = sum(s for _, s in by_plan)
    return {
        "dropped": tracer.dropped + tracer.gc_dropped,
        "one_queue_wait_each": all(n == 1 for n in waits.values()),
        "shares_with_dispatch_and_wait": len(builds & disp & wait),
        "shares_dispatched": len(builds),
        "dispatch_plus_wait_le_execute": split <= (
            metrics.get("executor.execute_ms") or 0.0) + 1.0,
        "no_unknown_module": not any("_unknown" in m for m, _ in by_plan),
        "plan_sum_over_busy": (plan_sum / reduced["busy_s"]
                               if reduced["busy_s"] else None),
        "program_idle_share": spans.program_share(
            idle, {s.name for s in program}),
        "gc_spans": sum(s.name == "runtime.gc" for s in program),
        "gc_short": list(tracer.gc_short),
    }


def traced_window(harness, session, seconds, device_peaks, first: bool):
    from repro.analytics import tracing
    from bench import devtrace, spans
    tracer = tracing.tracer()
    tracer.clear()
    trace_dir = tempfile.mkdtemp(prefix="span-report-")
    try:
        taker, anchors = anchors_during(seconds)
        win = session.window(seconds, trace_dir)
        taker.join()
        trace = spans.load(trace_dir)
        sample = spans.op_stats_sample(trace_dir) if first else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracing.follow_profiler()              # the profile has ended
    reduced = devtrace.reduce(trace)
    program = tracer.spans()
    lo, hi = trace["window"]
    to_ns = spans.clock(anchors, spans.anchor_points(trace))
    mapped = spans.map_spans(program, to_ns, lo, hi)
    idle = spans.idle_by_span(trace, mapped, top=16)
    by_plan = spans.busy_by_plan(trace)
    completed = win.completed()
    run = harness.RunView(session.cell, win.seconds, completed, 0, reduced,
                          {}, {}, device_peaks, {})
    metrics = {}
    for name in READERS:
        metrics[name] = harness.metric_reader(name)(run)
    by_rid: dict = {}
    for s in program:
        by_rid.setdefault(s.trace_id, []).append(s)
    gcs = [s for s in by_rid.get(-1, []) if s.name == "runtime.gc"]
    longest = {}
    for r in completed:
        if r.phases and (r.query not in longest or r.phases["execute"]
                         > longest[r.query].phases["execute"]):
            longest[r.query] = r
    split = {q: spans.execute_split(r.phases, by_rid.get(r.rid, []), gcs)
             for q, r in sorted(longest.items())}
    check = checks(program, completed, metrics, by_plan, idle, reduced,
                   tracer)
    metrics["device.idle_in_dispatch_share"] = 100.0 * spans.idle_under(
        trace, mapped, {"plan.dispatch"}) / reduced["window_s"]
    out = {"qps": win.qps(), "completed": len(completed),
           "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
           "metrics": metrics, "checks": check,
           "longest_execute_split": split,
           "idle_by_span": idle, "idle_gaps": reduced["idle_gaps"],
           "busy_by_plan": by_plan,
           "device_ops": reduced["device_ops"],
           "anchors": len(anchors)}
    if sample is not None:
        out["op_stats_sample"] = sample
    return out


def overhead_window(session, seconds, traced: bool):
    from repro.analytics import tracing
    tracing.tracer().clear()
    if traced:
        tracing.enable_tracing()
    try:
        win = session.window(seconds)
    finally:
        tracing.disable_tracing()
    return {"tracing": traced, "qps": win.qps(),
            "completed": len(win.completed()),
            "spans": len(tracing.tracer().spans())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--overhead-pairs", type=int, default=0)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--out", default=str(ROOT / ".span_report"))
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    cell = harness.load_cell(args.workload)
    if args.scale is None:
        try:
            devices, device_peaks = harness.accelerator(cell.chips)
        except harness.DeviceError as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
        harness.enable_compile_cache()
    else:
        cell.config["scale"] = args.scale
        devices, device_peaks = jax.devices(), harness.peaks("TPU v5 lite")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    session = harness.Session(cell, devices, T0)
    try:
        for i, seed in enumerate(args.seeds):
            session.load(seed)
            if i == 0:
                session.start()
            session.warm()
            res = traced_window(harness, session, args.seconds,
                                device_peaks, first=(i == 0))
            res.update(workload=cell.name, seed=seed)
            (out_dir / f"{cell.name}.{seed}.json").write_text(
                json.dumps(res, indent=1))
            short = {k: res[k] for k in ("workload", "seed", "qps",
                                         "metrics", "checks",
                                         "longest_execute_split")}
            short["idle_by_span"] = res["idle_by_span"][:6]
            short["busy_by_plan"] = res["busy_by_plan"][:8]
            print(json.dumps(short), flush=True)
        pairs = []
        for _ in range(args.overhead_pairs):
            pairs.append([overhead_window(session, args.seconds, False),
                          overhead_window(session, args.seconds, True)])
        if pairs:
            res = {"workload": cell.name, "seed": args.seeds[-1],
                   "overhead_pairs": pairs}
            (out_dir / f"{cell.name}.overhead.json").write_text(
                json.dumps(res, indent=1))
            print(json.dumps(res), flush=True)
    finally:
        session.close()
    return 0


if __name__ == "__main__":
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
