"""The program's spans beside the device trace (``bench/spans.py``): the
clock that puts them on the profile's time base, under a real profile on
the CPU; the idle gaps they name and the busy time split by module, on the
trace recorded on a v5e chip (``bench/fixtures/trace_v5e.json.gz``) with
spans and modules made by hand; and the readers of the per-layer metrics
that read them."""
import gzip
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace, harness, spans  # noqa: E402

FIXTURE = ROOT / "bench" / "fixtures" / "trace_v5e.json.gz"


@pytest.fixture
def fixture_trace():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


@pytest.fixture
def program_tracer():
    """The program's tracer, empty before and after the test."""
    from repro.analytics import tracing
    tracing.tracer().clear()
    yield tracing.tracer()
    tracing.tracer().clear()


def test_tracer_span_lands_on_the_profile_clock(tmp_path):
    """An annotation and a tracer span around the same 20 ms sleep agree
    within 100 us at both ends once the span is mapped by two anchors."""
    import jax
    from repro.analytics.tracing import Tracer
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            a0 = spans.anchor()
            with jax.profiler.TraceAnnotation("bench.test.sleep"):
                t0 = time.perf_counter()
                time.sleep(0.02)
                t1 = time.perf_counter()
            tr.add_complete("test.sleep", "test", t0, t1)
            time.sleep(0.01)
            a1 = spans.anchor()
    finally:
        jax.profiler.stop_trace()
    trace = spans.load(str(tmp_path))
    assert len(trace["anchors"]) == 2
    to_ns = spans.clock([a0, a1], spans.anchor_points(trace))
    (mapped,) = spans.map_spans(tr.spans(), to_ns, *trace["window"])
    (ann,) = [h for h in trace["host"] if h[0] == "bench.test.sleep"]
    assert abs(mapped[1] - ann[1]) < 100_000
    assert abs((mapped[1] + mapped[2]) - (ann[1] + ann[2])) < 100_000


def test_clock_fits_offset_and_rate():
    to_ns = spans.clock([10.0, 20.0], [1_000.0, 10_001_000.0])
    assert to_ns(10.0) == 1_000.0
    assert to_ns(15.0) == pytest.approx(5_001_000.0)
    assert spans.clock([3.0], [7.0])(3.5) == pytest.approx(7.0 + 5e8)
    with pytest.raises(ValueError):
        spans.clock([1.0, 2.0], [5.0])


def test_map_spans_clips_to_the_window():
    from repro.analytics.tracing import Span
    ss = [Span("a", "t", 1.0, 2.0, trace_id=4), Span("b", "t", 9.0, 1.0)]
    out = spans.map_spans(ss, lambda t: t * 1e9, int(2e9), int(5e9))
    assert out == [["a", int(2e9), int(1e9), 4]]


def _gap(trace):
    """The fixture's one idle gap of 100 us or more."""
    lo, hi = trace["window"]
    busy = devtrace.union([(s, s + d) for ops in trace["devices"].values()
                           for _, s, d in ops], lo, hi)
    (gap,) = [g for g in devtrace.gaps(busy, lo, hi)
              if g[1] - g[0] >= devtrace.SHORT_GAP_NS]
    return gap


def test_idle_gap_takes_the_innermost_program_span(fixture_trace):
    lo, hi = fixture_trace["window"]
    g0, g1 = _gap(fixture_trace)
    program = [["serve.round", lo, hi - lo, -1],
               ["plan.dispatch", g0 - 1000, g1 - g0 + 2000, 7],
               ["runtime.gc", hi - 5000, 1000, -1]]
    idle = spans.idle_by_span(fixture_trace, program)
    assert idle[0] == ["plan.dispatch (x1)", pytest.approx(0.00295025)]
    assert sum(s for _, s in idle) == pytest.approx(
        devtrace.reduce(fixture_trace)["window_s"]
        - devtrace.reduce(fixture_trace)["busy_s"])
    assert spans.program_share(idle, {"plan.dispatch"}) == 1.0


def test_idle_under_counts_idle_time_while_a_span_is_open(fixture_trace):
    lo, hi = fixture_trace["window"]
    g0, g1 = _gap(fixture_trace)
    half = (g1 - g0) // 2
    program = [["plan.dispatch", g0 + half, 10**9, 7],
               ["serve.round", lo, hi - lo, -1]]
    assert spans.idle_under(fixture_trace, program, {"plan.dispatch"}) == (
        pytest.approx((g1 - g0 - half) / 1e9, abs=2e-6))
    assert spans.idle_under(fixture_trace, program, {"plan.lower"}) == 0.0


def test_uncovered_idle_gap_keeps_the_runtime_label(fixture_trace):
    g0, g1 = _gap(fixture_trace)
    program = [["plan.dispatch", g1 + 10, 1000, 7]]    # after the gap
    idle = spans.idle_by_span(fixture_trace, program)
    assert idle[0] == devtrace.reduce(fixture_trace)["idle_gaps"][0]
    assert idle[0][0] == "ReadSyncFlag (x1)"
    assert spans.program_share(idle, {"plan.dispatch"}) == 0.0


def test_busy_by_plan_sums_to_busy_time(fixture_trace):
    """Two plans back to back, split at the idle gap between them."""
    lo, hi = fixture_trace["window"]
    g0, g1 = _gap(fixture_trace)
    dev = "/device:TPU:0"
    busy_s = devtrace.reduce(fixture_trace)["busy_s"]
    fixture_trace["modules"] = {dev: [["jit_plan_q3", lo - 10**8,
                                       g0 - lo + 10**8],
                                      ["jit_plan_q5", g1, hi - g1 + 10**8]]}
    by_plan = spans.busy_by_plan(fixture_trace)
    assert {name for name, _ in by_plan} == {"jit_plan_q3", "jit_plan_q5"}
    assert sum(s for _, s in by_plan) == pytest.approx(busy_s, rel=1e-9)
    # busy time under no module is named as such, and still sums up
    fixture_trace["modules"] = {dev: [["jit_plan_q3", lo, (g0 - lo) // 2]]}
    by_plan = dict(spans.busy_by_plan(fixture_trace))
    assert set(by_plan) == {"jit_plan_q3", "(no module)"}
    assert sum(by_plan.values()) == pytest.approx(busy_s, rel=1e-9)


def test_execute_split_names_pickup_dispatch_wait_and_gc():
    from repro.analytics.tracing import Span
    own = [Span("dispatch.build", "s", 1.000, 0.010, trace_id=3),
           Span("morsel.run", "s", 1.012, 0.900, trace_id=3),
           Span("plan.dispatch", "p", 1.013, 0.002, trace_id=3),
           Span("plan.device_wait", "p", 1.015, 0.895, trace_id=3)]
    gcs = [Span("runtime.gc", "runtime", 1.500, 0.050),
           Span("runtime.gc", "runtime", 5.000, 0.050)]
    out = spans.execute_split({"execute": 0.902}, own, gcs)
    assert out["execute"] == 0.902
    assert out["pickup"] == pytest.approx(0.002)
    assert out["dispatch"] == pytest.approx(0.002)
    assert out["device_wait"] == pytest.approx(0.895)
    assert out["gc"] == pytest.approx(0.050)


def _view(completed, trace=None):
    cell = harness.load_cell("tpch_sf10.scan")
    return harness.RunView(cell, 30.0, completed, 0, trace, {}, {}, {}, {})


def _request(rid):
    return harness.Request(0, "q1", 0.0, 1.0, {}, {"execute": 1.0}, None,
                           rid)


def test_span_readers_on_a_synthetic_window(program_tracer):
    tr = program_tracer
    tr.add_complete("queue.wait", "queue", 0.0, 1.0, trace_id=1)
    tr.add_complete("queue.wait", "queue", 1.5, 1.6, trace_id=2)
    tr.add_complete("queue.wait", "queue", 1.6, 1.7, trace_id=9)  # not done
    tr.add_complete("serve.round", "service", 0.2, 0.7)
    tr.add_complete("serve.round", "service", 0.9, 1.5)
    tr.add_complete("plan.dispatch", "plan", 1.0, 1.001, trace_id=1)
    tr.add_complete("plan.dispatch", "plan", 1.1, 1.102, trace_id=1)
    tr.add_complete("plan.dispatch", "plan", 1.6, 1.604, trace_id=2)
    tr.add_complete("plan.device_wait", "plan", 1.2, 1.5, trace_id=1)
    tr.add_complete("plan.device_wait", "plan", 1.7, 1.8, trace_id=2)
    view = _view([_request(1), _request(2), _request(5)])
    read = {m: harness.metric_reader(m)(view)
            for m in ("serving.round_block_ms", "executor.dispatch_ms",
                      "executor.device_wait_ms")}
    # request 1 waits 0.5 + 0.1 s behind rounds, request 2 none;
    # request 5 (a deduplicated peer) has no spans of its own
    assert read["serving.round_block_ms"] == pytest.approx(300.0)
    assert read["executor.dispatch_ms"] == pytest.approx(3.5)
    assert read["executor.device_wait_ms"] == pytest.approx(200.0)


def test_span_readers_give_nothing_without_spans(program_tracer):
    view = _view([_request(1)])
    for m in ("serving.round_block_ms", "executor.dispatch_ms",
              "executor.device_wait_ms"):
        assert harness.metric_reader(m)(view) is None


def test_hash_aggregate_share_reads_the_named_kernel():
    read = harness.metric_reader("kernel.hash_aggregate_share")
    trace = {"busy_s": 10.0, "device_ops": [
        ["hash_aggregate.1: custom-call tpu_custom_call -> f32[8,5,128]",
         3.5],
        ["fusion.2: fusion -> f32[60000000]", 3.0],
        ["hash_aggregate.4: custom-call tpu_custom_call -> f32[8,2,128]",
         0.5],
        ["hash_aggregate_sweep.1: fusion -> f32[8]", 1.0]]}
    assert read(_view([], trace)) == pytest.approx(40.0)
    unnamed = {"busy_s": 10.0, "device_ops": [
        ["_unknown_.1: custom-call tpu_custom_call -> f32[8,5,128]", 4.0]]}
    assert read(_view([], unnamed)) is None
    assert read(_view([], None)) is None
