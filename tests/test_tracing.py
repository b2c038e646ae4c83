"""Request-scoped tracing (analytics/tracing.py) + its serving-path hooks.

Three layers of coverage:

  * Tracer unit behaviour — begin/end handles, retrospective spans,
    bounded ring + drop accounting, flight-recorder snapshots, and the
    two exports (Chrome trace-event JSON, deterministic text timeline
    golden-snapshotted in tests/fixtures/trace_timeline.txt);
  * the zero-cost-when-disabled and cache-key contracts — an untraced
    service round allocates NO spans, and flipping the tracing flag must
    NOT change the plan-cache key (only telemetry's ``record`` re-jits);
  * the hammer: a traced chaos round (steals + retries + injected
    faults, morsel-split over two pools) after which every span is
    closed, spans with parents nest inside them, every completed
    request's phase attribution sums to <= its wall latency, and every
    fired fault left a flight-recorder dump.
"""
import gc
import json
import os

import pytest

from repro.analytics import tracing
from repro.analytics.planner import ExecutionContext, compile_plan, \
    plan_cache_info
from repro.analytics.service import (AnalyticsService, RetryPolicy,
                                     ServiceConfig, ServiceFaultInjector,
                                     ThreadPlacement)
from repro.analytics.service.service import PHASES
from repro.analytics.tpch import LOGICAL_QUERIES, generate, submit_query
from repro.analytics.tracing import Span, Trace, Tracer

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures")


@pytest.fixture(scope="module")
def data():
    return generate(scale=0.004, seed=1)


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the global flag off and the
    process tracer empty (mirrors telemetry's flag hygiene)."""
    tracing.disable_tracing()
    tracing.tracer().clear()
    yield
    tracing.disable_tracing()
    tracing.tracer().clear()


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------
def test_begin_end_closes_and_nests():
    tr = Tracer()
    outer = tr.begin("plan.execute", "plan", trace_id=3, pid="plan")
    assert [o.span_id for o in tr.open_spans()] == [outer]
    inner = tr.begin("merge.partials", "scheduler", trace_id=3,
                     parent_id=outer)
    s_in = tr.end(inner, rows=10)
    s_out = tr.end(outer)
    assert tr.open_spans() == []
    assert s_in.parent_id == outer and s_out.span_id == outer
    assert dict(s_in.args)["rows"] == 10
    assert s_out.t0 <= s_in.t0 and s_in.t1 <= s_out.t1
    # double-end is a no-op, not an error
    assert tr.end(outer) is None


def test_ring_is_bounded_and_counts_drops():
    tr = Tracer(max_spans=4)
    for i in range(6):
        tr.instant("morsel.steal", "scheduler", seq=i)
    assert tr.created == 6 and tr.dropped == 2
    assert [dict(s.args)["seq"] for s in tr.spans()] == [2, 3, 4, 5]


def test_flight_dump_snapshots_window_and_open_spans():
    tr = Tracer(flight_window=2)
    for i in range(4):
        tr.add_complete("morsel.run", "scheduler", 10.0 + i, 10.5 + i,
                        seq=i)
    sid = tr.begin("dispatch.build", "service", trace_id=9)
    dump = tr.flight_dump("fault.build_fail", ordinal=1)
    assert dump.reason == "fault.build_fail" and dump.args["ordinal"] == 1
    # window tail (2 finished) + the still-open span, rendered open-ended
    assert len(dump.spans) == 3
    assert [dict(s.args)["seq"] for s in dump.spans[:2]] == [2, 3]
    assert dict(dump.spans[-1].args)["open"] is True
    assert tr.flight.dumps()[-1] is dump
    tr.end(sid)


def test_chrome_trace_structure_roundtrips():
    tr = Tracer()
    tr.add_complete("queue.wait", "queue", 5.0, 5.002, trace_id=1)
    tr.add_complete("morsel.run", "scheduler", 5.002, 5.004, trace_id=1,
                    pid="pool0", tid="pool0-w1")
    tr.instant("morsel.steal", "scheduler", trace_id=1, pid="pool1")
    doc = json.loads(json.dumps(tr.trace().to_chrome_trace()))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    # 3 process lanes + 3 thread lanes named
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    assert {e["args"]["name"] for e in meta
            if e["name"] == "process_name"} == {"service", "pool0", "pool1"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"queue.wait", "morsel.run"}
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert all(e["args"]["trace_id"] == 1 for e in xs)
    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"


def test_timeline_matches_golden():
    spans = [
        Span("queue.wait", "queue", 100.000, 0.004, trace_id=7,
             pid="service", tid="main", args=(("cls", 1),)),
        Span("batch.group", "batcher", 100.004, 0.001, pid="service",
             tid="main", args=(("requests", 2),)),
        Span("dispatch.build", "service", 100.005, 0.006, trace_id=7,
             pid="service", tid="main"),
        Span("morsel.run", "scheduler", 100.011, 0.010, trace_id=7,
             pid="pool0", tid="pool0-w0", args=(("seq", 0),)),
        Span("morsel.steal", "scheduler", 100.013, 0.0, trace_id=7,
             pid="pool1", tid="pool1-w0", args=(("victim", 0),)),
        Span("morsel.run", "scheduler", 100.013, 0.009, trace_id=7,
             pid="pool1", tid="pool1-w0", args=(("seq", 1),)),
        Span("merge.partials", "scheduler", 100.022, 0.002, trace_id=7,
             pid="service", tid="drain"),
        Span("result.deliver", "service", 100.024, 0.001, trace_id=7,
             pid="service", tid="drain"),
    ]
    got = Trace(spans).render_timeline(width=40)
    with open(os.path.join(FIXDIR, "trace_timeline.txt")) as f:
        want = f.read().strip("\n")
    assert got == want, f"timeline drifted\n--- got ---\n{got}"


def test_tracing_context_manager_restores_flag():
    assert not tracing.tracing_enabled()
    with tracing.tracing() as tr:
        assert tracing.tracing_enabled() and tr is tracing.tracer()
    assert not tracing.tracing_enabled()


# ---------------------------------------------------------------------------
# contracts: zero-cost when disabled; flag NOT in the plan-cache key
# ---------------------------------------------------------------------------
def _cfg(faults=None, **kw):
    kw.setdefault("n_pools", 2)
    kw.setdefault("workers_per_pool", 2)
    kw.setdefault("morsel_rows", 997)
    kw.setdefault("placement", ThreadPlacement.SPARSE)
    kw.setdefault("retry", RetryPolicy(max_attempts=4, base_backoff_s=0.002,
                                       max_backoff_s=0.02))
    return ServiceConfig(faults=faults, **kw)


def _ctx():
    return ExecutionContext(executor="xla")


def test_disabled_tracing_allocates_nothing(data):
    """The satellite-6 contract: a full served round with tracing off
    must not allocate a single span (every hook is behind ONE flag
    read)."""
    before = tracing.tracer().created
    with AnalyticsService(_cfg()) as svc:
        rids = [submit_query(svc, n, data, context=_ctx())
                for n in LOGICAL_QUERIES]
        results = svc.drain()
    assert all(results[r].value is not None for r in rids)
    assert tracing.tracer().created == before
    # latency attribution is NOT gated on tracing — it is arithmetic over
    # stamps the service keeps anyway (same family as latency_s)
    assert all(results[r].phases is not None for r in rids)


def test_tracing_flag_not_in_plan_cache_key(data):
    """Flipping tracing must hit the same cache entry: plan.dispatch is a
    host-side span around an unchanged executable (only telemetry's
    ``record`` flag adds traced ops and re-jits)."""
    tables = data.as_jax()
    plan = LOGICAL_QUERIES["q6"]
    off = compile_plan(plan, tables, _ctx())
    h0 = plan_cache_info().hits
    tracing.enable_tracing()
    try:
        on = compile_plan(plan, tables, _ctx())
    finally:
        tracing.disable_tracing()
    assert on.cache_key == off.cache_key
    assert plan_cache_info().hits == h0 + 1   # hit, not a re-compile


# ---------------------------------------------------------------------------
# the hammer: traced chaos round — conservation under concurrency
# ---------------------------------------------------------------------------
def test_hammer_span_conservation_under_chaos(data):
    faults = ServiceFaultInjector(seed=11, build_fail_rate=0.15,
                                  poison_rate=0.10)
    names = list(LOGICAL_QUERIES) * 5          # 25 requests, 5 plans
    with tracing.tracing() as tr:
        with AnalyticsService(_cfg(faults)) as svc:
            rids = [submit_query(svc, n, data, context=_ctx(),
                                 client_id=i % 3, priority=1 + i % 2)
                    for i, n in enumerate(names)]
            results = svc.drain()
            st = svc.stats()
        spans = tr.spans()
        dumps = tr.flight.dumps()
        open_left = tr.open_spans()

    # every span closed
    assert open_left == []
    # the storm actually stormed (retries fired => backoff spans exist)
    assert faults.builds_failed + faults.waits_poisoned > 0
    assert any(s.name == "retry.backoff" for s in spans)
    # steals fired under morsel-split (two pools, shared backlog)
    assert any(s.name == "morsel.steal" for s in spans)
    # spans with parents nest inside them (time containment)
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.parent_id >= 0 and s.parent_id in by_id:
            p = by_id[s.parent_id]
            assert p.t0 <= s.t0 and s.t1 <= p.t1 + 1e-6
    # phase attribution: disjoint sub-intervals => sums <= wall
    completed = [results[r] for r in rids if results[r].value is not None]
    assert completed
    for res in completed:
        assert res.phases is not None
        assert set(res.phases) == set(PHASES)
        assert all(v >= 0.0 for v in res.phases.values())
        assert sum(res.phases.values()) <= res.latency_s + 1e-6, res
    # the stats() decomposition is populated and ordered p50 <= p99
    assert st.phase_p99_ms["execute"] > 0.0
    for ph in PHASES:
        assert st.phase_p50_ms[ph] <= st.phase_p99_ms[ph] + 1e-9
    # every fired fault produced a non-empty flight dump
    fired = faults.builds_failed + faults.waits_poisoned
    fault_dumps = [d for d in dumps if d.reason.startswith("fault.")]
    assert len(fault_dumps) == fired
    assert all(d.spans for d in fault_dumps)
    # request story: every completed request left queue.wait + deliver
    seen = {s.trace_id: set() for s in spans}
    for s in spans:
        seen[s.trace_id].add(s.name)
    for r in rids:
        if results[r].value is not None:
            assert "queue.wait" in seen.get(r, set())
            assert "result.deliver" in seen.get(r, set())


# ---------------------------------------------------------------------------
# serving rounds, dispatch vs device wait, the gc hook, the profiler
# ---------------------------------------------------------------------------
def _inside(inner, outer) -> bool:
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1 + 1e-6


def test_serve_round_holds_its_dispatches_and_waits_nest_in_morsels(data):
    """Whole-plan dispatch: each round's serve.round span contains the
    dispatch.build of every request it served; each request has a
    plan.dispatch and a plan.device_wait carrying its id, the wait
    inside that request's morsel.run."""
    names = ["q1", "q3", "q6"]
    with tracing.tracing() as tr:
        with AnalyticsService(_cfg(morsel_rows=None)) as svc:
            rids = [submit_query(svc, n, data, context=_ctx())
                    for n in names]
            results = svc.drain()
        spans = tr.spans()
    assert all(results[r].value is not None for r in rids)
    rounds = [s for s in spans if s.name == "serve.round"]
    assert rounds and sum(dict(s.args)["requests"] for s in rounds) == 3
    for r in rids:
        mine = {n: [s for s in spans if s.trace_id == r and s.name == n]
                for n in ("dispatch.build", "plan.dispatch",
                          "plan.device_wait", "morsel.run", "queue.wait")}
        assert len(mine["queue.wait"]) == 1
        assert mine["plan.dispatch"] and mine["plan.device_wait"]
        (build,) = mine["dispatch.build"]
        assert any(_inside(build, rd) for rd in rounds)
        (run,) = mine["morsel.run"]
        assert all(_inside(w, run) for w in mine["plan.device_wait"])
        assert all(_inside(d, run) for d in mine["plan.dispatch"])
        ph = results[r].phases
        took = sum(s.dur for s in mine["plan.dispatch"]
                   + mine["plan.device_wait"])
        assert took <= ph["execute"] + 1e-3


def test_untraced_whole_plan_round_allocates_nothing_and_no_gc_hook(data):
    """The zero-cost contract holds on the whole-plan path too, with the
    serve.round / plan.dispatch / plan.device_wait sites, and tracing
    off leaves no garbage-collector hook behind."""
    before = tracing.tracer().created
    with AnalyticsService(_cfg(morsel_rows=None)) as svc:
        rids = [submit_query(svc, n, data, context=_ctx())
                for n in ("q1", "q3")]
        results = svc.drain()
    assert all(results[r].value is not None for r in rids)
    assert tracing.tracer().created == before
    assert tracing._on_gc not in gc.callbacks


def test_gc_hook_registered_only_while_tracing():
    assert tracing._on_gc not in gc.callbacks
    with tracing.tracing() as tr:
        assert gc.callbacks.count(tracing._on_gc) == 1
        with tracing.tracing():            # nested: still one hook
            assert gc.callbacks.count(tracing._on_gc) == 1
        n0 = tr.gc_short[0] + len([s for s in tr.spans()
                                   if s.name == "runtime.gc"])
        gc.collect()
        n1 = tr.gc_short[0] + len([s for s in tr.spans()
                                   if s.name == "runtime.gc"])
        assert n1 >= n0 + 1
    assert tracing._on_gc not in gc.callbacks
    assert not tracing.tracing_enabled()


def test_long_collections_are_spanned_short_ones_counted():
    tr = Tracer()
    tr.note_gc(1.0, 1.0004, 0, 3)
    tr.note_gc(2.0, 2.0150, 2, 900)
    assert tr.gc_short[0] == 1 and tr.gc_short[1] == pytest.approx(4e-4)
    (span,) = tr.spans()
    assert span.name == "runtime.gc" and span.dur == pytest.approx(0.015)
    assert dict(span.args) == {"generation": 2, "collected": 900}


def test_tracing_follows_the_jax_profiler(tmp_path):
    import jax
    assert not tracing.follow_profiler()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.follow_profiler() and tracing.tracing_enabled()
        assert tracing._on_gc in gc.callbacks
    finally:
        jax.profiler.stop_trace()
    assert not tracing.follow_profiler()
    assert tracing._on_gc not in gc.callbacks
    # an explicit enable outlives the profiler's end
    with tracing.tracing():
        assert tracing.follow_profiler()
    assert not tracing.tracing_enabled()
