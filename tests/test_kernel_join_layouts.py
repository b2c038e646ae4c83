"""The kernel join's partition layouts, pooled per base column.

``columnar.join_side_layout`` builds one side of ``pkfk_join_kernel``'s
dense (P, cap) layout from the key column alone; ``JoinIndexPool.layout``
builds it once per column array and the planner passes it to every plan
whose kernel join has base-column keys on both sides (``kernel_joins``).
Covered: the pooled layouts give the same bits as layouts built in the
call (uniform keys, and skewed keys that overflow so the residual pass
runs), a probe key derived by a Project keeps its layouts in the plan and
matches the sorted join, and served q3 and q5 share the layouts they have
in common, report them on their ``plan.dispatch`` spans and let them go
with the dataset, and distinct layouts build side by side.
"""
import gc
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import plan as L
from repro.analytics import planner, tracing
from repro.analytics.columnar import (JoinSide, Table, join_layout,
                                      join_side_layout, pkfk_join,
                                      pkfk_join_kernel)
from repro.analytics.service import AnalyticsService, ServiceConfig
from repro.analytics.tpch import LOGICAL_QUERIES, generate


def _tables(rng, n_dim, n_fact, skewed):
    dk = jnp.asarray(rng.permutation(n_dim), jnp.int32)
    dim = Table({"dk": dk,
                 "payload": jnp.asarray(rng.randn(n_dim), jnp.float32)})
    dim = dim.filter(jnp.asarray(rng.rand(n_dim) < 0.8))
    if skewed:
        # half the probes hammer 32 hot keys: partitions overflow
        keys = np.concatenate([rng.randint(0, 32, n_fact // 2),
                               rng.randint(0, n_dim + 64,
                                           n_fact - n_fact // 2)])
    else:
        keys = rng.randint(0, n_dim + 100, n_fact)   # misses included
    fact = Table({"fk": jnp.asarray(keys, jnp.int32)})
    return fact.filter(jnp.asarray(rng.rand(n_fact) < 0.9)), dim


@pytest.mark.parametrize("skewed,n_partitions,cf,residual", [
    (False, 32, 2.0, True),
    (True, 2, 0.25, True),
    (True, 2, 0.25, False),
], ids=["uniform", "skewed-residual", "skewed-counted"])
def test_pooled_layouts_match_the_in_call_layouts(rng, skewed, n_partitions,
                                                  cf, residual):
    fact, dim = _tables(rng, 2048, 4096, skewed)
    P, build_cap, probe_cap = join_layout(4096, 2048, n_partitions, cf)
    pool = planner.JoinIndexPool()
    layouts = (pool.layout("f", "fk", fact.col("fk"), P, probe_cap,
                           "probe"),
               pool.layout("d", "dk", dim.col("dk"), P, build_cap, "build"))
    assert pool.layout_builds == 2
    kw = dict(n_partitions=n_partitions, capacity_factor=cf,
              mode="interpret", residual=residual)
    inline, inline_ovf = pkfk_join_kernel(fact, dim, "fk", "dk",
                                          {"p": "payload"}, **kw)
    pooled, pooled_ovf = pkfk_join_kernel(fact, dim, "fk", "dk",
                                          {"p": "payload"}, layouts=layouts,
                                          **kw)
    overflow = int(layouts[0].overflow + layouts[1].overflow)
    assert (overflow > 0) == skewed
    assert int(pooled_ovf) == int(inline_ovf) == (0 if residual
                                                  else overflow)
    np.testing.assert_array_equal(np.asarray(pooled.weights()),
                                  np.asarray(inline.weights()))
    np.testing.assert_array_equal(np.asarray(pooled.col("p")),
                                  np.asarray(inline.col("p")))
    if residual:                     # exact under any skew
        ref = pkfk_join(fact, dim, "fk", "dk", {"p": "payload"})
        np.testing.assert_array_equal(np.asarray(pooled.weights()),
                                      np.asarray(ref.weights()))
        w = np.asarray(ref.weights()) > 0
        np.testing.assert_array_equal(np.asarray(pooled.col("p"))[w],
                                      np.asarray(ref.col("p"))[w])


def test_probe_layout_maps_each_row_to_its_slot(rng):
    """The probe side's row -> slot map reads each row's key back from the
    padded keys; rows beyond capacity map to P * cap."""
    keys = jnp.asarray(rng.randint(0, 64, 3000), jnp.int32)
    lay = join_side_layout(keys, n_partitions=4, cap=512, side="probe")
    slots = np.asarray(lay.rows)
    placed = slots < 4 * 512
    assert int(lay.overflow) == int((~placed).sum()) > 0
    np.testing.assert_array_equal(
        np.asarray(lay.keys).reshape(-1)[slots[placed]],
        np.asarray(keys)[placed])
    assert len(set(slots[placed])) == int(placed.sum())
    assert (np.asarray(lay.keys) >= 0).sum() == int(placed.sum())


def _derived_key_plan() -> L.LogicalPlan:
    li = L.scan("lineitem").project(_ok=L.col("l_orderkey") * 1)
    li = li.join(L.scan("orders"), "_ok", "o_orderkey",
                 {"_od": "o_orderdate"})
    li = li.project(_rev=L.col("l_extendedprice") * L.col("_od"))
    return L.LogicalPlan(li.aggregate(None, 1, revenue=("sum", "_rev")),
                         ("revenue", "_count", "_overflow"), name="derived")


def test_derived_probe_key_keeps_its_layouts_in_the_plan():
    data = generate(scale=0.002, seed=4)
    tables = data.as_jax()
    plan = _derived_key_plan()
    kernel = planner.ExecutionContext(join="kernel", mode="interpret")
    cp = planner.compile_plan(plan, tables, kernel)
    assert cp.joins == planner.KernelJoins((), 0, 1)
    got = cp(tables)
    ref = planner.execute_plan(plan, tables,
                               planner.ExecutionContext(join="sorted"))
    for k in ("revenue", "_count", "_overflow"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k]))


def _serve(svc, name, tables, ctx):
    rid = svc.submit(LOGICAL_QUERIES[name], tables, context=ctx)
    return rid, svc.drain()[rid]


def test_served_q3_and_q5_share_pooled_layouts():
    planner.clear_plan_cache()
    pool = planner.join_index_pool()
    pool.clear()
    data = generate(scale=0.004, seed=6)
    tables = data.as_jax()
    ctx = planner.ExecutionContext(join="kernel", mode="ref")
    specs = {q: set(planner.compile_plan(LOGICAL_QUERIES[q], tables,
                                         ctx).joins.layouts)
             for q in ("q3", "q5")}
    shared = specs["q3"] & specs["q5"]
    rows = {t: next(iter(c.values())).shape[0] for t, c in tables.items()}
    assert planner.LayoutSpec("lineitem", "l_orderkey", *join_layout(
        rows["lineitem"], rows["orders"], ctx.n_partitions,
        ctx.capacity_factor)[::2], "probe") in shared
    cfg = ServiceConfig(n_pools=1, workers_per_pool=1, morsel_rows=None)
    with tracing.tracing() as tr, AnalyticsService(cfg) as svc:
        r3, a3 = _serve(svc, "q3", tables, ctx)
        assert pool.layout_builds == len(specs["q3"])
        r5, a5 = _serve(svc, "q5", tables, ctx)
        built = pool.layout_builds
        assert built == len(specs["q3"] | specs["q5"])
        _serve(svc, "q3", tables, ctx)
        _serve(svc, "q5", tables, ctx)
        assert pool.layout_builds == built
        spans = tr.spans()
    assert a3.value is not None and a5.value is not None
    # every kernel join of q3 (2) and q5 (4, nation's too when forced)
    # runs on pooled layouts
    for rid, hoisted in ((r3, 2), (r5, 4)):
        args = [dict(s.args) for s in spans
                if s.name == "plan.dispatch" and s.trace_id == rid]
        assert args and all((a["hoisted_joins"], a["inline_joins"])
                            == (hoisted, 0) for a in args)
    # the layouts go with the dataset, as the argsorts do
    col = weakref.ref(tables["lineitem"]["l_orderkey"])
    del data, tables, svc
    gc.collect()
    assert col() is None
    pool.layout("t", "k", jnp.arange(8, dtype=jnp.int32), 1, 128, "build")
    live = [k for k in pool._lru._d if "layout" in k]
    assert live == [("t", "k", live[0][2], "layout", 1, 128, "build")]


def test_layout_pool_does_not_pin_arrays(rng):
    pool = planner.JoinIndexPool()
    arr = jnp.asarray(rng.permutation(1000).astype(np.int32))
    ref = weakref.ref(arr)
    lay = pool.layout("t", "k", arr, 8, 256, "build")
    assert isinstance(lay, JoinSide)
    assert pool.layout("t", "k", arr, 8, 256, "build") is lay
    assert pool.layout_builds == 1
    del arr
    gc.collect()
    assert ref() is None             # the pool must not keep datasets alive


def test_concurrent_first_touch_builds_each_layout_once(rng):
    """Plans compiled side by side ask the pool for the same layouts at
    once: each is built once and every thread gets the same entry."""
    import sys
    import threading

    pool = planner.JoinIndexPool()
    cols = [jnp.asarray(rng.randint(0, 500, 2000), jnp.int32)
            for _ in range(3)]
    got, errors = [], []

    def touch(i):
        try:
            for j in range(len(cols)):
                c = cols[(i + j) % len(cols)]
                got.append((id(c), pool.layout("t", "k", c, 4, 1024,
                                               "probe")))
        except Exception as e:          # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=touch, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    assert pool.layout_builds == len(cols)
    assert len(got) == 16 * len(cols)
    for c in cols:
        assert len({id(lay) for k, lay in got if k == id(c)}) == 1


def test_distinct_layouts_build_side_by_side(monkeypatch):
    """A layout whose build (a cold compile, on the chip tens of seconds)
    is under way holds back only the callers that want that layout."""
    import threading

    started, release = threading.Event(), threading.Event()
    build = planner.join_side_layout

    def slow_for_a(keys, n_partitions, cap, side):
        if cap == 256:
            started.set()
            assert release.wait(60)
        return build(keys, n_partitions, cap, side)

    monkeypatch.setattr(planner, "join_side_layout", slow_for_a)
    pool = planner.JoinIndexPool()
    a = jnp.arange(100, dtype=jnp.int32)
    b = jnp.arange(50, dtype=jnp.int32)
    ta = threading.Thread(target=pool.layout,
                          args=("t", "a", a, 2, 256, "build"))
    tb = threading.Thread(target=pool.layout,
                          args=("t", "b", b, 2, 128, "build"))
    ta.start()
    try:
        assert started.wait(30)
        tb.start()
        tb.join(30)
        b_done_first = not tb.is_alive() and pool.layout_builds == 1
    finally:
        release.set()
        ta.join(30)
        if tb.is_alive():
            tb.join(30)
    assert b_done_first
    assert pool.layout_builds == 2 and not pool._building
    pool.layout("t", "a", a, 2, 256, "build")
    assert pool.layout_builds == 2
