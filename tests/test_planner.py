"""Logical-plan IR + cost-based physical planner.

Covers: the documented cost-model choice table, logical-plan parity vs the
imperative queries under both executors, the placement-policy x
kernel-executor compose path on a multi-device CPU mesh, the bounded LRU
plan cache, the join-index pool (argsort survival across Table/pytree
reconstruction), and the join_probe-kernel join lowering.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import run_with_devices

from repro.analytics import plan as L
from repro.analytics import planner
from repro.analytics.columnar import Table, pkfk_join, pkfk_join_kernel
from repro.analytics.planner import (CostProfile, ExecutionContext,
                                     choose_aggregate, choose_dist_join,
                                     choose_join, configure_plan_cache,
                                     dist_join_costs, explain,
                                     join_index_pool, plan_cache_info)
from repro.analytics.tpch import (LOGICAL_QUERIES, QUERIES,
                                  clear_plan_cache, generate, run_query)


@pytest.fixture(scope="module")
def data():
    return generate(scale=0.004, seed=1)


@pytest.fixture(autouse=True)
def _fresh_cache_config():
    yield
    configure_plan_cache(planner.DEFAULT_PLAN_CACHE_ENTRIES)
    planner.set_cost_profile(None)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_rows,n_groups,n_cols,expect", [
    # small domain, single aggregate (C = weights + 1 source): segment ops
    (10_000, 37, 2, "xla"),
    # small domain, several fused aggregates: one dense fused sweep wins
    (10_000, 37, 3, "dense"),
    (24_000, 6, 5, "dense"),
    # large domain, single aggregate: the ROADMAP fix — do NOT pay the
    # range-partition argsort; dense is invalid, xla wins
    (24_000, 6_000, 2, "xla"),
    # large domain, very wide fused stack: the argsort is amortized
    (24_000, 6_000, 12, "partitioned"),
])
def test_cost_model_choice_table(n_rows, n_groups, n_cols, expect):
    assert choose_aggregate(n_rows, n_groups, n_cols, "cost") == expect


def test_executor_preference_overrides_cost():
    # "kernel" keeps the PR-1 tuned behavior: always fused, layout by domain
    assert choose_aggregate(24_000, 37, 2, "kernel") == "dense"
    assert choose_aggregate(24_000, 6_000, 2, "kernel") == "partitioned"
    assert choose_aggregate(24_000, 6, 5, "xla") == "xla"


def test_cost_profile_overrides_constants(tmp_path, data):
    """A calibrated profile replaces the hand-set constants, flips the
    cost-based choice accordingly, and keys the plan cache (a recalibration
    can never serve a plan compiled under stale constants)."""
    # default constants: Q1's 5-column stack picks the fused dense sweep
    assert choose_aggregate(24_000, 6, 5, "cost") == "dense"
    prof = tmp_path / "profile.json"
    prof.write_text('{"fused_fixed": 400.0, "fused_per_col": 60.0,'
                    ' "sort_pass_factor": 14.0, "backend": "cpu-ref"}')
    installed = planner.load_cost_profile(str(prof))
    assert installed.source == "cpu-ref"
    try:
        # measured profile says the fused sweep never pays off here
        assert choose_aggregate(24_000, 6, 5, "cost") == "xla"
        # ... and the executor="kernel" preference still overrides cost
        assert choose_aggregate(24_000, 6, 5, "kernel") == "dense"
        clear_plan_cache()
        run_query("q1", data, executor="cost")
        assert plan_cache_info().currsize == 1
        planner.set_cost_profile(None)
        run_query("q1", data, executor="cost")   # same ctx, new profile
        assert plan_cache_info().currsize == 2   # distinct cache entry
    finally:
        planner.set_cost_profile(None)


def test_dist_join_cost_model():
    """Broadcast wins for small dimension builds; partitioned wins once
    the build side outgrows ~probe/(n-1); overrides and profiles apply."""
    ctx = ExecutionContext(executor="xla")
    # tiny dimension table vs a big fact probe: broadcast
    assert choose_dist_join(1 << 18, 1 << 10, 8, ctx) == "broadcast"
    # build as large as the probe: the all-gather moves ~n x more rows
    # than routing both sides once
    assert choose_dist_join(1 << 18, 1 << 18, 8, ctx) == "partitioned"
    # wider mesh moves the crossover lower, never higher
    assert choose_dist_join(1 << 18, 1 << 15, 2, ctx) == "broadcast"
    assert choose_dist_join(1 << 18, 1 << 16, 16, ctx) == "partitioned"
    # explicit override beats the model
    forced = ExecutionContext(executor="xla", dist_join="broadcast")
    assert choose_dist_join(1 << 18, 1 << 18, 8, forced) == "broadcast"
    with pytest.raises(ValueError):
        ExecutionContext(dist_join="bogus")
    # a measured routing overhead shifts the crossover
    costs = dist_join_costs(1 << 18, 1 << 14, 8)
    assert costs["broadcast"] < costs["partitioned"]
    heavy = CostProfile(dist_route_factor=30.0)
    assert choose_dist_join(1 << 18, 1 << 18, 8, ctx, heavy) == "broadcast"


def test_explain_reports_dist_join_choice(data):
    """explain() surfaces the distributed-join decision (with costs) when
    the context carries a mesh: TPC-H dimension builds stay broadcast."""
    import jax
    mesh = jax.make_mesh((1,), ("data",))
    tables = data.as_jax()
    dj = [d for d in explain(LOGICAL_QUERIES["q5"], tables,
                             ExecutionContext(executor="xla", mesh=mesh))
          if d.node == "DistJoin"]
    assert len(dj) == 4 and all(d.costs for d in dj)
    assert all(d.choice == "broadcast" for d in dj)     # small dim builds
    # and honors a forced strategy
    forced = [d for d in explain(LOGICAL_QUERIES["q5"], tables,
                                 ExecutionContext(executor="xla", mesh=mesh,
                                                  dist_join="partitioned"))
              if d.node == "DistJoin"]
    assert all(d.choice == "partitioned" for d in forced)
    # without a mesh the local sorted/kernel decision is reported instead
    local = explain(LOGICAL_QUERIES["q5"], tables,
                    ExecutionContext(executor="xla"))
    assert not any(d.node == "DistJoin" for d in local)


def test_validate_rejects_malformed_plans(data):
    with pytest.raises(ValueError, match="unknown agg op"):
        L.validate(L.scan("t").aggregate("k", 4, x=("mode", "v")))
    with pytest.raises(ValueError, match="at least one aggregate"):
        L.validate(L.Aggregate(L.scan("t"), "k", 4, ()))
    with pytest.raises(ValueError, match="n_groups"):
        L.validate(L.scan("t").aggregate("k", 0, x=("sum", "v")))
    with pytest.raises(ValueError, match="TopK"):
        L.validate(L.scan("t").top_k("v", 5, "i"))
    with pytest.raises(ValueError, match="unknown binary op"):
        L.validate(L.scan("t").filter(L.BinOp("xor", L.col("a"),
                                              L.col("b"))))
    # group dicts cannot feed Table-consuming nodes (would die mid-trace)
    agg = L.scan("t").aggregate("k", 4, x=("sum", "v"))
    with pytest.raises(ValueError, match="must be a Table node"):
        L.validate(agg.filter(L.col("x") > 0))
    with pytest.raises(ValueError, match="must be a Table node"):
        L.validate(agg.project(_y=L.col("x") * 2))
    with pytest.raises(ValueError, match="must be a Table node"):
        L.validate(agg.join(L.scan("d"), "x", "pk"))
    with pytest.raises(ValueError, match="must be a Table node"):
        L.validate(agg.aggregate("x", 4, y=("sum", "x")))
    # the planner validates on cache miss and refuses to trace garbage
    bad = L.LogicalPlan(L.scan("lineitem").aggregate(
        "l_returnflag", 3, x=("mode", "l_quantity")), None)
    with pytest.raises(ValueError, match="unknown agg op"):
        planner.execute_plan(bad, data.as_jax())
    # the median op is a valid aggregate kind
    L.validate(L.scan("t").aggregate("k", 4, m=("median", "v")))


def test_join_choice_is_sorted_without_mxu():
    # the broadcast-compare probe only pays off when Pallas compiles it;
    # on the CPU reference lowering the planner must keep the sorted gather
    ctx = ExecutionContext(executor="cost", mode="ref")
    assert choose_join(1 << 20, 1 << 15, ctx) == "sorted"
    assert choose_join(100, 50, ExecutionContext(join="kernel")) == "kernel"


def test_explain_q3_q18_avoid_partition_argsort(data):
    tables = data.as_jax()
    for name in ("q3", "q18"):
        aggs = [d for d in explain(LOGICAL_QUERIES[name], tables,
                                   ExecutionContext(executor="cost"))
                if d.node == "Aggregate"]
        assert aggs and all(d.choice == "xla" for d in aggs), name
    q1 = [d for d in explain(LOGICAL_QUERIES["q1"], tables,
                             ExecutionContext(executor="cost"))
          if d.node == "Aggregate"]
    assert [d.choice for d in q1] == ["dense"]


# ---------------------------------------------------------------------------
# logical-plan parity vs the imperative reference queries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("executor", ["xla", "kernel", "cost"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_logical_plan_parity(data, name, executor):
    tables = data.as_jax()
    ref_exec = "kernel" if executor == "kernel" else "xla"
    ref = QUERIES[name](tables, executor=ref_exec)
    got = run_query(name, data, executor=executor)
    assert set(got) == set(ref), name
    for k in ref:
        if k == "_overflow":
            assert int(np.asarray(got[k])) == int(np.asarray(ref[k]))
            continue
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-3, rtol=1e-4,
                                   err_msg=f"{name}/{executor}/{k}")


# ---------------------------------------------------------------------------
# placement-policy backend: same plans on a multi-device CPU mesh
# ---------------------------------------------------------------------------
DIST_TEST = """
import numpy as np, jax
from repro.core.config import PlacementPolicy
from repro.analytics.tpch import QUERIES, generate, run_query
from repro.analytics.planner import ExecutionContext

mesh = jax.make_mesh((8,), ("data",))
data = generate(scale=0.004, seed=1)
cases = [(name, "xla", pol) for name in sorted(QUERIES)
         for pol in (PlacementPolicy.FIRST_TOUCH, PlacementPolicy.INTERLEAVE)]
# the compose axis: fused-kernel executor under placement policies
cases += [("q1", "kernel", PlacementPolicy.INTERLEAVE),
          ("q1", "kernel", PlacementPolicy.LOCAL_ALLOC),
          ("q18", "kernel", PlacementPolicy.PREFERRED)]
for name, ex, pol in cases:
    ref = run_query(name, data, executor="xla")
    ctx = ExecutionContext(executor=ex, mesh=mesh, policy=pol,
                           capacity_factor=4.0)
    got = run_query(name, data, context=ctx)
    assert set(got) == set(ref), (name, pol)
    for k in ref:
        if k == "_overflow":
            assert int(np.asarray(got[k])) == 0, (name, pol, k)
            continue
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-2, rtol=1e-4,
                                   err_msg=f"{name}/{pol}/{ex}/{k}")
print("DIST_PLANNER_OK")
"""


def test_placement_policies_execute_logical_plans():
    out = run_with_devices(DIST_TEST, timeout=900)
    assert "DIST_PLANNER_OK" in out


INTERLEAVE_LARGE_DOMAIN_TEST = """
import numpy as np, jax
from repro.analytics.plan import LogicalPlan, scan
from repro.analytics.planner import ExecutionContext, execute_plan
from repro.core.config import PlacementPolicy

# slot domain G/n > DENSE_GROUP_LIMIT: the routed interleave buffer masses
# its padding on the drop slot, so the local aggregation must fall back to
# an occupancy-independent layout — no phantom overflow, no dropped rows
rng = np.random.RandomState(0)
N, G = 65536, 40000
tables = {"t": {"k": rng.randint(0, G, N).astype(np.int32),
                "v": rng.rand(N).astype(np.float32)}}
plan = LogicalPlan(scan("t").aggregate("k", G, s=("sum", "v")),
                   ("s", "_count", "_overflow"))
ref = execute_plan(plan, tables, ExecutionContext(executor="xla"))
mesh = jax.make_mesh((4,), ("data",))
got = execute_plan(plan, tables, ExecutionContext(
    executor="kernel", mesh=mesh, policy=PlacementPolicy.INTERLEAVE))
assert int(np.asarray(got["_overflow"])) == 0, "phantom overflow"
np.testing.assert_allclose(np.asarray(got["s"]), np.asarray(ref["s"]),
                           atol=1e-2, rtol=1e-5)
print("INTERLEAVE_LARGE_OK")
"""


def test_interleave_kernel_large_slot_domain_exact():
    out = run_with_devices(INTERLEAVE_LARGE_DOMAIN_TEST, n_devices=4,
                           timeout=600)
    assert "INTERLEAVE_LARGE_OK" in out


def test_key_index_does_not_cache_tracers(rng):
    """An eager Table joined inside a jit trace must stay usable after."""
    import jax

    dim = Table({"dk": jnp.asarray(rng.permutation(100), jnp.int32),
                 "p": jnp.asarray(rng.randn(100), jnp.float32)})
    fk = jnp.asarray(rng.randint(0, 100, 512), jnp.int32)

    @jax.jit
    def inside(keys):
        return pkfk_join(Table({"fk": keys}), dim, "fk", "dk",
                         {"p": "p"}).col("p")

    a = inside(fk)                   # dim closed over eagerly by the trace
    assert "dk" not in dim.index_cache
    b = pkfk_join(Table({"fk": fk}), dim, "fk", "dk", {"p": "p"}).col("p")
    assert "dk" in dim.index_cache   # eager call may cache concrete arrays
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_join_index_pool_does_not_pin_arrays(rng):
    import gc
    import weakref

    pool = join_index_pool()
    pool.clear()
    arr = jnp.asarray(rng.permutation(1000).astype(np.int32))
    ref = weakref.ref(arr)
    pool.get("t", "k", arr)
    del arr
    gc.collect()
    assert ref() is None             # the pool must not keep datasets alive


# ---------------------------------------------------------------------------
# bounded LRU plan cache
# ---------------------------------------------------------------------------
def test_plan_cache_lru_bound(data):
    clear_plan_cache()
    configure_plan_cache(2)
    run_query("q1", data, executor="xla")
    run_query("q1", data, executor="kernel")
    run_query("q1", data, executor="cost")       # evicts the oldest entry
    info = plan_cache_info()
    assert info.currsize == 2 and info.maxsize == 2
    run_query("q1", data, executor="cost")       # still resident -> hit
    assert plan_cache_info().hits >= 1
    # shrinking evicts immediately
    configure_plan_cache(1)
    assert plan_cache_info().currsize == 1
    with pytest.raises(ValueError):
        configure_plan_cache(0)


# ---------------------------------------------------------------------------
# join-index pool: argsorts survive Tables-pytree reconstruction
# ---------------------------------------------------------------------------
def test_join_index_pool_survives_reruns(data):
    clear_plan_cache()
    pool = join_index_pool()
    pool.clear()
    run_query("q5", data, executor="xla")
    first = pool.builds
    assert first == 4                    # nation, customer, orders, supplier
    # re-dispatch, a different executor, and a REBUILT Tables mapping (new
    # dict objects, same column arrays) must all reuse the pooled argsorts
    run_query("q5", data, executor="xla")
    run_query("q5", data, executor="kernel")
    rebuilt = {t: dict(cols) for t, cols in data.as_jax().items()}
    run_query("q5", rebuilt, executor="xla")
    assert pool.builds == first
    # q3 joins through orders/customer again -> shared entries, +0 new
    run_query("q3", data, executor="xla")
    assert pool.builds == first
    # genuinely new column arrays do build new indexes
    other = generate(scale=0.004, seed=9)
    run_query("q3", other, executor="xla")
    assert pool.builds > first


# ---------------------------------------------------------------------------
# kernel-probed PK-FK join lowering
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_pkfk_join_kernel_matches_sorted(rng, mode):
    n_dim, n_fact = 500, 4096
    dk = jnp.asarray(rng.permutation(n_dim), jnp.int32)
    dim = Table({"dk": dk,
                 "payload": jnp.asarray(rng.randn(n_dim), jnp.float32)})
    dim = dim.filter(jnp.asarray(rng.rand(n_dim) < 0.8))
    # fact keys include misses (>= n_dim) which must zero the mask
    fk = jnp.asarray(rng.randint(0, n_dim + 100, n_fact), jnp.int32)
    fact = Table({"fk": fk}).filter(jnp.asarray(rng.rand(n_fact) < 0.9))
    ref = pkfk_join(fact, dim, "fk", "dk", {"p": "payload"})
    got, ovf = pkfk_join_kernel(fact, dim, "fk", "dk", {"p": "payload"},
                                mode=mode, capacity_factor=4.0)
    assert int(np.asarray(ovf)) == 0
    np.testing.assert_array_equal(np.asarray(got.weights()),
                                  np.asarray(ref.weights()))
    np.testing.assert_allclose(
        np.asarray(got.col("p")) * np.asarray(got.weights()),
        np.asarray(ref.col("p")) * np.asarray(ref.weights()), rtol=1e-6)


def test_pkfk_join_kernel_counts_overflow(rng):
    # all build keys hash-collide into few partitions at capacity 1.0 ->
    # without the residual pass, overflow must be surfaced and overflowed
    # rows degrade to misses (the PR-2 accounting behavior)
    n = 4096
    dim = Table({"dk": jnp.asarray(np.arange(n), jnp.int32),
                 "v": jnp.ones((n,), jnp.float32)})
    fact = Table({"fk": jnp.asarray(np.arange(n), jnp.int32)})
    got, ovf = pkfk_join_kernel(fact, dim, "fk", "dk", {"v": "v"},
                                n_partitions=2, capacity_factor=0.25,
                                mode="ref", residual=False)
    assert int(np.asarray(ovf)) > 0
    assert float(np.asarray(got.weights()).sum()) < n


def test_pkfk_join_kernel_residual_pass_exact(rng):
    """Deliberate capacity overflow on both sides: the residual sorted
    re-probe (default) must recover every missed match — zero misses, and
    values identical to the exact sorted join."""
    n_dim, n_fact = 2048, 4096
    dk = jnp.asarray(rng.permutation(n_dim), jnp.int32)
    dim = Table({"dk": dk,
                 "payload": jnp.asarray(rng.randn(n_dim), jnp.float32)})
    # skewed probe: half the probes hammer 32 hot keys, so partitions
    # overflow at capacity_factor 0.25 on either side
    hot = rng.randint(0, 32, n_fact // 2)
    cold = rng.randint(0, n_dim + 64, n_fact - n_fact // 2)
    fk = jnp.asarray(np.concatenate([hot, cold]), jnp.int32)
    fact = Table({"fk": fk}).filter(jnp.asarray(rng.rand(n_fact) < 0.9))
    ref = pkfk_join(fact, dim, "fk", "dk", {"p": "payload"})

    # sanity: this configuration really does overflow without the residual
    _, raw_ovf = pkfk_join_kernel(fact, dim, "fk", "dk", {"p": "payload"},
                                  n_partitions=2, capacity_factor=0.25,
                                  mode="ref", residual=False)
    assert int(np.asarray(raw_ovf)) > 0

    got, ovf = pkfk_join_kernel(fact, dim, "fk", "dk", {"p": "payload"},
                                n_partitions=2, capacity_factor=0.25,
                                mode="ref")
    assert int(np.asarray(ovf)) == 0          # repaired, not surfaced
    np.testing.assert_array_equal(np.asarray(got.weights()),
                                  np.asarray(ref.weights()))
    np.testing.assert_allclose(
        np.asarray(got.col("p")) * np.asarray(got.weights()),
        np.asarray(ref.col("p")) * np.asarray(ref.weights()), rtol=1e-6)


def test_plan_name_names_the_executable_not_the_cache_key(data):
    """A named q3 lowers as ``jit_plan_q3``, its ops under per-node name
    scopes, and shares its plan-cache entry with the same plan unnamed."""
    import dataclasses
    tables = data.as_jax()
    ctx = ExecutionContext(executor="cost")
    clear_plan_cache()
    named = LOGICAL_QUERIES["q3"]
    assert named.name == "q3"
    compiled = planner.compile_plan(named, tables, ctx)
    lowered = compiled.lower(tables)
    assert lowered.as_text().startswith("module @jit_plan_q3")
    assert "PTopK_0/PAggregate_1" in lowered.as_text(debug_info=True)
    h0 = plan_cache_info().hits
    bare = planner.compile_plan(dataclasses.replace(named, name=""),
                                tables, ctx)
    assert bare.cache_key == compiled.cache_key
    assert plan_cache_info().hits == h0 + 1
