"""Tuned (kernel-backed) TPC-H executor vs the default XLA plan.

Both executor paths must produce the same results on every query — the
Fig 8/9 default-vs-tuned benchmark is only meaningful if the two plans are
semantically identical. Also covers the cached pkfk_join build index and
the plan cache keying.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.analytics.columnar import Table, group_aggregate, pkfk_join
from repro.analytics.tpch import (DATE1, QUERIES, clear_plan_cache, generate,
                                  plan_cache_size, run_query)


@pytest.fixture(scope="module")
def data():
    return generate(scale=0.004, seed=1)


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_executor_parity(data, name):
    ref = run_query(name, data, executor="xla")
    got = run_query(name, data, executor="kernel")
    assert set(got) == set(ref)
    for k in ref:
        if k == "_overflow":
            continue
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-3, rtol=1e-4,
                                   err_msg=f"{name}/{k}")
    if "_overflow" in got:
        assert int(np.asarray(got["_overflow"])) == 0


def test_group_aggregate_kernel_matches_xla_all_ops(rng):
    """Every agg op, masked rows, both kernel regimes (dense/partitioned)."""
    for n_groups in (37, 6000):   # below / above DENSE_GROUP_LIMIT
        n = 10_000
        t = Table({
            "k": jnp.asarray(rng.randint(0, n_groups, n), jnp.int32),
            "v": jnp.asarray(rng.randn(n) * 100, jnp.float32),
            "u": jnp.asarray(rng.rand(n), jnp.float32),
        }).filter(jnp.asarray(rng.rand(n) < 0.7))
        aggs = {"s": ("sum", "v"), "a": ("avg", "v"), "c": ("count", "v"),
                "s2": ("sum", "u"), "mx": ("max", "v"), "mn": ("min", "v")}
        ref = group_aggregate(t, "k", n_groups, aggs, executor="xla")
        got = group_aggregate(t, "k", n_groups, aggs, executor="kernel")
        assert int(np.asarray(got["_overflow"])) == 0
        for k in ref:
            if k == "_overflow":
                continue
            np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                       atol=1e-3, rtol=1e-4,
                                       err_msg=f"G={n_groups}/{k}")


@pytest.mark.parametrize("executor", ["xla", "kernel"])
def test_group_aggregate_past_f32_exact_count(executor):
    """A group of more than 2^24 rows: an f32 scatter-add stops counting
    at 2^24, so the count must come back as the exact count rounded once
    to f32, the sum accurate, and the median at the right position."""
    n = (1 << 24) + 3                      # odd: not an f32 integer
    v = np.arange(n, dtype=np.int32) % 7
    t = Table({"k": jnp.zeros(n, jnp.int32),
               "v": jnp.asarray(v, jnp.float32)})
    got = group_aggregate(t, "k", 2, {"c": ("count", "v"), "s": ("sum", "v"),
                                      "m": ("median", "v")},
                          executor=executor, mode="ref")
    assert np.asarray(got["c"])[0] == np.float32(n)
    assert np.asarray(got["_count"])[1] == 0
    np.testing.assert_allclose(np.asarray(got["s"])[0], v.sum(), rtol=1e-6)
    assert np.asarray(got["m"])[0] == np.median(v)


def test_group_aggregate_kernel_counts_overflow(rng):
    """Skewed keys beyond partition capacity are counted, never dropped."""
    n, n_groups = 20_000, 6000
    keys = jnp.zeros(n, jnp.int32)        # all rows hit partition 0
    t = Table({"k": keys, "v": jnp.ones(n, jnp.float32)})
    got = group_aggregate(t, "k", n_groups, {"s": ("sum", "v")},
                          executor="kernel", capacity_factor=1.0)
    assert int(np.asarray(got["_overflow"])) > 0


def test_pkfk_join_cached_index_matches_uncached(rng):
    n_dim, n_fact = 500, 4000
    dk = jnp.asarray(rng.permutation(n_dim), jnp.int32)
    dim = Table({"dk": dk, "payload": jnp.asarray(rng.randn(n_dim),
                                                 jnp.float32)})
    # fact keys include misses (>= n_dim) which must zero the mask
    fk = jnp.asarray(rng.randint(0, n_dim + 100, n_fact), jnp.int32)
    fact = Table({"fk": fk})

    cold = pkfk_join(fact, dim, "fk", "dk", {"p": "payload"})
    assert "dk" in dim.index_cache            # build index was cached
    warm = pkfk_join(fact, dim, "fk", "dk", {"p": "payload"})
    np.testing.assert_array_equal(np.asarray(cold.col("p")),
                                  np.asarray(warm.col("p")))
    np.testing.assert_array_equal(np.asarray(cold.weights()),
                                  np.asarray(warm.weights()))
    # oracle: dense lookup
    lut = np.zeros(n_dim + 100, np.float32)
    lut[np.asarray(dk)] = np.asarray(dim.col("payload"))
    hit = np.asarray(fk) < n_dim
    np.testing.assert_allclose(np.asarray(cold.col("p")) * hit,
                               lut[np.asarray(fk)] * hit, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cold.weights()),
                                  hit.astype(np.float32))


def test_index_cache_propagation(rng):
    n = 256
    t = Table({"a": jnp.asarray(rng.permutation(n), jnp.int32),
               "b": jnp.asarray(rng.randn(n), jnp.float32)})
    t.key_index("a")
    # filter keeps column identity -> shares the cache
    assert "a" in t.filter(t.col("b") > 0).index_cache
    # adding an unrelated column keeps the entry; overwriting drops it
    assert "a" in t.with_columns(c=t.col("b")).index_cache
    assert "a" not in t.with_columns(a=t.col("a") + 1).index_cache


def test_plan_cache_keying(data):
    clear_plan_cache()
    run_query("q1", data, executor="xla")
    n1 = plan_cache_size()
    assert n1 == 1
    run_query("q1", data, executor="xla")        # same key -> no new plan
    assert plan_cache_size() == n1
    run_query("q1", data, executor="kernel")     # executor is part of the key
    assert plan_cache_size() == n1 + 1
    other = generate(scale=0.006, seed=3)        # new shapes -> new plan
    run_query("q1", other, executor="xla")
    assert plan_cache_size() == n1 + 2
    # same shapes, different values -> cached plan, fresh (correct) results:
    # the seed behavior baked tables in as constants, which this catches
    twin = generate(scale=0.004, seed=9)
    before = plan_cache_size()
    out = run_query("q1", twin, executor="xla")
    assert plan_cache_size() == before
    li = twin.tables["lineitem"]
    expect = li["l_quantity"][li["l_shipdate"] <= DATE1 - 90].sum()
    np.testing.assert_allclose(float(np.asarray(out["sum_qty"]).sum()),
                               expect, rtol=1e-5)
