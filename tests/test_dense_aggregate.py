"""The fused grouped-sum layouts through the column form: the dense and
range-partitioned kernel sweeps (Pallas interpreter) against the XLA
layout and a numpy oracle, the morsel path, COUNT, and the lowered q1
plan's freedom from an (N, C) measure matrix."""
import re

import numpy as np
import jax.numpy as jnp
import pytest

from repro.analytics import planner, tpch
from repro.analytics.aggregate import count_partitioned
from repro.analytics.columnar import (F32_EXACT_COUNT, Table, dense_layout,
                                     stacked_columns, stacked_group_sums)
from repro.analytics.engine import (merge_morsel_partials, morsel_group_sums,
                                    morsel_slices)
from repro.kernels.hash_aggregate.kernel import (_UNROLL, STEP_TILES, TILE,
                                                 step_tiles)

AGGS = {"s0": ("sum", "v0"), "s1": ("sum", "v1"), "s2": ("sum", "v2"),
        "s3": ("sum", "v3"), "c": ("count", "v0")}


def _table(rng, n, n_groups, n_src):
    """Keys in [0, n_groups + 2) (the top two clip into the last group),
    ~30% of rows masked, ``n_src`` measure columns."""
    cols = {"k": jnp.asarray(rng.randint(0, n_groups + 2, n), jnp.int32)}
    for i in range(n_src):
        cols[f"v{i}"] = jnp.asarray(rng.randn(n) * 100, jnp.float32)
    return Table(cols).filter(jnp.asarray(rng.rand(n) < 0.7))


def _oracle(t, key, n_groups, src):
    """float64 (n_groups, C) sums: column 0 the count of unmasked rows."""
    keys = np.minimum(np.asarray(t.col(key)), n_groups - 1)
    w = np.asarray(t.weights(), np.float64)
    vals = [w] + [np.asarray(t.col(c), np.float64) * w for c in src]
    return np.stack([np.bincount(keys, v, minlength=n_groups) for v in vals],
                    axis=1)


def _check(got, want, xla):
    got, xla = np.asarray(got), np.asarray(xla)
    np.testing.assert_array_equal(got[:, 0], want[:, 0])     # exact counts
    np.testing.assert_array_equal(xla[:, 0], want[:, 0])
    scale = np.abs(want).max() + 1.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(got, xla, rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("C", [1, 5])
@pytest.mark.parametrize("n", [1, 1023, 1025, 8 * 1024 + 1, 100_003,
                               8 * STEP_TILES * TILE - 5,
                               8 * (STEP_TILES + 9) * TILE + 7])
def test_dense_sums_match_xla_and_oracle(rng, n, C):
    """Ragged N around the 1024-record tile, the 8-chunk split and the
    grid step (chunks of 13, STEP_TILES and 48 tiles: 42 rounded up to 2
    steps of 24): the folded columns' padding adds nothing, masked rows
    vanish, counts are exact."""
    n_groups = 6
    aggs = dict(list(AGGS.items())[:C - 1] + [("c", ("count", "v0"))])
    t = _table(rng, n, n_groups, max(C - 1, 1))
    keys, cols, src = stacked_columns(t, "k", n_groups, aggs)
    assert len(cols) == C and all(c.shape == (n,) for c in cols)
    got, ovf = stacked_group_sums(keys, cols, n_groups, layout="dense",
                                  mode="interpret")
    xla, _ = stacked_group_sums(keys, cols, n_groups, layout="xla")
    assert int(ovf) == 0 and got.shape == (n_groups, C)
    _check(got, _oracle(t, "k", n_groups, src), xla)


@pytest.mark.parametrize("n", [1025, 30_001, 32_000, 37_888, 40_000,
                               65_000])
def test_partitioned_sums_match_xla_and_oracle(rng, n):
    """Range-partitioned layout: each partition's padded slots fold into
    whole tiles of the kernel's operands (1, 30, 32, 37, 40 and 64 tiles:
    steps of all, all, STEP_TILES, 1, 20 and STEP_TILES tiles)."""
    n_groups = 5000
    t = _table(rng, n, n_groups, 2)
    aggs = {"s0": ("sum", "v0"), "s1": ("sum", "v1")}
    keys, cols, src = stacked_columns(t, "k", n_groups, aggs)
    got, ovf = stacked_group_sums(keys, cols, n_groups, layout="partitioned",
                                  mode="interpret", n_partitions=4,
                                  capacity_factor=4.0)
    xla, _ = stacked_group_sums(keys, cols, n_groups, layout="xla")
    assert int(ovf) == 0
    _check(got, _oracle(t, "k", n_groups, src), xla)


@pytest.mark.parametrize("n", [1, 8 * TILE - 1, 8 * TILE, 8 * 33 * TILE,
                               400_000, 60_000_000,
                               8 * (F32_EXACT_COUNT - STEP_TILES * TILE),
                               8 * (F32_EXACT_COUNT - STEP_TILES * TILE) + 1,
                               600_000_000, 6_000_000_000])
def test_dense_layout_whole_steps_and_exact_counts(n):
    """Every chunk splits into no more default steps than
    ceil(tiles / STEP_TILES), padded by fewer than min(STEP_TILES,
    steps * _UNROLL) tiles (33 tiles pad to 40, not 64), and holds fewer
    than F32_EXACT_COUNT rows, padding included. At SF 10 (60M rows) q1's
    chunks are 7328 tiles (7325 rounded up to 229 steps of 32)."""
    chunks, tiles = dense_layout(n)
    need = -(-n // (chunks * TILE))
    steps = -(-need // STEP_TILES)
    assert 0 <= tiles - need < min(STEP_TILES, steps * _UNROLL)
    assert tiles // step_tiles(tiles) <= steps
    assert tiles * TILE < F32_EXACT_COUNT
    assert chunks == 1 if n < 8 * TILE else chunks >= 8
    if n == 60_000_000:
        assert (chunks, tiles) == (8, 7328)


def test_morsel_dense_partials_merge_to_oracle(rng):
    """Per-morsel dense partials, merged in morsel order, equal the whole
    scan's sums."""
    n, n_groups = 5000, 6
    t = _table(rng, n, n_groups, 4)
    keys, cols, src = stacked_columns(t, "k", n_groups, AGGS)
    parts = [morsel_group_sums(keys[lo:hi], [c[lo:hi] for c in cols],
                               n_groups, layout="dense", mode="interpret")
             for lo, hi in morsel_slices(n, 2048)]
    assert len(parts) == 3
    sums, ovf = merge_morsel_partials(parts)
    xla, _ = stacked_group_sums(keys, cols, n_groups, layout="xla")
    assert int(ovf) == 0
    _check(sums, _oracle(t, "k", n_groups, src), xla)


def test_count_partitioned_column_form(rng):
    """COUNT rides as the one weights column of the partitioned sweep."""
    keys = rng.randint(0, 3000, 20_000).astype(np.int32)
    got, ovf = count_partitioned(jnp.asarray(keys), 3000, n_partitions=4,
                                 capacity_factor=4.0, mode="interpret")
    assert int(ovf) == 0
    np.testing.assert_array_equal(np.asarray(got),
                                  np.bincount(keys, minlength=3000))


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_q1_lowering_holds_no_measure_matrix(mode):
    """q1's dense aggregate lowers from its columns: no (N, C) tensor of
    the lineitem rows appears in the module."""
    tables = tpch.generate(scale=0.001).tables
    n = tables["lineitem"]["l_quantity"].shape[0]
    ctx = planner.ExecutionContext(executor="cost", mode=mode)
    q1 = tpch.LOGICAL_QUERIES["q1"]
    assert [d.choice for d in planner.explain(q1, tables, ctx)
            if d.node == "Aggregate"] == ["dense"]
    plan = planner.compile_plan(q1, tables, ctx)
    text = plan.fn.lower(tables, {}).as_text()
    assert re.search(r"tensor<\d+x8x128xf32>", text)   # the folded columns
    assert not re.search(rf"tensor<{n}x\d+x", text)
