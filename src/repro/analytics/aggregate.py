"""W1 (holistic MEDIAN) and W2 (distributive COUNT) aggregation operators.

Two implementations per operator:
  *_direct       XLA-native (segment ops / sort) — oracle + small inputs.
  *_partitioned  the TPU-optimized pipeline: radix partition (Pallas
                 histogram) -> dense partition layout -> partition-local
                 kernel (hash_aggregate) or sort. This mirrors the paper's
                 state-of-the-art CPU pipeline (partition -> per-thread
                 table) with VMEM playing the role of the per-thread cache.

Holistic aggregation cannot be computed from partials (paper Section 2) —
median requires all of a group's values co-located; the sort-based
formulation is the TPU-idiomatic equivalent of the paper's per-group
vectors (documented adaptation, DESIGN.md Section 8).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analytics.columnar import stacked_group_sums


# ---------------------------------------------------------------------------
# W2: distributive COUNT
# ---------------------------------------------------------------------------
def count_direct(keys: jax.Array, cardinality: int) -> jax.Array:
    """SELECT groupkey, COUNT(*) GROUP BY groupkey — XLA segment sum."""
    return jax.ops.segment_sum(jnp.ones_like(keys, jnp.float32), keys,
                               num_segments=cardinality)


@functools.partial(jax.jit, static_argnames=("cardinality", "n_partitions",
                                             "capacity_factor", "mode"))
def count_partitioned(keys: jax.Array, cardinality: int, *,
                      n_partitions: int = 64, capacity_factor: float = 2.0,
                      mode: Optional[str] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Partitioned COUNT via range partitioning + the hash_aggregate kernel.

    Range partitioning on dense group ids makes the partition-local slot
    (key % range) collision-free — the kernel result is EXACT whenever no
    partition overflows its capacity (overflow is returned, never dropped
    silently). Returns (counts (cardinality,), overflow).

    Thin wrapper: a COUNT is a fused sweep over a single all-ones weights
    column, so this delegates to the shared range-partitioned recipe in
    ``columnar.stacked_group_sums`` (COUNT always rides in measure column
    0 — padded slots carry zero weight, so no dead-bin correction is
    needed)."""
    clipped = jnp.clip(keys, 0, cardinality - 1).astype(jnp.int32)
    ones = jnp.ones(keys.shape, jnp.float32)
    sums, overflow = stacked_group_sums(
        clipped, [ones], cardinality, layout="partitioned", mode=mode,
        n_partitions=n_partitions, capacity_factor=capacity_factor)
    return sums[:, 0], overflow


# ---------------------------------------------------------------------------
# W1: holistic MEDIAN
# ---------------------------------------------------------------------------
def median_direct(keys: jax.Array, vals: jax.Array,
                  cardinality: int) -> jax.Array:
    """SELECT groupkey, MEDIAN(val) GROUP BY groupkey.

    Sort by (key, val) — stable two-pass sort — then pick the middle
    element(s) of each group run. Empty groups return NaN."""
    order_v = jnp.argsort(vals, stable=True)
    k1, v1 = keys[order_v], vals[order_v]
    order_k = jnp.argsort(k1, stable=True)
    sk, sv = k1[order_k], v1[order_k]
    counts = jax.ops.segment_sum(jnp.ones_like(keys, jnp.float32), keys,
                                 num_segments=cardinality)
    starts = jnp.cumsum(counts) - counts
    c = counts.astype(jnp.int32)
    s = starts.astype(jnp.int32)
    lo = s + jnp.maximum((c - 1) // 2, 0)
    hi = s + jnp.maximum(c // 2, 0)
    lo = jnp.clip(lo, 0, sv.shape[0] - 1)
    hi = jnp.clip(hi, 0, sv.shape[0] - 1)
    med = (sv[lo] + sv[hi]) * 0.5
    return jnp.where(c > 0, med, jnp.nan)


@functools.partial(jax.jit, static_argnames=("cardinality",))
def median_jit(keys: jax.Array, vals: jax.Array, cardinality: int) -> jax.Array:
    return median_direct(keys, vals, cardinality)
