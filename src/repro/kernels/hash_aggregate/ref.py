"""Oracle for partitioned hash aggregation (distributive: SUM / COUNT).

Inputs are pre-partitioned: ids[p, t] in [0, n_bins) are partition-local
group slots, vals[p(, c), t] the aggregated measures (1.0 for COUNT). A
padding slot with val 0 is the convention for ragged partitions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hash_aggregate_multi_ref(ids: jax.Array, vals: jax.Array, *,
                             n_bins: int) -> jax.Array:
    """ids: (P, T) int32; vals: (P, C, T) f32. Returns (P, C, n_bins) sums.

    One fused pass: segment_sum carries all C measure columns per record, so
    the key stream is read once regardless of how many aggregates ride on it
    (the XLA-lowered shape of the fused Pallas kernel).
    """
    def one(i, v):
        return jax.ops.segment_sum(v.T, i, num_segments=n_bins).T
    return jax.vmap(one)(ids, vals.astype(jnp.float32))


def hash_aggregate_ref(ids: jax.Array, vals: jax.Array, *,
                       n_bins: int) -> jax.Array:
    """ids: (P, T) int32; vals: (P, T) f32. Returns (P, n_bins) f32 sums."""
    return hash_aggregate_multi_ref(ids, vals[:, None], n_bins=n_bins)[:, 0]
