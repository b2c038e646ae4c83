"""Oracle for partitioned hash aggregation (distributive: SUM / COUNT).

Inputs are pre-partitioned: ids are part-local group slots in [0, n_bins),
cols the aggregated measures (the selection weights for COUNT), all in the
kernel's (R, 8, 128) fold with part p holding a contiguous range of
R / n_parts tiles. A padding slot with val 0 is the convention for ragged
parts.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def hash_aggregate_ref(ids: jax.Array, cols: Sequence[jax.Array], *,
                       n_parts: int, n_bins: int) -> jax.Array:
    """Returns (n_parts, C, n_bins) f32 sums.

    One fused pass: segment_sum carries all C measure columns per record,
    so the key stream is read once regardless of how many aggregates ride
    on it (the XLA-lowered shape of the fused Pallas kernel)."""
    vals = jnp.stack([c.astype(jnp.float32).reshape(n_parts, -1)
                      for c in cols], axis=-1)          # (P, T, C)

    def one(i, v):
        return jax.ops.segment_sum(v, i, num_segments=n_bins).T
    return jax.vmap(one)(ids.reshape(n_parts, -1), vals)
