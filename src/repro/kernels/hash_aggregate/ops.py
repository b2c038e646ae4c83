"""Public partitioned-aggregation ops with mode dispatch."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.common import kernel_mode
from repro.kernels.hash_aggregate.kernel import hash_aggregate_multi_pallas
from repro.kernels.hash_aggregate.ref import hash_aggregate_multi_ref


def hash_aggregate_multi(ids: jax.Array, vals: jax.Array, *, n_bins: int,
                         block: int = 1024,
                         mode: Optional[str] = None) -> jax.Array:
    """Fused partition-local segment sums over C stacked measure columns.

    ids: (P, T); vals: (P, C, T) -> (P, C, n_bins). The one-hot/ids stream
    cost is paid once for all C aggregates (see kernel.py)."""
    resolved = kernel_mode(mode)
    if resolved == "pallas":
        return hash_aggregate_multi_pallas(ids, vals, n_bins=n_bins,
                                           block=block)
    if resolved == "interpret":
        return hash_aggregate_multi_pallas(ids, vals, n_bins=n_bins,
                                           block=block, interpret=True)
    return hash_aggregate_multi_ref(ids, vals, n_bins=n_bins)


def hash_aggregate(ids: jax.Array, vals: jax.Array, *, n_bins: int,
                   block: int = 1024, mode: Optional[str] = None) -> jax.Array:
    """Partition-local segment sums. ids, vals: (P, T) -> (P, n_bins).

    Thin single-aggregate wrapper over :func:`hash_aggregate_multi`."""
    return hash_aggregate_multi(ids, vals[:, None], n_bins=n_bins,
                                block=block, mode=mode)[:, 0]
