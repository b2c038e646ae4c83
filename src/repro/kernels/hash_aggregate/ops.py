"""Public partitioned-aggregation op with mode dispatch."""
from __future__ import annotations

from typing import Optional, Sequence

import jax

from repro.kernels.common import kernel_mode
from repro.kernels.hash_aggregate.kernel import hash_aggregate_pallas
from repro.kernels.hash_aggregate.ref import hash_aggregate_ref


def hash_aggregate(ids: jax.Array, cols: Sequence[jax.Array], *,
                   n_parts: int, n_bins: int,
                   mode: Optional[str] = None) -> jax.Array:
    """Fused part-local segment sums of C measure columns.

    ids and each of cols: (R, 8, 128), the fold of a 1-D column padded to
    a multiple of 1024 * n_parts records; part p is a contiguous range of
    R / n_parts tiles, swept ``kernel.step_tiles`` of them per grid step.
    Returns (n_parts, C, n_bins). The one-hot/ids stream cost is paid once
    for all C aggregates (see kernel.py)."""
    resolved = kernel_mode(mode)
    if resolved == "ref":
        return hash_aggregate_ref(ids, cols, n_parts=n_parts, n_bins=n_bins)
    return hash_aggregate_pallas(ids, cols, n_parts=n_parts, n_bins=n_bins,
                                 interpret=resolved == "interpret")
