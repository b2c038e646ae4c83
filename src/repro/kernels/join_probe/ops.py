"""Public partition-wise join probe with mode dispatch."""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels.common import kernel_mode
from repro.kernels.join_probe.kernel import join_probe_pallas
from repro.kernels.join_probe.ref import join_probe_ref


def join_probe(build_keys: jax.Array, build_vals: jax.Array,
               probe_keys: jax.Array, *, block_p: int = 2048,
               mode: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """PK-FK partition-local probe -> (matched vals (P,Pk), found (P,Pk))."""
    resolved = kernel_mode(mode)
    if resolved == "pallas":
        return join_probe_pallas(build_keys, build_vals, probe_keys,
                                 block_p=block_p)
    if resolved == "interpret":
        return join_probe_pallas(build_keys, build_vals, probe_keys,
                                 block_p=block_p, interpret=True)
    return join_probe_ref(build_keys, build_vals, probe_keys)
