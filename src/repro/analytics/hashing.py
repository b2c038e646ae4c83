"""Hashing + partition-layout utilities shared by the analytics operators.

``multiply_shift`` is the classic universal hash (Dietzfelbinger); on TPU it
is one vector multiply + shift — the same choice state-of-the-art CPU joins
use, so FLOP parity with the paper's codebase is preserved.

``pad_partitions`` converts the (contiguous-but-ragged) output of
radix_partition into the dense (P, padT) layout the Pallas kernels consume.
Capacity follows a capacity-factor convention (like the MoE dispatch);
overflow is counted and surfaced, never silently dropped.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_KNUTH = jnp.uint32(2654435761)


def multiply_shift(keys: jax.Array, bits: int = 32) -> jax.Array:
    """32-bit multiplicative hash; returns uint32 with high bits well-mixed."""
    h = keys.astype(jnp.uint32) * _KNUTH
    if bits < 32:
        h = jax.lax.shift_right_logical(h, jnp.uint32(32 - bits))
    return h


def partition_of(keys: jax.Array, n_partitions: int) -> jax.Array:
    """Partition id from the TOP radix bits of the hash (uniform split)."""
    bits = max(1, int(n_partitions - 1).bit_length())
    h = multiply_shift(keys, 32)
    return (jax.lax.shift_right_logical(h, jnp.uint32(32 - bits))
            .astype(jnp.int32) % n_partitions)


def pad_partitions(sorted_keys: jax.Array, sorted_vals: jax.Array,
                   starts: jax.Array, counts: jax.Array, n_partitions: int,
                   pad_t: int, *, pad_key: int = -1
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense (P, padT) layout from partition-contiguous arrays.

    ``sorted_vals`` is one (N,) payload column or a list of them (the
    measure columns of a multi-aggregate ride through the same gather as
    their keys). Returns (keys (P, padT), vals (P, padT) or a list of them,
    overflow: total records beyond capacity). Padded slots carry
    ``pad_key`` and zero values."""
    idx = starts[:, None] + jnp.arange(pad_t)[None, :]          # (P, padT)
    valid = jnp.arange(pad_t)[None, :] < jnp.minimum(counts, pad_t)[:, None]
    idx = jnp.clip(idx, 0, sorted_keys.shape[0] - 1)
    keys = jnp.where(valid, sorted_keys[idx], pad_key)
    vals = jax.tree.map(lambda v: jnp.where(valid, v[idx], 0), sorted_vals)
    overflow = jnp.maximum(counts - pad_t, 0).sum()
    return keys, vals, overflow
