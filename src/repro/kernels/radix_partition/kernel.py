"""Pallas TPU kernel: per-block radix histograms.

TPU adaptation of the CPU/GPU radix counting loop: instead of per-lane
scatter-increment into a shared histogram (bank-conflict territory on GPUs,
cache-line ping-pong on NUMA CPUs — the exact contention the paper's
allocator/placement work fights), each block computes
    one_hot(digits) summed over the block via an MXU matmul-shaped reduce,
so the "histogram update" becomes a dense (block x n_bins) reduction with no
scatter at all. Each grid step owns its output row — zero write contention,
the embodiment of the paper's LOCAL_ALLOC-then-merge recipe at tile scale.

TPU layout: a block's last two dims must be (8k, 128m) or whole, and a
(1, n) row would be padded to 8 sublanes in HBM. So each block of keys is
folded into 8 sublane rows, (n_blocks, 8, block/8), whose block is the
whole of its last two dims; each block's histogram is a (1, 1, n_bins) row.
The one-hot is built transposed, (n_bins, block/8) per row, and reduced
over lanes by an MXU dot.

Grid: (n_blocks,). Working set: (8, block/8) keys + (n_bins, block/8)
one-hot in fp32 — block=1024, bins=256 -> ~128 KB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(keys_ref, out_ref, *, n_bins: int, shift: int, lanes: int):
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, lanes), 0)
    ones = jnp.ones((1, lanes), jnp.float32)
    counts = jnp.zeros((1, n_bins), jnp.float32)
    for r in range(8):                                # the 8 sublane rows
        k = keys_ref[0, pl.ds(r, 1), :]               # (1, lanes) int32
        digits = jax.lax.shift_right_logical(k, shift) & (n_bins - 1)
        oh = (digits == bins).astype(jnp.float32)     # (n_bins, lanes)
        counts += jax.lax.dot_general(ones, oh, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    out_ref[0] = counts.astype(jnp.int32)             # (1, n_bins)


def block_histograms_pallas(keys: jax.Array, *, n_bins: int, shift: int,
                            block: int, interpret: bool = False) -> jax.Array:
    """keys: (N,) with N % block == 0 and block % 8 == 0. Returns
    (N // block, n_bins) int32."""
    N = keys.shape[0]
    if N % block or block % 8:
        raise ValueError(f"N={N} not divisible by block={block}, or block "
                         f"not a multiple of 8")
    n_blocks, lanes = N // block, block // 8
    kernel = functools.partial(_hist_kernel, n_bins=n_bins, shift=shift,
                               lanes=lanes)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((1, 8, lanes), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, n_bins), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, n_bins), jnp.int32),
        interpret=interpret,
    )(keys.reshape(n_blocks, 8, lanes))
    return out.reshape(n_blocks, n_bins)
