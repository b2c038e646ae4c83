"""Pallas TPU kernel: partitioned hash aggregation (distributive SUM/COUNT).

This is the W2 hot loop (paper Section 2.1) made TPU-native. The CPU
implementation the paper benchmarks is a concurrent cuckoo hash table whose
scalability is gated by allocator arenas and cache-line contention. On TPU
we keep the *partition table resident in VMEM scratch* across the stream of
record blocks (the analogue of a per-thread table in L2 — LOCAL_ALLOC at
tile scale), and the per-record "table update" becomes a one_hot^T @ vals
MXU matmul — contention-free by construction.

Fused multi-aggregate form: TPC-H Q1 needs seven independent SUMs over the
same key column. Instead of seven passes, the kernel computes the
(n_bins, block) one-hot ONCE per record block and contracts it against the
(C, block) values tile in a single MXU dot — the ids stream and the one-hot
build are amortized across every aggregate, so the sweep is one read of
each measure column and one read of the key column, total.

TPU layout: a block's last two dims must be (8k, 128m) or whole, and a
(1, n) row would be padded to 8 sublanes in HBM. So the record axis is
folded into 8 sublane rows: ids ride as (P, 8, T/8) in (1, 8, block/8)
blocks, values as (P, C, 8, T/8) in (1, C, 8, block/8) blocks (records on
lanes, measures on the major axis), and the table is kept as (C, n_bins) —
a (n_bins, C) table would pad C to 128 lanes. Values therefore arrive
measure-major, (P, C, T): a record-major (P, T, C) operand would need a
minor-dim transpose of the whole stream, which the TPU compiler takes
minutes over at 10^8 records. The fold is a bijection on
record positions, and a sum does not care which row a record sits in. On
the chip ``block`` is a multiple of 1024 (128 lanes x 8 rows) unless it
spans all of T.

Grid: (n_partitions, n_blocks); blocks innermost so the scratch table for a
partition accumulates across its stream, then emits once.
Working set: (n_bins x block/8) one-hot fp32 per row + (C, n_bins) table —
with block=1024, bins=4096: ~2 MB VMEM. Callers bound n_bins
(columnar.MAX_PARTITION_BINS) so the one-hot fits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_multi_kernel(ids_ref, vals_ref, out_ref, table_scr, *, n_bins: int,
                      lanes: int, n_blocks: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        table_scr[...] = jnp.zeros(table_scr.shape, table_scr.dtype)

    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, lanes), 0)
    contrib = jnp.zeros(table_scr.shape, jnp.float32)
    for r in range(8):                                  # the 8 sublane rows
        ids = ids_ref[0, pl.ds(r, 1), :]                # (1, lanes)
        vals = vals_ref[0, :, r, :].astype(jnp.float32)  # (C, lanes)
        oh = (ids == bins).astype(jnp.float32)          # (n_bins, lanes)
        # full f32 passes: a one-pass bf16 product would round the measures
        contrib += jax.lax.dot_general(vals, oh, (((1,), (1,)), ((), ())),
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)
    table_scr[...] = table_scr[...] + contrib           # (C, n_bins)

    @pl.when(bi == n_blocks - 1)
    def _emit():
        out_ref[0] = table_scr[...]


def hash_aggregate_multi_pallas(ids: jax.Array, vals: jax.Array, *,
                                n_bins: int, block: int = 1024,
                                interpret: bool = False) -> jax.Array:
    """ids: (P, T); vals: (P, C, T) with T % block == 0, block % 8 == 0
    and n_bins % 128 == 0.

    Returns (P, C, n_bins) f32: per-partition tables of C fused sums."""
    P, T = ids.shape
    C = vals.shape[1]
    if vals.shape != (P, C, T):
        raise ValueError(f"vals {vals.shape} does not match ids {ids.shape}")
    if T % block or block % 8:
        raise ValueError(f"T={T} not divisible by block={block}, or block "
                         f"not a multiple of 8")
    n_blocks, lanes = T // block, block // 8
    kernel = functools.partial(_agg_multi_kernel, n_bins=n_bins, lanes=lanes,
                               n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid=(P, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 8, lanes), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, C, 8, lanes), lambda p, b: (p, 0, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, C, n_bins), lambda p, b: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, C, n_bins), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, n_bins), jnp.float32)],
        interpret=interpret,
    )(ids.reshape(P, 8, T // 8), vals.reshape(P, C, 8, T // 8))


def hash_aggregate_pallas(ids: jax.Array, vals: jax.Array, *, n_bins: int,
                          block: int = 1024,
                          interpret: bool = False) -> jax.Array:
    """Single-aggregate entrypoint: thin wrapper over the fused kernel.

    ids, vals: (P, T) with T % block == 0. Returns (P, n_bins) f32."""
    out = hash_aggregate_multi_pallas(ids, vals[:, None], n_bins=n_bins,
                                      block=block, interpret=interpret)
    return out[:, 0]
