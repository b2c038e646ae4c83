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
(n_bins, 128) one-hot ONCE per row of records and contracts it against the
(C, 128) values of that row in a single MXU dot — the ids stream and the
one-hot build are amortized across every aggregate, so the sweep is one
read of each measure column and one read of the key column, total.

Operand form: the ids and each of the C measures are separate (R, 8, 128)
operands, each a bitcast of a 1-D column padded to a multiple of 1024
records. A 1-D f32/int32 column is laid out on the TPU in 1024-element
tiles, each exactly one (8, 128) tile, so the fold costs no relayout: the
only staging a caller pays is the padding, at most one pass per operand.
Records sit on 8 sublanes x 128 lanes; a sum does not care which row a
record sits in. The table is kept as (C, n_bins) — a (n_bins, C) table
would pad C to 128 lanes.

Grid: (n_parts, steps); each part is a contiguous range of R / n_parts
tiles, ``tiles`` of them per step, so the scratch table for a part
accumulates across its stream, then emits once. A step sweeps its tiles
in a loop of up to _UNROLL tiles an iteration, fewer above _UNROLL_BINS
bins (compile time does not grow with ``tiles``) and adds its (C, n_bins) contribution to the table once, so
the per-step cost (pipeline bookkeeping, DMA start and wait) is paid per
STEP_TILES tiles, not per 4 KB tile. ``tiles`` comes from the operand
shape (``step_tiles``). Working set: C + 1 double-buffered (tiles, 8, 128)
blocks (1.5 MB at C = 5, 32 tiles), the (n_bins x 128) one-hot per row
and the (C, n_bins) table — with bins=4096 the one-hot is 2 MB VMEM.
Callers bound n_bins (columnar.MAX_PARTITION_BINS) so the one-hot fits.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Records in one (8, 128) tile: the unit of the operand fold.
TILE = 1024
# Most tiles one grid step sweeps: C + 1 double-buffered (STEP_TILES, 8,
# 128) blocks stay far inside the default scoped VMEM (1.5 MB at C = 5).
STEP_TILES = 32
# Tiles per iteration of a step's loop (the 8 rows of a tile are unrolled):
# the scheduler overlaps one tile's one-hot with another's dot. The live
# one-hots grow with tiles x bins: 8 tiles overflow scoped VMEM at 2048
# bins, and so do 4 at 4096, so above _UNROLL_BINS the group shrinks in
# proportion.
_UNROLL = 4
_UNROLL_BINS = 2048


def padded_tiles(part_tiles: int) -> int:
    """``part_tiles`` rounded up to steps = ceil(part_tiles / STEP_TILES)
    equal grid steps, each a multiple of _UNROLL tiles when there are
    several: fewer than min(STEP_TILES, steps * _UNROLL) tiles are added."""
    steps = -(-part_tiles // STEP_TILES)
    if steps == 1:
        return part_tiles
    step = -(-part_tiles // steps)
    return steps * -(-step // _UNROLL) * _UNROLL


def step_tiles(part_tiles: int) -> int:
    """Tiles per grid step for parts of ``part_tiles`` tiles: the largest
    divisor of ``part_tiles`` that is at most STEP_TILES."""
    return max(d for d in range(1, min(STEP_TILES, part_tiles) + 1)
               if part_tiles % d == 0)


def _agg_kernel(ids_ref, *refs, n_bins: int, tiles: int, steps: int):
    *col_refs, out_ref, table_scr = refs
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        table_scr[...] = jnp.zeros(table_scr.shape, table_scr.dtype)

    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, 128), 0)

    unroll = math.gcd(tiles, max(1, min(_UNROLL,
                                        _UNROLL * _UNROLL_BINS // n_bins)))

    def sweep(i, contrib):
        for u in range(unroll):
            t = i * unroll + u
            for r in range(8):                          # the 8 sublane rows
                ids = ids_ref[t, pl.ds(r, 1), :]        # (1, 128)
                vals = jnp.concatenate(
                    [c[t, pl.ds(r, 1), :].astype(jnp.float32)
                     for c in col_refs], axis=0)        # (C, 128)
                oh = (ids == bins).astype(jnp.float32)  # (n_bins, 128)
                # full f32 passes: a one-pass bf16 product would round the
                # measures
                contrib += jax.lax.dot_general(
                    vals, oh, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        return contrib

    contrib = jax.lax.fori_loop(0, tiles // unroll, sweep,
                                jnp.zeros(table_scr.shape, jnp.float32))
    table_scr[...] = table_scr[...] + contrib           # (C, n_bins)

    @pl.when(si == steps - 1)
    def _emit():
        out_ref[0] = table_scr[...]


def hash_aggregate_pallas(ids: jax.Array, cols: Sequence[jax.Array], *,
                          n_parts: int, n_bins: int,
                          tiles: Optional[int] = None,
                          interpret: bool = False) -> jax.Array:
    """ids and each of cols: (R, 8, 128), R % (n_parts * tiles) == 0,
    n_bins % 128 == 0. ``tiles`` per grid step defaults to
    ``step_tiles(R // n_parts)``.

    Returns (n_parts, C, n_bins) f32: part p sums tiles
    [p * R / n_parts, (p + 1) * R / n_parts) of each column."""
    R = ids.shape[0]
    C = len(cols)
    if ids.shape[1:] != (8, 128) or any(c.shape != ids.shape for c in cols):
        raise ValueError(f"operands {ids.shape}, "
                         f"{[c.shape for c in cols]} are not one (R, 8, 128)")
    if R % n_parts or (R // n_parts) % (tiles or 1):
        raise ValueError(f"R={R} tiles not divisible into {n_parts} parts "
                         f"of {tiles}-tile steps")
    tiles = tiles or step_tiles(R // n_parts)
    steps = R // (n_parts * tiles)
    kernel = functools.partial(_agg_kernel, n_bins=n_bins, tiles=tiles,
                               steps=steps)
    spec = pl.BlockSpec((tiles, 8, 128), lambda p, s: (p * steps + s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(n_parts, steps),
        in_specs=[spec] * (C + 1),
        out_specs=pl.BlockSpec((1, C, n_bins), lambda p, s: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_parts, C, n_bins), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, n_bins), jnp.float32)],
        interpret=interpret,
        name="hash_aggregate",
    )(ids, *cols)
