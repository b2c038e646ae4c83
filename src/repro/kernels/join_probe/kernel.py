"""Pallas TPU kernel: partition-wise join probe.

TPU adaptation of the W3 hash-join probe. The CPU version (Blanas '11)
chases hash buckets per tuple — per-lane random access that the paper speeds
up with allocators and placement. TPUs have no per-lane gather worth using,
so the partition-local probe is recast as a *blocked broadcast compare*:
the build partition's (keys, vals) tile stays resident in VMEM while probe
blocks stream through; an (bp x bb) equality matrix (VPU) followed by a
matmul against build values (MXU) yields matched values — effectively a
tiny nested-loop join per partition, which on the MXU is faster than any
scatter/gather hash probe for build tiles <= ~2K keys. Radix partitioning
(kernels/radix_partition) guarantees that bound.

TPU layout: a block's last two dims must be (8k, 128m) or whole, and a
(1, n) row would be padded to 8 sublanes in HBM. The probe keys and both
outputs are folded into 8 sublane rows, (P, 8, Pk/8) in (1, 8, bp/8)
blocks (the same fold in and out, so every slot keeps its position); the
build keys and values ride as (P, 1, Bk) rows, fetched once per partition.
At the first probe block of a partition the build keys are turned into a
(Bk, 1) column, kept in VMEM scratch, so each row's (Bk, bp/8) equality
matrix is a plain broadcast compare and its match a (1, Bk) @ (Bk, bp/8)
dot.

Grid: (n_partitions, n_probe_blocks).
Working set: 2 x Bk + bp + Bk x bp/8 fp32 — callers bound the build tile
(columnar.MAX_BUILD_TILE = 4096) and bp shrinks so one row's equality
matrix stays <= 4 MB of VMEM.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EQ_TILE_ELEMS = 1 << 20        # bound on one row's (Bk, bp/8) equality matrix


def _probe_kernel(bkeys_ref, bvals_ref, pkeys_ref, vals_ref, found_ref,
                  bcol_scr):
    @pl.when(pl.program_id(1) == 0)
    def _build_column():
        row = bkeys_ref[0]                             # (1, Bk)
        bcol_scr[...] = jnp.broadcast_to(row, (8, row.shape[1])).T[:, :1]

    bk = bcol_scr[...]                                 # (Bk, 1)
    bv = bvals_ref[0].astype(jnp.float32)              # (1, Bk)
    ones = jnp.ones_like(bv)
    for r in range(8):                                 # the 8 sublane rows
        pk = pkeys_ref[0, pl.ds(r, 1), :]              # (1, lanes)
        eqf = (bk == pk).astype(jnp.float32)           # (Bk, lanes)
        # full f32 passes: the payload is a row position, exact only
        # unrounded
        vals_ref[0, pl.ds(r, 1), :] = jax.lax.dot_general(
            bv, eqf, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        # match count per probe slot, also on the MXU (a sublane max-reduce
        # over the build tile crashes the TPU compiler)
        hits = jax.lax.dot_general(ones, eqf, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        found_ref[0, pl.ds(r, 1), :] = (hits > 0).astype(jnp.int32)


def _probe_lanes(rows: int, n_build: int, block_p: int) -> int:
    """Lanes per probe row and grid step: the widest multiple of 128 that
    divides ``rows`` within the block and VMEM bounds, or all of ``rows``
    when they fit (a block spanning the whole dim is always legal)."""
    cap = max(128, min(block_p // 8, EQ_TILE_ELEMS // max(n_build, 1)))
    if rows <= cap:
        return rows
    fits = [n for n in range(128, cap + 1, 128) if rows % n == 0]
    return fits[-1] if fits else rows


def join_probe_pallas(build_keys: jax.Array, build_vals: jax.Array,
                      probe_keys: jax.Array, *, block_p: int = 2048,
                      interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """build_keys/vals: (P, Bk); probe_keys: (P, Pk) with Pk % 8 == 0. On
    the chip Bk is a multiple of 128 and Pk of 1024.

    Returns (vals (P, Pk) f32, found (P, Pk) bool)."""
    P, Bk = build_keys.shape
    _, Pk = probe_keys.shape
    if Pk % 8:
        raise ValueError(f"probe width {Pk} is not a multiple of 8")
    rows = Pk // 8
    lanes = _probe_lanes(rows, Bk, block_p)

    vals, found = pl.pallas_call(
        _probe_kernel,
        grid=(P, rows // lanes),
        in_specs=[
            pl.BlockSpec((1, 1, Bk), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, Bk), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 8, lanes), lambda p, b: (p, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 8, lanes), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 8, lanes), lambda p, b: (p, 0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, 8, rows), jnp.float32),
            jax.ShapeDtypeStruct((P, 8, rows), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((Bk, 1), jnp.int32)],
        interpret=interpret,
        name="join_probe",
    )(build_keys.reshape(P, 1, Bk), build_vals.reshape(P, 1, Bk),
      probe_keys.reshape(P, 8, rows))
    return vals.reshape(P, Pk), found.reshape(P, Pk) > 0
