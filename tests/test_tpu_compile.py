"""Compile the analytics kernels and the q1 kernel plan for a TPU v5e chip.

Nothing runs: each program is compiled by the TPU compiler against a
described (not attached) v5e, at the shapes TPC-H SF 10 gives the
planner. This catches what interpret mode cannot — block shapes the TPU
refuses, and more VMEM than a kernel may use — before a chip run pays for
it. The topology is described inside a fixture: only one process at a time
may load the TPU library, so nothing here touches it while tests are
collected.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analytics import planner, tpch
from repro.analytics.columnar import (DENSE_GROUP_LIMIT, dense_layout,
                                     join_layout, partition_layout)
from repro.kernels.hash_aggregate.kernel import (STEP_TILES, TILE,
                                                 hash_aggregate_pallas,
                                                 step_tiles)
from repro.kernels.join_probe.kernel import join_probe_pallas
from repro.kernels.radix_partition.kernel import block_histograms_pallas

SF10_ROWS = {"lineitem": 60_000_000, "orders": 15_000_000,
             "customer": 1_500_000, "supplier": 100_000, "nation": 25}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sf10_tables(sharding):
    schema = tpch.generate(scale=0.001).tables
    return {t: {c: _shape(sharding, (SF10_ROWS[t],), a.dtype)
                for c, a in cols.items()} for t, cols in schema.items()}


def _entry_ops(hlo):
    """(name, opcode, largest result element count) of each instruction
    of the ENTRY computation."""
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ops = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(",
                         entry, re.M):
        dims = [math.prod(int(d) for d in shape.split(",") if d)
                for shape in re.findall(r"\[([\d,]*)\]", m.group(2))]
        ops.append((m.group(1), m.group(3), max(dims, default=1)))
    return ops


def _folded(sharding, rows, dtype):
    """A 1-D column of ``rows`` records in the kernel's (R, 8, 128) fold."""
    return _shape(sharding, (rows // TILE, 8, 128), dtype)


def test_q1_dense_aggregate_compiles(one_chip):
    """Q1's fused sweep: 8 positional chunks of SF 10 lineitem, each
    7328 tiles (7325 rounded up to whole STEP_TILES-tile grid steps), the
    weights and 4 weighted measures as separate folded columns, 6 groups
    in a 128-bin table."""
    (P, tiles), C = dense_layout(SF10_ROWS["lineitem"]), 5
    assert (P, tiles) == (8, 7328) and step_tiles(tiles) == STEP_TILES
    rows = P * tiles * TILE
    fn = functools.partial(hash_aggregate_pallas, n_parts=P, n_bins=128)
    hlo = _compiled_text(
        lambda ids, *cols: fn(ids, cols), _folded(one_chip, rows, jnp.int32),
        *[_folded(one_chip, rows, jnp.float32)] * C)
    assert "tpu_custom_call" in hlo


def test_widest_dense_aggregate_compiles(one_chip):
    """The widest table the dense layout takes (DENSE_GROUP_LIMIT bins)
    at q1's chunks and default steps: the loop's live one-hots grow with
    the bins, so this is where a step's VMEM runs out first."""
    (P, tiles), C = dense_layout(SF10_ROWS["lineitem"]), 5
    fn = functools.partial(hash_aggregate_pallas, n_parts=P,
                           n_bins=DENSE_GROUP_LIMIT)
    hlo = _compiled_text(
        lambda ids, *cols: fn(ids, cols),
        _folded(one_chip, P * tiles * TILE, jnp.int32),
        *[_folded(one_chip, P * tiles * TILE, jnp.float32)] * C)
    assert "tpu_custom_call" in hlo


def test_q18_partitioned_aggregate_compiles(one_chip):
    """Q18's per-order sums: 15M groups over 60M rows, range-partitioned
    so each partition table stays within MAX_PARTITION_BINS; a grid step
    sweeps a partition's 16 tiles."""
    P, _, bins, pad_t = partition_layout(SF10_ROWS["lineitem"],
                                         SF10_ROWS["orders"], 64, 2.0, TILE)
    assert step_tiles(pad_t // TILE) == pad_t // TILE == 16
    fn = functools.partial(hash_aggregate_pallas, n_parts=P, n_bins=bins)
    hlo = _compiled_text(
        lambda ids, *cols: fn(ids, cols),
        _folded(one_chip, P * pad_t, jnp.int32),
        *[_folded(one_chip, P * pad_t, jnp.float32)] * 2)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("P,build,probe", [
    (64, 4096, 8192),                                  # the widest tile
    join_layout(SF10_ROWS["orders"], SF10_ROWS["customer"], 64, 2.0),
])
def test_join_probe_compiles(one_chip, P, build, probe):
    hlo = _compiled_text(join_probe_pallas, _shape(one_chip, (P, build)),
                         _shape(one_chip, (P, build), jnp.float32),
                         _shape(one_chip, (P, probe)))
    assert "tpu_custom_call" in hlo


def test_radix_histogram_compiles(one_chip):
    fn = functools.partial(block_histograms_pallas, n_bins=256, shift=0,
                           block=1024)
    hlo = _compiled_text(fn, _shape(one_chip, (60_000_256,)))
    assert "tpu_custom_call" in hlo


def test_q1_kernel_plan_compiles(one_chip):
    """The whole q1 plan under the kernel executor, forced to Pallas: the
    planner lowers it, and the TPU compiler accepts the program."""
    tables = _sf10_tables(one_chip)
    ctx = planner.ExecutionContext(executor="kernel", mode="pallas")
    plan = planner.compile_plan(tpch.LOGICAL_QUERIES["q1"], tables, ctx)
    hlo = plan.fn.lower(tables, {}).compile().as_text()
    assert "tpu_custom_call" in hlo
    # named in a profile: the plan's module and the kernel's instruction
    assert hlo.startswith("HloModule jit_plan_q1")
    assert re.search(r"%hash_aggregate[.\d]* = .*tpu_custom_call", hlo)


def test_q1_cost_plan_stages_no_relayout(one_chip):
    """q1 as the SF 10 scan cell runs it (cost executor, Pallas kernel):
    every operand of the dense aggregate is a bitcast of its padded
    column, so the ENTRY computation holds no relayout loop, no
    concatenation and no copy or transpose of a whole column."""
    tables = _sf10_tables(one_chip)
    ctx = planner.ExecutionContext(executor="cost", mode="pallas")
    plan = planner.compile_plan(tpch.LOGICAL_QUERIES["q1"], tables, ctx)
    hlo = plan.fn.lower(tables, {}).compile().as_text()
    ops = _entry_ops(hlo)
    assert any(name.startswith("hash_aggregate") for name, _, _ in ops)
    assert not [o for o in ops if o[1] in ("while", "concatenate")]
    big = SF10_ROWS["lineitem"]
    assert not [o for o in ops if o[2] >= big and (
        o[1] in ("copy", "transpose")
        or o[0].startswith(("copy_bitcast_fusion", "transpose")))]
