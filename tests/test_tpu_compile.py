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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analytics import planner, tpch
from repro.analytics.columnar import join_layout, partition_layout
from repro.kernels.hash_aggregate.kernel import hash_aggregate_multi_pallas
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


def test_q1_dense_aggregate_compiles(one_chip):
    """Q1's fused sweep: 8 positional chunks of SF 10 lineitem, 7 stacked
    measures, 6 groups in a 128-bin table."""
    P, T, C = 8, 7_500_800, 7
    fn = functools.partial(hash_aggregate_multi_pallas, n_bins=128,
                           block=1024)
    hlo = _compiled_text(fn, _shape(one_chip, (P, T)),
                         _shape(one_chip, (P, C, T), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_q18_partitioned_aggregate_compiles(one_chip):
    """Q18's per-order sums: 15M groups over 60M rows, range-partitioned
    so each partition table stays within MAX_PARTITION_BINS."""
    P, _, bins, pad_t = partition_layout(SF10_ROWS["lineitem"],
                                         SF10_ROWS["orders"], 64, 2.0, 1024)
    fn = functools.partial(hash_aggregate_multi_pallas, n_bins=bins,
                           block=1024)
    hlo = _compiled_text(fn, _shape(one_chip, (P, pad_t)),
                         _shape(one_chip, (P, 2, pad_t), jnp.float32))
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
    schema = tpch.generate(scale=0.001).tables
    tables = {t: {c: _shape(one_chip, (SF10_ROWS[t],), a.dtype)
                  for c, a in cols.items()} for t, cols in schema.items()}
    ctx = planner.ExecutionContext(executor="kernel", mode="pallas")
    plan = planner.compile_plan(tpch.LOGICAL_QUERIES["q1"], tables, ctx)
    hlo = plan.fn.lower(tables, {}).compile().as_text()
    assert "tpu_custom_call" in hlo
