"""Distributed join/median lowerings through the planner.

Two regression nets for the retirement of the bespoke W1/W3 shard_map
plans (PR 4):

1. FIXTURE PARITY — tests/fixtures/w1w3_retired_plans.npz pins the outputs
   of the deleted hand-written plans (captured on this backend before
   deletion). The planner-lowered dist_median / dist_hash_join must
   reproduce them under every placement policy: the new lowerings mirror
   the retired plans' data movement (same routing capacities, same
   sort/selection ops, same reduction order), so W1 medians and W3 counts
   match bit for bit. The W3 float checksum is a psum whose rounding
   depends on the JAX build it was recorded under; it matches to 1e-6.

2. STRATEGY PARITY — partitioned-join == broadcast-join == local-join on
   every TPC-H join query under both placement policies: the distributed
   join strategy (like the placement policy) may change cost, never
   answers, and routing capacity overflow must stay zero (surfaced, never
   silent) on these uniform keys.
"""
import os

import pytest

from conftest import run_with_devices

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "w1w3_retired_plans.npz")

FIXTURE_TEST = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.config import PlacementPolicy
from repro.analytics.engine import dist_median, dist_hash_join
from repro.analytics.datasets import moving_cluster, zipf, blanas_join

fx = np.load({fixtures!r})
mesh = jax.make_mesh((8,), ("data",))
G, N, n = 64, 8192, 8

def expand_interleave(out):
    # the RETIRED interleave plan emitted shard-major layout (shard s held
    # groups g % n == s); the planner lowering republishes natural order
    full = np.zeros(G, np.float32)
    per = out.reshape(n, G // n)
    for s in range(n):
        full[np.arange(G)[np.arange(G) % n == s]] = per[s]
    return full

for dsname, ds in (("mc", moving_cluster(N, G, seed=5)),
                   ("zipf", zipf(N, G, seed=5))):
    keys, vals = jnp.asarray(ds.keys), jnp.asarray(ds.vals)
    for pol in PlacementPolicy:
        new = np.asarray(jax.jit(dist_median(mesh, pol, G))(keys, vals))
        old = fx[f"w1_{{dsname}}_{{pol.value}}"]
        if pol == PlacementPolicy.INTERLEAVE:
            old = expand_interleave(old)
        assert np.array_equal(new, old, equal_nan=True), \\
            ("w1", dsname, pol, np.nanmax(np.abs(new - old)))

jd = blanas_join(1024, 8192, seed=6)
bk, bv, pk = map(jnp.asarray, (jd.build_keys, jd.build_vals, jd.probe_keys))
for pol in PlacementPolicy:
    c, s = jax.jit(dist_hash_join(mesh, pol))(bk, bv, pk)
    assert int(np.asarray(c)) == int(fx[f"w3_count_{{pol.value}}"]), pol
    # a float psum recorded under another JAX build is not bit-stable: the
    # checksum is compared to f32 sum precision, the count stays exact
    np.testing.assert_allclose(float(np.asarray(s)),
                               float(fx[f"w3_checksum_{{pol.value}}"]),
                               rtol=1e-6, err_msg=f"w3 checksum {{pol}}")
print("FIXTURE_PARITY_OK")
"""


def test_retired_plan_fixture_parity():
    out = run_with_devices(FIXTURE_TEST.format(fixtures=FIXTURES),
                           timeout=600)
    assert "FIXTURE_PARITY_OK" in out


STRATEGY_TEST = """
import numpy as np, jax
from repro.core.config import PlacementPolicy
from repro.analytics.tpch import generate, run_query
from repro.analytics.planner import ExecutionContext

mesh = jax.make_mesh((8,), ("data",))
data = generate(scale=0.004, seed=1)
for name in ("q3", "q5", "q18"):
    ref = run_query(name, data, executor="xla")
    for pol in (PlacementPolicy.FIRST_TOUCH, PlacementPolicy.INTERLEAVE):
        for dj in ("broadcast", "partitioned"):
            ctx = ExecutionContext(executor="xla", mesh=mesh, policy=pol,
                                   capacity_factor=4.0, dist_join=dj)
            got = run_query(name, data, context=ctx)
            assert set(got) == set(ref), (name, pol, dj)
            for k in ref:
                if k == "_overflow":
                    assert int(np.asarray(got[k])) == 0, (name, pol, dj)
                    continue
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(ref[k]),
                    atol=1e-2, rtol=1e-4, err_msg=f"{name}/{pol}/{dj}/{k}")
print("STRATEGY_PARITY_OK")
"""


def test_partitioned_equals_broadcast_equals_local():
    out = run_with_devices(STRATEGY_TEST, timeout=900)
    assert "STRATEGY_PARITY_OK" in out
