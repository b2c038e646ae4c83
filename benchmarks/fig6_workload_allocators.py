"""Paper Figure 6: workload x allocator.

Device workloads W1/W2/W3 wall time with the partition-buffer tuning the
allocator implies (capacity factor = slack the allocator reserves;
partition count = arena granularity) — the device-side analogue of "which
allocator backs the hash tables".
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp

from benchmarks.common import Row, time_fn
from repro.analytics.aggregate import count_partitioned, median_jit
from repro.analytics.datasets import blanas_join, moving_cluster
from repro.analytics.join import hash_join


def run() -> List[Row]:
    rows: List[Row] = []
    G, N = 4096, 1 << 19
    ds = moving_cluster(N, G, seed=1)
    keys = jnp.asarray(ds.keys)
    vals = jnp.asarray(ds.vals)

    # device-side buffer tuning (bump=tight serial, slab=sized classes)
    tunings = {"bump_like": dict(n_partitions=1, capacity_factor=1.05),
               "arena_like": dict(n_partitions=16, capacity_factor=1.5),
               "slab_like": dict(n_partitions=64, capacity_factor=2.0)}
    for name, kw in tunings.items():
        us = time_fn(lambda kw=kw: count_partitioned(keys, G, mode="ref",
                                                     **kw))
        rows.append((f"fig6_w2_{name}", us, str(kw)))
    us = time_fn(lambda: median_jit(keys, vals, G))
    rows.append(("fig6_w1_sort_median", us, f"N={N};G={G}"))

    jd = blanas_join(1 << 14, 1 << 17, seed=2)
    bk, bv, pk = (jnp.asarray(jd.build_keys), jnp.asarray(jd.build_vals),
                  jnp.asarray(jd.probe_keys))
    for name, nparts in (("arena_like", 32), ("slab_like", 128)):
        kw = dict(n_partitions=nparts, capacity_factor=2.0)
        us = time_fn(lambda kw=kw: hash_join(bk, bv, pk, mode="ref", **kw))
        rows.append((f"fig6_w3_{name}", us, str(kw)))

    return rows
