"""Quickstart: the paper's thesis in 60 seconds on CPU.

Runs W1 (holistic aggregation) + W3 (hash join) single-device, then shows
the four placement policies producing identical answers with different
communication plans, and a reduced-LM train step — all through the same
application-agnostic knobs.

    PYTHONPATH=src python examples/quickstart.py

The placement-policy step uses a mesh over every device JAX sees in this
process (on a CPU, XLA_FLAGS=--xla_force_host_platform_device_count=N
gives N).
"""
import sys
import os

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.analytics.aggregate import median_jit
from repro.analytics.datasets import blanas_join, moving_cluster, zipf
from repro.analytics.engine import dist_count
from repro.analytics.join import hash_join
from repro.configs.reduced import REDUCED
from repro.core.compile_cache import enable_compile_cache
from repro.core.config import LM_SHAPES, PlacementPolicy, RunConfig, TrainConfig
from repro.models.lm import LMModel
from repro.runtime import train


def main():
    enable_compile_cache()
    print("== W1: holistic aggregation (GROUP BY median) ==")
    ds = moving_cluster(200_000, 4096, seed=0)
    med = median_jit(jnp.asarray(ds.keys), jnp.asarray(ds.vals), 4096)
    print(f"   groups: {int(jnp.sum(~jnp.isnan(med)))}/4096, "
          f"median[0]={float(med[0]):.4f}")

    print("== W3: hash join (1:16 PK-FK) ==")
    jd = blanas_join(65_536, 1_048_576, seed=1)
    cnt, chk, ovf = hash_join(jnp.asarray(jd.build_keys),
                              jnp.asarray(jd.build_vals),
                              jnp.asarray(jd.probe_keys),
                              n_partitions=64, mode="ref")
    print(f"   matches: {int(cnt)}, checksum: {float(chk):.1f}, "
          f"overflow: {int(ovf)}")

    n_dev = len(jax.devices())
    print(f"== placement policies ({n_dev}-device mesh) ==")
    mesh = jax.make_mesh((n_dev,), ("data",))
    keys = jnp.asarray(zipf(65536, 64, seed=2).keys)
    for pol in PlacementPolicy:
        out = jax.jit(dist_count(mesh, pol, 64))(keys)
        print(f"   {pol.value:12s} total={float(out.sum()):.0f}")

    print("== reduced-LM train step (qwen2-family) ==")
    arch = REDUCED["qwen2-0.5b"]
    model = LMModel(arch, tp=1, remat="none")
    cfg = RunConfig(arch=arch, shape=LM_SHAPES["train_4k"],
                    train=TrainConfig(learning_rate=3e-3, warmup_steps=2))
    res = train(model, cfg, n_steps=6, batch=4, seq=32)
    print(f"   loss: {res.losses[0]:.3f} -> {res.final_loss:.3f}")
    print("quickstart OK")


if __name__ == "__main__":
    main()
