"""One user-authored logical plan, many execution strategies.

The paper's thesis — NUMA tuning applies without rewriting the application
— as an API: a query is authored ONCE against the logical plan IR
(repro.analytics.plan) and handed to the cost-based physical planner
(repro.analytics.planner), which changes the execution strategy through
the ExecutionContext alone: naive XLA plan, cost-chosen fused kernels, or
a distributed placement-policy backend on a device mesh.

    PYTHONPATH=src python examples/analytics_plan.py

The mesh spans every device JAX sees in this process: the chips of a TPU
host, or on a CPU the devices that
XLA_FLAGS=--xla_force_host_platform_device_count=N asks for.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.analytics.plan import LogicalPlan, col, describe, scan
from repro.analytics.planner import ExecutionContext, execute_plan, explain
from repro.analytics.tpch import generate
from repro.core.compile_cache import enable_compile_cache
from repro.core.config import PlacementPolicy

enable_compile_cache()

# A query that is NOT one of the five shipped TPC-H builders: revenue and
# order count per customer nation for heavily-discounted recent lineitems.
li = scan("lineitem").filter((col("l_discount") >= 0.05)
                             & (col("l_shipdate") > 1800))
li = li.join(scan("orders"), "l_orderkey", "o_orderkey",
             {"_cust": "o_custkey"})
li = li.join(scan("customer"), "_cust", "c_custkey",
             {"_nation": "c_nationkey"})
li = li.project(_rev=col("l_extendedprice") * (1 - col("l_discount")))
plan = LogicalPlan(
    li.aggregate("_nation", 25, revenue=("sum", "_rev"),
                 avg_rev=("avg", "_rev"), orders=("count", "_rev")),
    ("revenue", "avg_rev", "orders", "_overflow"))

print("logical plan:")
print(describe(plan))

tables = generate(scale=0.01, seed=7).as_jax()

# Context 1: single device, cost-based physical choices.
local = ExecutionContext(executor="cost")
print("\nplanner decisions (local, cost-based):")
for d in explain(plan, tables, local):
    print(" ", d.describe())
out_local = execute_plan(plan, tables, local)

# Context 2: SAME plan on a device mesh under a placement policy.
n_dev = len(jax.devices())
mesh = jax.make_mesh((n_dev,), ("data",))
dist = ExecutionContext(executor="cost", mesh=mesh,
                        policy=PlacementPolicy.INTERLEAVE)
out_dist = execute_plan(plan, tables, dist)

print("\nrevenue by nation (local cost-based):")
print(np.array2string(np.asarray(out_local["revenue"]), precision=0))
print(f"revenue by nation ({n_dev}-device mesh, INTERLEAVE policy):")
print(np.array2string(np.asarray(out_dist["revenue"]), precision=0))
err = np.abs(np.asarray(out_local["revenue"])
             - np.asarray(out_dist["revenue"])).max()
print(f"\nmax |local - distributed| = {err:.3g} "
      "(same logical plan, two execution strategies)")
