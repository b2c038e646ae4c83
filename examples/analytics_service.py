"""Serving a mixed analytics workload through the concurrent service.

Three clients submit a mix of TPC-H queries — same tables, different
plans, priorities, one with a distributed placement-policy context —
into one ALWAYS-ON AnalyticsService (background drain loop serving
while admission continues). The admission queue bounds intake with
priority classes, the batcher collapses structurally identical requests
into single dispatches, and the morsel scheduler spreads row-range
morsels over socket-pinned worker pools under a ThreadPlacement
strategy (work steals counted). Served results are the planner's own
compiled plans: the whole-plan path is bit-identical to calling
run_query yourself.

The tail of the example is a fault drill: a seeded ServiceFaultInjector
kills worker pool 1 mid-round and fails one dispatch build — the
service retries the build, requeues the dead pool's backlog, and keeps
serving on the survivor (same results, counters tell the story).

    PYTHONPATH=src python examples/analytics_service.py

The mesh spans every device JAX sees in this process: the chips of a TPU
host, or on a CPU the devices that
XLA_FLAGS=--xla_force_host_platform_device_count=N asks for.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.analytics.planner import ExecutionContext
from repro.analytics.service import (AnalyticsService, ServiceConfig,
                                     ThreadPlacement)
from repro.analytics.tpch import generate, run_query, submit_query
from repro.core.compile_cache import enable_compile_cache
from repro.core.config import PlacementPolicy

enable_compile_cache()
data = generate(scale=0.01, seed=7)
local = ExecutionContext(executor="cost")
mesh = jax.make_mesh((len(jax.devices()),), ("data",))
dist = ExecutionContext(executor="cost", mesh=mesh,
                        policy=PlacementPolicy.INTERLEAVE)

service = AnalyticsService(ServiceConfig(
    n_pools=2, workers_per_pool=2, queue_depth=64,
    morsel_rows=8000,                       # split big scans into morsels
    placement=ThreadPlacement.SPARSE))      # stripe morsels across pools
service.start()                             # always-on background drain

# an open-loop burst from three clients: dashboards hammering Q1 (the
# interactive class), an analyst running the join-heavy Q3/Q5, a
# distributed Q18 on the mesh — admitted WHILE the loop serves
rids = {}
for i in range(8):
    rids[f"dash-{i}"] = submit_query(service, "q1", data, context=local,
                                     client_id=0, priority=2)
for i, name in enumerate(("q3", "q5", "q6")):
    rids[f"analyst-{name}"] = submit_query(service, name, data,
                                           context=local, client_id=1,
                                           priority=1)
rids["mesh-q18"] = submit_query(service, "q18", data, context=dist,
                                client_id=2, priority=0)

results = service.drain(timeout=300.0)      # wait for quiescence
stats = service.stats()

print("served", stats.completed, "queries:", stats.describe())
print(f"  batching: {stats.dispatches} dispatches for {stats.completed} "
      f"queries ({stats.dedup_hits} dedup hits)")
print(f"  morsels: {stats.morsels} dispatched, steals/pool = "
      f"{list(stats.steals_per_pool)}")
print(f"  queue wait p50/p99: {stats.queue_wait_p50_ms:.2f}/"
      f"{stats.queue_wait_p99_ms:.2f} ms")

# the whole-plan served result is bit-identical to serial execution
ref = run_query("q18", data, context=dist)
got = results[rids["mesh-q18"]].value
err = max(np.abs(np.asarray(got[k]) - np.asarray(ref[k])).max()
          for k in ref)
print(f"\nserved q18 vs serial run_query: max |diff| = {err} "
      "(same compiled plan, same inputs)")
service.stop()

# --- fault drill: kill a pool mid-round + fail a build, keep serving ---
from repro.analytics.service import RetryPolicy, ServiceFaultInjector

faults = ServiceFaultInjector(seed=0, build_fail_at={0},
                              kill_pool_at=(2, 1))
drill = AnalyticsService(ServiceConfig(
    n_pools=2, workers_per_pool=2, batching=False, faults=faults,
    retry=RetryPolicy(max_attempts=3, base_backoff_s=0.005)))
drill_rids = [submit_query(drill, q, data, context=local)
              for q in ("q1", "q3", "q6", "q1", "q6")]
drill_res = drill.drain()
dst = drill.stats()
drill.close()
ref_q1 = run_query("q1", data, context=local)
same = all(np.array_equal(np.asarray(drill_res[drill_rids[0]].value[k]),
                          np.asarray(ref_q1[k])) for k in ref_q1)
print(f"\nfault drill: build_failures={faults.builds_failed} "
      f"pool_kills={faults.pools_killed} -> retries={dst.retries}, "
      f"dead_pools={list(dst.dead_pools)}, requeued={dst.requeued}, "
      f"completed={dst.completed}/{len(drill_rids)} "
      f"(bit-identical={same})")
