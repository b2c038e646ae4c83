"""Calibrate the planner's cost constants from measured microbenchmarks.

The cost model (planner.aggregate_costs) expresses every physical
Aggregate layout in *pass-equivalents* over the input rows, with three
hand-set constants: FUSED_FIXED (fused sweep setup), FUSED_PER_COL
(marginal cost per stacked column), SORT_PASS_FACTOR (argsort passes per
log2 n). This script measures them on the CURRENT backend:

  1. one-pass baseline: t_xla(C) — the XLA layout runs one segment op per
     stacked column, so its slope over C is the per-pass unit time;
  2. fused sweep: t_dense(C) / pass_time fit to fixed + per_col * C;
  3. sort: t_argsort / (pass_time * log2 n).

and writes a JSON profile ``planner.load_cost_profile()`` consumes —
replacing the hand-set constants with the crossover the hardware actually
exhibits (a CPU reference lowering and a real TPU disagree wildly about
the fused kernel's fixed cost; the profile lets the same model serve
both).

With ``--dist`` it also measures the DISTRIBUTED join crossover on a
fake-device child mesh: broadcast (all-gather the build side) vs
key-partitioned (route both sides) at a sweep of build sizes. The model
prices broadcast at n_build*(n-1) moved rows and partitioned at
(n_probe+n_build)*(n-1)/n * dist_route_factor; setting the two equal at
the MEASURED crossover build size B* gives

    dist_route_factor = B* * n / (n_probe + B*)

which is written into the profile so planner.choose_dist_join flips
strategies where this hardware actually flips.

With ``--exchange`` it measures the hash-Exchange ROUTING LAYOUT
crossover on the same fake-device child mesh: the partitioned join with
``exchange_impl`` forced to the stable argsort vs the radix-histogram
layout at a sweep of probe sizes. The model prices the argsort layout at
sort_pass_factor * log2(per-shard rows) pass-equivalents and the radix
layout flat; setting the two equal at the MEASURED crossover probe size
P* gives

    radix_route_factor = sort_pass_factor * log2(P* / devices)

written into the profile so planner.choose_exchange_impl flips layouts
where this hardware actually flips.

With ``--morsel`` it measures the serving scheduler's SPLIT-PROBE
crossover in-process (no mesh): a PK-FK join pipeline dispatched as one
whole-plan morsel vs split into per-pool probe morsels (build side
replicated per pool) at a sweep of probe sizes. Below the crossover the
per-morsel dispatch overhead loses to one fused dispatch; the first
probe size where splitting wins (geometric midpoint with its
single-winning neighbor) is written as ``morsel_split_rows`` — the
threshold ``planner.lower`` marks PJoin probe phases morsel-splittable
at, cache-keyed like the other fitted constants.

With ``--refresh PROFILE.json`` it instead runs the TELEMETRY loop: load
the profile, execute a representative recorded workload (a selective-
probe partitioned join on a fake-device mesh — the shape whose runtime
selectivity static costing cannot see), and rewrite the profile's
drifting entries from the observed stats via
``telemetry.refresh_profile`` (``dist_route_factor`` from observed vs
estimated moved rows, ``compact_margin`` from observed Compact
occupancy; ``dense_group_limit`` is never auto-refreshed). Entries
within the drift band are left untouched — refresh complements the
microbenchmark fits, it does not replace them.

With ``--sweep-groups`` it additionally sweeps the GROUP DOMAIN and fits
the two remaining hand-set constants:

  * ``dense_group_limit`` — the largest swept n_groups where the dense
    full-width fused layout still beats the range-partitioned one (the
    hand-set constant is a VMEM model; the sweep measures where the
    crossover actually sits on this backend);
  * ``partition_capacity_factor`` — the smallest capacity factor at which
    the range-partitioned layout reports ZERO overflow on a zipf-skewed
    key set (the paper's e=0.5 skew), times a 1.25 safety margin. The
    planner applies it to the partitioned AGGREGATE layout only; routing
    capacities stay on the ExecutionContext.

    PYTHONPATH=src python scripts/calibrate_costs.py --out cost_profile.json
    PYTHONPATH=src python scripts/calibrate_costs.py --dist --out cost_profile.json
    PYTHONPATH=src python scripts/calibrate_costs.py --exchange --out cost_profile.json
    PYTHONPATH=src python scripts/calibrate_costs.py --morsel --out cost_profile.json
    PYTHONPATH=src python scripts/calibrate_costs.py --sweep-groups --out cost_profile.json
    PYTHONPATH=src python scripts/calibrate_costs.py --refresh cost_profile.json
    >>> planner.load_cost_profile("cost_profile.json")
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

def calibrate_dist(probe: int, builds, devices: int):
    """(dist_route_factor, raw sweep) from a fake-device child mesh.

    The child runs repro.analytics.dist_join_bench.sweep_code through
    benchmarks.common.run_in_mesh — the SAME snippet and the SAME
    subprocess harness benchmarks/fig7_index_join.py uses, so the fitted
    constant prices exactly what the benchmark (and the planner's cost
    model) measures."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.common import run_in_mesh
    from repro.analytics.dist_join_bench import sweep_code
    raw = run_in_mesh(sweep_code(probe=probe, builds=builds,
                                 devices=devices),
                      n_devices=devices, timeout=1800)
    sweep = sorted((int(b), d) for b, d in raw.items())
    # crossover: first build size where routing both sides beats the
    # all-gather; geometric midpoint with its broadcast-winning neighbor
    b_star = None
    for i, (b, d) in enumerate(sweep):
        if d["partitioned"] < d["broadcast"]:
            b_star = (math.sqrt(sweep[i - 1][0] * b) if i else float(b))
            break
    if b_star is None:
        # partitioned never won in range: pin the factor just above the
        # largest measured build so the model keeps broadcasting there
        b_star = 2.0 * sweep[-1][0]
    factor = b_star * devices / (probe + b_star)
    return max(round(float(factor), 4), 0.01), raw


def calibrate_exchange(probes, build: int, devices: int,
                       sort_pass_factor: float):
    """(radix_route_factor, raw sweep) from the forced-impl Exchange
    sweep — repro.analytics.dist_join_bench.exchange_code, the SAME
    snippet fig7_index_join.run_dist records, through the same
    subprocess-mesh harness.

    choose_exchange_impl compares sort_pass_factor * log2(n) against the
    flat radix_route_factor at n = per-shard routed rows; equality at the
    measured crossover probe size P* fits the flat constant."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.common import run_in_mesh
    from repro.analytics.dist_join_bench import exchange_code
    raw = run_in_mesh(exchange_code(build=build, probes=probes,
                                    devices=devices),
                      n_devices=devices, timeout=1800)
    sweep = sorted((int(p), d) for p, d in raw.items())
    # crossover: first probe size where the radix layout beats the
    # argsort; geometric midpoint with its argsort-winning neighbor
    p_star = None
    for i, (p, d) in enumerate(sweep):
        if d["radix"] < d["argsort"]:
            p_star = (math.sqrt(sweep[i - 1][0] * p) if i else float(p))
            break
    if p_star is None:
        # radix never won in range: pin the crossover just above the
        # largest measured probe so the model keeps the argsort layout
        p_star = 2.0 * sweep[-1][0]
    factor = sort_pass_factor * math.log2(max(p_star / devices, 2.0))
    return max(round(float(factor), 4), 0.01), raw


def calibrate_morsel(probes, n_pools: int, workers: int,
                     morsels_per_pool: int = 4):
    """(morsel_split_rows, raw sweep) from the in-process serving
    scheduler: single-morsel whole-plan dispatch vs split-probe dispatch
    of the SAME join pipeline, per probe size.

    Both sides run through MorselScheduler.run — the exact dispatch path
    build_task takes in production — with the split decision forced each
    way via the profile's morsel_split_rows (n+1 = never split, 1 =
    always split), so the fitted threshold prices exactly the overhead
    the planner's mark trades against."""
    import dataclasses

    import jax.numpy as jnp

    from repro.analytics import plan as L
    from repro.analytics import planner
    from repro.analytics.planner import ExecutionContext
    from repro.analytics.service.scheduler import MorselScheduler

    rng = np.random.RandomState(7)
    dim_rows = 256
    base = planner.current_cost_profile()
    raw = {}
    wins = []                          # (probe_rows, split_won) ascending
    try:
        for n in sorted(probes):
            tables = {
                "fact": {"fk": jnp.asarray(rng.randint(
                             0, dim_rows, n).astype(np.int32)),
                         "fv": jnp.asarray(rng.rand(n).astype(np.float32))},
                "dim": {"pk": jnp.asarray(np.arange(dim_rows,
                                                    dtype=np.int32)),
                        "dv": jnp.asarray(rng.rand(dim_rows).astype(
                            np.float32))},
            }
            p = L.LogicalPlan(
                L.scan("fact").join(L.scan("dim"), "fk", "pk", {"dv": "dv"})
                .aggregate("fk", dim_rows, s=("sum", "fv"),
                           c=("count", "fv")), None)
            ctx = ExecutionContext()
            morsel = max(n // (n_pools * morsels_per_pool), 1)
            t = {}
            for tag, threshold in (("single", n + 1), ("split", 1)):
                planner.set_cost_profile(dataclasses.replace(
                    base, morsel_split_rows=threshold))
                with MorselScheduler(n_pools=n_pools,
                                     workers_per_pool=workers,
                                     morsel_rows=morsel) as sched:
                    t[tag] = time_fn(lambda: sched.run(p, tables, ctx))
            raw[str(n)] = {k: round(v * 1e6, 1) for k, v in t.items()}
            wins.append((n, t["split"] < t["single"]))
    finally:
        planner.set_cost_profile(base)
    p_star = None
    for i, (n, won) in enumerate(wins):
        if won:
            p_star = (math.sqrt(wins[i - 1][0] * n) if i else float(n))
            break
    if p_star is None:
        # splitting never won in range: pin the threshold just above the
        # largest measured probe so the planner keeps whole-plan dispatch
        p_star = 2.0 * wins[-1][0]
    return max(int(round(p_star)), 1), raw


def sweep_groups(rows: int, groups_sweep, cols: int, mode,
                 capacity_factors) -> dict:
    """Measure the dense/partitioned crossover over n_groups and the
    smallest zero-overflow partition capacity factor under zipf skew.

    Returns {"dense_group_limit", "partition_capacity_factor", raw
    timings}. dense_group_limit falls back to the builtin constant when
    dense wins everywhere in range (the sweep then only certifies it)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.analytics.columnar import (DENSE_GROUP_LIMIT,
                                          stacked_group_sums)
    from repro.analytics.datasets import zipf

    rng = np.random.RandomState(1)
    raw = {"dense": {}, "partitioned": {}}
    wins = []                      # (G, dense_won) in ascending-G order
    for G in sorted(groups_sweep):
        keys = jnp.asarray(rng.randint(0, G, rows).astype(np.int32))
        vals = [jnp.asarray(rng.rand(rows).astype(np.float32))
                for _ in range(cols)]
        t = {}
        for layout in ("dense", "partitioned"):
            fn = jax.jit(functools.partial(stacked_group_sums, n_groups=G,
                                           layout=layout, mode=mode))
            t[layout] = time_fn(lambda: fn(keys, vals))
            raw[layout][str(G)] = round(t[layout] * 1e6, 1)
        wins.append((G, t["dense"] <= t["partitioned"]))
    # Crossover = first SUSTAINED loss (a loss followed by another loss,
    # or a loss at the end of the range): a single noisy sample at either
    # end can neither disable dense everywhere nor extend it past the
    # measured flip. The fitted limit is the last win before it.
    cross_idx = next(
        (i for i, (_G, won) in enumerate(wins)
         if not won and (i == len(wins) - 1 or not wins[i + 1][1])), None)
    if cross_idx is None:
        # dense never sustainedly lost in range: no crossover observed,
        # keep the VMEM-model constant rather than extrapolate past data
        limit = DENSE_GROUP_LIMIT
    else:
        prior_wins = [G for G, won in wins[:cross_idx] if won]
        # no win below the crossover: the measurement upper-bounds the
        # limit just below the smallest swept point (recording the
        # permissive builtin would contradict the sweep's own numbers)
        limit = max(prior_wins) if prior_wins else min(groups_sweep) - 1

    # capacity-factor fit: smallest cf with zero overflow on zipf keys
    ds = zipf(rows, max(groups_sweep), seed=3)
    keys = jnp.asarray(ds.keys)
    vals = [jnp.asarray(ds.vals)] * cols
    fitted_cf = None
    raw["overflow_at_cf"] = {}
    for cf in sorted(capacity_factors):
        fn = jax.jit(functools.partial(
            stacked_group_sums, n_groups=max(groups_sweep),
            layout="partitioned", mode=mode, capacity_factor=cf))
        _sums, ovf = jax.block_until_ready(fn(keys, vals))
        raw["overflow_at_cf"][str(cf)] = int(np.asarray(ovf))
        if int(np.asarray(ovf)) == 0:
            fitted_cf = cf
            break
    if fitted_cf is None:
        # every swept factor overflowed: the fit is INCONCLUSIVE — leave
        # the profile entry null (the planner keeps the context's factor)
        # rather than record a known-overflowing value as calibrated
        print(f"sweep_groups: no overflow-free capacity factor in "
              f"{sorted(capacity_factors)} (overflows: "
              f"{raw['overflow_at_cf']}); leaving "
              f"partition_capacity_factor unset", file=sys.stderr)
    return {
        "dense_group_limit": int(limit),
        "partition_capacity_factor": (None if fitted_cf is None
                                      else round(float(fitted_cf) * 1.25,
                                                 4)),
        "raw": raw,
    }


def refresh_from_telemetry(path: str, devices: int) -> None:
    """Rewrite ``path``'s drifting cost entries from observed telemetry.

    Must run before jax is imported anywhere in the process: it forces
    ``devices`` fake host devices so the recorded workload exercises the
    real distributed Exchange/Compact lowerings."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}").strip()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.analytics import plan as L
    from repro.analytics import planner, telemetry

    from repro.core.config import PlacementPolicy

    profile = planner.load_cost_profile(path)
    rng = np.random.RandomState(0)
    n_rows = ((1 << 12) // devices) * devices
    dim_rows = 512
    tables = {
        "fact": {"fk": jnp.asarray(
                     rng.randint(0, dim_rows, n_rows).astype(np.int32)),
                 "fv": jnp.asarray(rng.rand(n_rows).astype(np.float32))},
        "dim": {"pk": jnp.asarray(np.arange(dim_rows, dtype=np.int32)),
                "dv": jnp.asarray(rng.rand(dim_rows).astype(np.float32))},
    }
    # selective probe ahead of a forced-partitioned join: the routed
    # traffic the profile's dist_route_factor prices, observed exactly
    p = L.LogicalPlan(
        L.scan("fact").filter(L.col("fv") < 0.1)
        .join(L.scan("dim"), "fk", "pk", {"dv": "dv"})
        .aggregate("fk", dim_rows, c=("count", "fv"), x=("max", "dv")),
        ("c", "x"))
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    ctx = planner.ExecutionContext(executor="cost", mesh=mesh,
                                   policy=PlacementPolicy.INTERLEAVE,
                                   dist_join="partitioned")
    telemetry.registry().clear()
    with telemetry.recording():
        planner.compile_plan(p, tables, ctx)(tables)
    refreshed = telemetry.refresh_profile(profile)
    planner.set_cost_profile(None)
    if refreshed is profile:
        print(f"refresh: no cost entry drifted outside the "
              f"{telemetry.DRIFT_BAND}x band; {path} left unchanged")
        return
    with open(path) as f:
        raw = json.load(f)
    updates = {}
    for entry in ("dist_route_factor", "compact_margin",
                  "filter_selectivity"):
        new = getattr(refreshed, entry)
        if new is not None and new != getattr(profile, entry):
            updates[entry] = new
    raw.update(updates)
    raw["refreshed_from"] = "telemetry"
    with open(path, "w") as f:
        json.dump(raw, f, indent=2)
        f.write("\n")
    print(f"refresh: rewrote {sorted(updates)} in {path}: "
          + ", ".join(f"{k}={v}" for k, v in sorted(updates.items())))


def time_fn(fn, *, warmup: int = 2, iters: int = 5) -> float:
    """Median seconds per call, results blocked."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 18,
                    help="input rows for the microbenchmarks")
    ap.add_argument("--groups", type=int, default=512,
                    help="group domain (must stay under DENSE_GROUP_LIMIT)")
    ap.add_argument("--cols", type=int, nargs="+", default=[1, 2, 3, 4, 6],
                    help="stacked-matrix widths to sweep")
    ap.add_argument("--mode", default=None,
                    help="kernel lowering mode (None = backend default)")
    ap.add_argument("--dist", action="store_true",
                    help="also measure the broadcast vs partitioned "
                         "distributed-join crossover on a fake-device mesh "
                         "and fit dist_route_factor")
    ap.add_argument("--exchange", action="store_true",
                    help="also measure the argsort vs radix Exchange "
                         "routing-layout crossover on a fake-device mesh "
                         "and fit radix_route_factor")
    ap.add_argument("--exchange-probes", type=int, nargs="+",
                    default=[1 << b for b in range(10, 19, 2)],
                    help="probe sizes to sweep for the --exchange "
                         "crossover")
    ap.add_argument("--exchange-build", type=int, default=1 << 14,
                    help="build-side size for the --exchange sweep")
    ap.add_argument("--morsel", action="store_true",
                    help="also measure the serving scheduler's whole-plan "
                         "vs split-probe dispatch crossover in-process and "
                         "fit morsel_split_rows")
    ap.add_argument("--morsel-probes", type=int, nargs="+",
                    default=[1 << b for b in range(8, 17, 2)],
                    help="probe sizes to sweep for the --morsel crossover")
    ap.add_argument("--morsel-pools", type=int, default=2)
    ap.add_argument("--morsel-workers", type=int, default=2)
    ap.add_argument("--sweep-groups", action="store_true",
                    help="also sweep n_groups to fit dense_group_limit and "
                         "the partitioned-layout capacity factor")
    ap.add_argument("--groups-sweep", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096, 8192, 16384],
                    help="group domains for the --sweep-groups crossover")
    ap.add_argument("--capacity-factors", type=float, nargs="+",
                    default=[1.0, 1.25, 1.5, 2.0, 3.0],
                    help="candidate partition capacity factors "
                         "(--sweep-groups fits the smallest overflow-free)")
    ap.add_argument("--refresh", metavar="PROFILE.json", default=None,
                    help="telemetry-refresh mode: run a recorded "
                         "representative workload on a fake-device mesh and "
                         "rewrite the profile's drifting entries "
                         "(dist_route_factor / compact_margin) from the "
                         "observed stats; all other sweeps are skipped")
    ap.add_argument("--dist-devices", type=int, default=8)
    ap.add_argument("--dist-probe", type=int, default=1 << 17,
                    help="probe rows for the distributed-join sweep")
    ap.add_argument("--dist-builds", type=int, nargs="+",
                    default=[1 << b for b in range(10, 18, 2)],
                    help="build-side sizes to sweep for the crossover")
    ap.add_argument("--out", default="cost_profile.json")
    args = ap.parse_args()

    if args.refresh:
        # must precede ANY jax import (it forces fake host devices)
        refresh_from_telemetry(args.refresh, min(args.dist_devices, 4))
        return

    import functools

    import jax
    import jax.numpy as jnp

    from repro.analytics.columnar import stacked_group_sums

    rng = np.random.RandomState(0)
    N, G = args.rows, args.groups
    keys = jnp.asarray(rng.randint(0, G, N).astype(np.int32))

    def bench(layout: str, C: int) -> float:
        vals = [jnp.asarray(rng.rand(N).astype(np.float32))
                for _ in range(C)]
        fn = jax.jit(functools.partial(stacked_group_sums, n_groups=G,
                                       layout=layout, mode=args.mode))
        return time_fn(lambda: fn(keys, vals))

    cols = sorted(set(args.cols))
    t_xla = {C: bench("xla", C) for C in cols}
    t_dense = {C: bench("dense", C) for C in cols}
    # per-pass unit time = slope of the one-segment-op-per-column layout
    xs = np.asarray(cols, np.float64)
    pass_time = max(float(np.polyfit(xs, [t_xla[C] for C in cols], 1)[0]),
                    1e-9)
    # fused pass-equivalents: fixed + per_col * C
    fused_eq = np.asarray([t_dense[C] / pass_time for C in cols])
    per_col, fixed = np.polyfit(xs, fused_eq, 1)
    # the model needs positive constants; a negative fit (e.g. a noisy
    # tiny-input run) falls back toward the hand-set shape
    fixed = max(float(fixed), 0.05)
    per_col = max(float(per_col), 0.01)

    t_sort = time_fn(lambda: jnp.sort(keys))
    sort_factor = max(t_sort / (pass_time * math.log2(max(N, 2))), 0.01)

    profile = {
        "fused_fixed": round(fixed, 4),
        "fused_per_col": round(per_col, 4),
        "sort_pass_factor": round(float(sort_factor), 4),
        "backend": jax.default_backend(),
        "n_rows": N,
        "n_groups": G,
        "pass_time_us": round(pass_time * 1e6, 3),
        "raw_us": {
            "xla": {str(C): round(t_xla[C] * 1e6, 1) for C in cols},
            "dense": {str(C): round(t_dense[C] * 1e6, 1) for C in cols},
            "sort": round(t_sort * 1e6, 1),
        },
    }
    if args.sweep_groups:
        fit = sweep_groups(args.rows, args.groups_sweep, max(cols),
                           args.mode, args.capacity_factors)
        profile["dense_group_limit"] = fit["dense_group_limit"]
        profile["partition_capacity_factor"] = \
            fit["partition_capacity_factor"]
        profile["raw_us"]["groups_sweep"] = fit["raw"]
    if args.dist:
        factor, raw_dist = calibrate_dist(args.dist_probe, args.dist_builds,
                                          args.dist_devices)
        profile["dist_route_factor"] = factor
        profile["dist_probe"] = args.dist_probe
        profile["dist_devices"] = args.dist_devices
        profile["raw_us"]["dist_join"] = raw_dist
    if args.exchange:
        # fit against the sort factor just measured above, so both sides
        # of the choose_exchange_impl comparison share one unit system
        factor, raw_ex = calibrate_exchange(
            args.exchange_probes, args.exchange_build, args.dist_devices,
            profile["sort_pass_factor"])
        profile["radix_route_factor"] = factor
        profile["exchange_build"] = args.exchange_build
        profile["raw_us"]["exchange_impl"] = raw_ex
    if args.morsel:
        threshold, raw_morsel = calibrate_morsel(
            args.morsel_probes, args.morsel_pools, args.morsel_workers)
        profile["morsel_split_rows"] = threshold
        profile["morsel_pools"] = args.morsel_pools
        profile["raw_us"]["morsel_split"] = raw_morsel

    with open(args.out, "w") as f:
        json.dump(profile, f, indent=2)
        f.write("\n")
    print(json.dumps(profile, indent=2))
    print(f"\nwrote {args.out}; install with "
          f"planner.load_cost_profile({args.out!r})")


if __name__ == "__main__":
    main()
