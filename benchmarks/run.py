"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment format). Modules:
  fig2   allocator microbenchmark (scalability + memory overhead)
  fig3/4 thread placement (layouts; sparse/dense undersubscription)
  fig5   placement policies x auto-rebalance (8-device mesh, measured)
  fig6   workload x allocator (device buffers + serving page pool)
  fig7   index nested-loop join (three index kinds)
  fig7_dist  distributed join: broadcast vs key-partitioned, plus the
         distributed TopK lowerings (replicated vs candidate-exchange)
         (8-dev mesh)
  fig8/9 TPC-H default vs tuned configuration
  fig_service  concurrent serving: QPS x p99 for ThreadPlacement x
         PlacementPolicy over a mixed Q1/Q3/Q6 open-loop workload
  fig_service_faults  degraded-mode serving: multi-tenant skewed-rate
         open-loop workload with a mid-run pool kill; per-class SLO and
         the degraded/healthy QPS ratio (absolute floor >= 0.50, gated
         whenever the module runs)
  fig_service_morsel  intra-query morsel parallelism: the same burst
         served whole-plan vs split-probe (build sides pool-replicated);
         QPS/p99 plus the split/whole ratio (absolute floor >= 0.15)
  fig_drift  estimator-drift summary: representative plans run under
         telemetry; reports drifting (node, stat) entries (absolute
         floor >= 1 — the detector must fire) and the max
         observed/estimated deviation ratio per Decision kind
  roofline  the dry-run (arch x shape x mesh) table
"""
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings to run")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the subprocess-mesh figures")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write rows as JSON {name: us_per_call}, e.g. "
                         "BENCH_tpch.json for the perf trajectory")
    ap.add_argument("--check", default=None, metavar="PREV",
                    help="compare guarded rows against a previous --json "
                         "recording and exit non-zero on a >25%% latency "
                         "regression (makes the bench trajectory "
                         "enforceable in CI)")
    args = ap.parse_args()

    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig2_allocator_microbench,
                            fig3_fig4_thread_placement,
                            fig5_placement_policies,
                            fig6_workload_allocators, fig7_index_join,
                            fig8_fig9_tpch, fig_drift,
                            fig_service_throughput, roofline_table)
    from types import SimpleNamespace
    modules = [
        ("fig2", fig2_allocator_microbench),
        ("fig3_fig4", fig3_fig4_thread_placement),
        ("fig5", fig5_placement_policies),
        ("fig6", fig6_workload_allocators),
        ("fig7", fig7_index_join),
        ("fig7_dist", SimpleNamespace(run=fig7_index_join.run_dist)),
        ("fig8_fig9", fig8_fig9_tpch),
        ("fig_service", fig_service_throughput),
        ("fig_service_faults",
         SimpleNamespace(run=fig_service_throughput.run_faults)),
        ("fig_service_morsel",
         SimpleNamespace(run=fig_service_throughput.run_morsel)),
        ("fig_drift", fig_drift),
        ("roofline", roofline_table),
    ]
    if args.skip_slow:
        # the subprocess-mesh figures
        modules = [m for m in modules
                   if m[0] not in ("fig5", "fig7_dist", "fig_service")]
    if args.only:
        # a token that IS a module name selects exactly that module
        # (--only fig7 must not drag in the slow fig7_dist subprocess
        # sweep); other tokens keep substring semantics (--only fig3)
        names = {m[0] for m in modules}
        keys = args.only.split(",")
        modules = [m for m in modules
                   if any(k == m[0] if k in names else k in m[0]
                          for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    collected = {}
    for name, mod in modules:
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}")
                collected[row_name] = us
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=2, sort_keys=True)
            f.write("\n")
    # absolute floors are checked WHENEVER their row was collected — no
    # baseline recording needed, so even a bootstrap CI run (no previous
    # --json) gates degraded-mode serving capacity
    floor_failed = check_floors(collected)
    if args.check and check_regression(collected, args.check):
        sys.exit(2)
    if floor_failed:
        sys.exit(2)
    sys.exit(1 if failures else 0)


# Rows whose latency the --check gate guards (the tuned-path trajectory).
CHECKED_ROWS = ("fig8_tpch_q1_tuned",)
CHECK_THRESHOLD = 1.25           # fail on >25% latency regression
# Rows whose value column is a THROUGHPUT (higher is better): the served
# Q1-mix QPS floor. A >25% QPS drop (collected < 0.75 * baseline) fails.
CHECKED_THROUGHPUT_ROWS = ("fig_service_q1mix_batched_qps",)
QPS_CHECK_THRESHOLD = 1.0 / 0.75
# Rows gated against an ABSOLUTE floor (no baseline needed): checked on
# every run that collects them. The degraded-QPS ratio asserts the
# service keeps >= 50% of healthy throughput after losing a pool; the
# drift-report row asserts the telemetry detector actually fires on the
# representative mis-estimated plans (a drift report is PRODUCED); the
# morsel ratio asserts split-probe serving keeps at least 15% of
# whole-plan throughput (best-of-3 bursts; it should GAIN on real
# multi-socket hardware, but the floor only has to catch a broken
# split path, not enforce speedup on an arbitrarily-loaded CI box
# whose single XLA threadpool serializes per-morsel dispatch).
CHECKED_FLOOR_ROWS = {"fig_service_degraded_qps_ratio": 0.50,
                      "fig_drift_report_rows": 1.0,
                      "fig_service_morsel_qps_ratio": 0.15}


def check_floors(collected: dict) -> bool:
    """True (-> non-zero exit) if any collected row sits below its floor."""
    failed = False
    for row, floor in CHECKED_FLOOR_ROWS.items():
        if row not in collected:
            continue
        ok = collected[row] >= floor
        print(f"check_{row},{collected[row]:.3f},"
              f"floor={floor:.2f} {'ok' if ok else 'BELOW_FLOOR'}")
        if not ok:
            failed = True
    return failed


def check_regression(collected: dict, prev_path: str) -> bool:
    """True (-> non-zero exit) if any guarded row regressed past threshold."""
    with open(prev_path) as f:
        prev = json.load(f)
    regressed = False
    checks = ([(r, CHECK_THRESHOLD, False) for r in CHECKED_ROWS]
              + [(r, QPS_CHECK_THRESHOLD, True)
                 for r in CHECKED_THROUGHPUT_ROWS])
    for row, threshold, is_qps in checks:
        if row not in collected:
            print(f"CHECK_SKIP,{row},not measured this run (check --only "
                  f"selection)", file=sys.stderr)
            continue
        if row not in prev:
            print(f"CHECK_SKIP,{row},not in {prev_path}", file=sys.stderr)
            continue
        # latency rows regress upward, throughput rows regress downward
        ratio = (prev[row] / collected[row] if is_qps
                 else collected[row] / prev[row])
        unit = "qps" if is_qps else "us"
        status = "REGRESSED" if ratio > threshold else "ok"
        print(f"check_{row},{collected[row]:.1f},"
              f"baseline={prev[row]:.1f}{unit} ratio={ratio:.2f}x {status}")
        if ratio > threshold:
            regressed = True
    return regressed


if __name__ == "__main__":
    main()
