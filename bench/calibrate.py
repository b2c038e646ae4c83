#!/usr/bin/env python3
"""Readings that the limits of ``bench/limits/<workload>.json`` are set from.

    python3 bench/calibrate.py --workload tpch_sf1.join --seeds 12 \\
        --control-seeds 3 --seconds 8

In one process, on the cell's own chips and sizes: for each of ``--seeds``
seeds, new data, one window of ``--seconds`` through the served path and the
checks' readings of every answer (the program's readings, whose largest is
the lower reading of each limit); then, for ``--control-seeds`` of them, the
same readings of the control, the reference computed in bfloat16 and put in
the program's place, at the same queries and parameter sets (the smallest is
the upper reading). Prints one JSON line per reading and a summary line.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def control_readings(cell, host) -> dict:
    """The bfloat16 control's readings over every (stream, query) answer."""
    import ml_dtypes
    from bench import checks, harness
    keys = cell.plan_keys()
    refs = harness.reference_answers(cell, host, keys)
    lows = harness.reference_answers(cell, host, keys, ml_dtypes.bfloat16)
    rel, mismatches = 0.0, 0
    for k in keys:
        e, m = checks.compare(lows[k], refs[k],
                              checks.reference_module(k[1]).EXACT)
        rel, mismatches = max(rel, e), mismatches + m
    return {"rel_err": rel, "exact_mismatches": float(mismatches),
            "missing": 0.0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--first-seed", type=int, default=1_000_003,
                    help="seeds are this, this + 7919, ...")
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devices, _ = harness.accelerator(cell.chips)
    except harness.DeviceError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()

    program, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        # a new session per seed, as each benchmark run has: the service
        # and the last seed's tables are let go before the next load
        session = harness.Session(cell, devices, T0)
        try:
            session.load(seed)
            session.start()
            session.warm()
            win = session.window(args.seconds)
            harness.answers_on_host(win)
            host = session.host
        finally:
            session.close()
        read = harness.check(cell, host, win)
        line = {"side": "program", "seed": seed, **read,
                "answers": sum(r.value is not None for r in win.requests)}
        print(json.dumps(line), flush=True)
        program.append(read)
        if i < args.control_seeds:
            t = time.perf_counter()
            read = control_readings(cell, host)
            print(json.dumps({"side": "control", "seed": seed, **read,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
            control.append(read)
    summary = {"workload": cell.name, "seeds": len(program),
               "lower": {k: max(r[k] for r in program) for k in program[0]},
               "upper": {k: min(r[k] for r in control) for k in control[0]}
               if control else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
