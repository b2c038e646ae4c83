#!/usr/bin/env python3
"""Run one benchmark cell on this machine's TPU chips.

    python3 bench/run.py --workload tpch_sf1.join --seed 7 --seconds 30 --trace 0

Prints progress on standard error and, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each number
the correctness check compared, with its limit. The same numbers are the
last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU, a device kind
missing from ``bench/peaks.json``, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devices, device_peaks = harness.accelerator(cell.chips)
    except harness.DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    harness.log(f"{cell.name} seed {args.seed} on {len(devices)} x "
                f"{devices[0].device_kind} at {time.perf_counter() - T0:.3f} s")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, device_peaks, T0)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the benchmark is imported as the package ``bench``, the program from
    # src/; neither from this script's own directory
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
