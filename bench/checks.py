"""The comparison that decides ``correct``.

Every answer a run served, in its window or within a minute after it, is
compared with the plain reference of its (query, parameter set). Three
numbers come out of a run, each held to its limit in
``bench/limits/<workload>.json``:

- ``rel_err``: the widest relative gap |served - reference| / |reference|
  over every float output of every compared answer (a reference of 0 must
  be served as 0);
- ``exact_mismatches``: entries of the exact outputs (counts, row keys)
  that differ, plus answers whose shape differs or that report overflow;
- ``missing``: requests of the window that never got an answer (failed,
  expired, shed, or not back a minute after the window closed).

The control is the same reference computed in bfloat16
(``reference_answer(..., dt=bfloat16)``), put in the program's place.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Dict, Mapping, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
NAMES = ("rel_err", "exact_mismatches", "missing")


def reference_module(query: str):
    return importlib.import_module(f"bench.reference.{query}")


def reference_answer(query: str, tables, params: Mapping, dt=np.float64
                     ) -> Dict[str, np.ndarray]:
    return reference_module(query).answer(tables, dt, **params)


def compare(got: Mapping, ref: Mapping, exact: Tuple[str, ...]
            ) -> Tuple[float, int]:
    """(widest relative gap, exact mismatches) of one answer."""
    rel, mismatches = 0.0, 0
    if "_overflow" in got and np.any(np.asarray(got["_overflow"]) != 0):
        mismatches += 1
    for k, r in ref.items():
        g, r = np.asarray(got[k]), np.asarray(r)
        if g.shape != r.shape:
            mismatches += max(1, r.size)
        elif k in exact:
            mismatches += int(np.count_nonzero(g != r.astype(g.dtype)))
        else:
            g64, r64 = g.astype(np.float64), r.astype(np.float64)
            both_nan = np.isnan(g64) & np.isnan(r64)
            with np.errstate(divide="ignore", invalid="ignore"):
                gap = np.abs(g64 - r64) / np.maximum(np.abs(r64), 1e-30)
            gap = np.where(both_nan, 0.0, gap)
            if gap.size:
                worst = float(np.max(np.where(np.isnan(gap), np.inf, gap)))
                rel = max(rel, worst)
    return rel, mismatches


def limits(workload: str) -> Dict[str, float]:
    with open(BENCH / "limits" / f"{workload}.json") as f:
        spec = json.load(f)
    return {k: float(spec[k]) for k in NAMES}


def judge(readings: Mapping[str, float], lim: Mapping[str, float]) -> bool:
    return all(readings[k] <= lim[k] for k in NAMES)
