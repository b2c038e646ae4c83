"""Plain numpy answers to the benchmark's queries, one module per query.

Each module ``<query>.py`` defines

- ``answer(tables, dt=np.float64, **params)``: the query's answer over the
  host tables, with the same parameter names as the engine's
  ``tpch.build_<query>``. Float columns and float arithmetic are taken in
  ``dt``; sums accumulate in float64 and every float output is rounded to
  ``dt``. With ``dt=np.float64`` this is the reference; with
  ``ml_dtypes.bfloat16`` it is the lower-precision control.
- ``EXACT``: output keys compared exactly (counts and keys).
- ``KEYS``: output keys that are row keys (never rounded).
- ``READS``: {table: columns} the query has to read.

Nothing here imports the engine.
"""
from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


def pk_lookup(pk: np.ndarray, fk: np.ndarray):
    """Row of ``pk`` holding each ``fk`` value, and whether it exists."""
    order = np.argsort(pk, kind="stable")
    sk = pk[order]
    pos = np.clip(np.searchsorted(sk, fk), 0, len(sk) - 1)
    return order[pos], sk[pos] == fk


def sums(groups: np.ndarray, values: np.ndarray, n: int) -> np.ndarray:
    """Per-group float64 sums of ``values`` (taken in whatever dtype)."""
    return np.bincount(groups, weights=np.asarray(values, np.float64),
                       minlength=n)


def finish(out: Dict[str, np.ndarray], dt, keys: Iterable[str] = ()
           ) -> Dict[str, np.ndarray]:
    """Round every output but the row keys to ``dt`` (float64 values)."""
    if np.dtype(dt) == np.float64:
        return out
    return {k: (v if k in keys else
                np.asarray(v, np.float64).astype(dt).astype(np.float64))
            for k, v in out.items()}
