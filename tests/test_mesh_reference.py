"""The four-chip mesh path against the plain reference: on four CPU devices
with the ``tpch_sf1_x4`` configuration's context (INTERLEAVE over a (4,)
``data`` mesh), q3, q5 and q18 under both streams' parameter sets of the
``join`` mix are served through ``AnalyticsService`` on data from
``bench/tpch_data.py`` and held to ``bench/reference/`` within the
``tpch_sf1_x4.join`` cell's limits, under each hash Exchange layout
(``radix``, the cost model's choice in the cell, and ``argsort``) forced
through ``ExecutionContext.exchange_impl``; the two must give the same
bits."""
import functools
import json
import os
import sys

import pytest

from conftest import run_with_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import checks  # noqa: E402

CELL = "tpch_sf1_x4.join"
SCALE = 0.005

SCRIPT = r"""
import dataclasses, hashlib, json, sys
sys.path.insert(0, {repo!r})
import jax
import numpy as np
from bench import checks, harness, tpch_data
from repro.analytics import physical as PH, planner, tpch
from repro.analytics.service import AnalyticsService

cell = harness.load_cell({cell!r})
host = tpch_data.generate({scale!r}, 2**31 + 17)
tables = jax.block_until_ready(tpch.TPCHData(host, {scale!r}).as_jax())
ctx = dataclasses.replace(harness.context(cell.config, jax.devices()),
                          exchange_impl={impl!r})
plans = harness.build_plans(cell)
svc = AnalyticsService(harness.service_config(cell.config)).start()
rids = {{k: svc.submit(p, tables, context=ctx, client_id=k[0])
         for k, p in plans.items()}}
out = {{}}
for (s, q), rid in rids.items():
    res = svc.result(rid, timeout=600)
    got = {{k: np.asarray(v) for k, v in (res.value or {{}}).items()}}
    ref = checks.reference_answer(q, host, cell.streams[s][q])
    rel, mismatches = (checks.compare(got, ref,
                                      checks.reference_module(q).EXACT)
                       if res.value is not None else (None, None))
    phys = planner.compile_plan(plans[s, q], tables, ctx).physical
    out[f"{{s}}/{{q}}"] = {{
        "answered": res.value is not None, "error": res.error,
        "rel_err": rel, "exact_mismatches": mismatches,
        "impls": sorted({{e.impl for e in PH.exchanges(phys.root)
                          if e.kind == "hash" and e.key is not None}}),
        "digest": hashlib.sha256(b"".join(
            got[k].tobytes() for k in sorted(got))).hexdigest()}}
svc.close()
print("RESULT " + json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _served(impl):
    out = run_with_devices(SCRIPT.format(repo=REPO, cell=CELL, scale=SCALE,
                                         impl=impl),
                           n_devices=4, timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("impl", ["radix", "argsort"])
@pytest.mark.parametrize("stream", [0, 1])
@pytest.mark.parametrize("query", ["q3", "q5", "q18"])
def test_mesh_answer_meets_the_cell_limits(impl, stream, query):
    got = _served(impl)[f"{stream}/{query}"]
    assert got["answered"], got["error"]
    lim = checks.limits(CELL)
    assert got["rel_err"] <= lim["rel_err"]
    assert got["exact_mismatches"] <= lim["exact_mismatches"]
    # every key-routing Exchange the plan holds takes the forced layout
    assert set(got["impls"]) <= {impl}


def test_exchange_layouts_give_the_same_bits():
    radix, argsort = _served("radix"), _served("argsort")
    assert {k: v["digest"] for k, v in radix.items()} == \
        {k: v["digest"] for k, v in argsort.items()}
