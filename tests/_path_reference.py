"""The reference-parity matrix over the planner's forced paths.

Each case is one (forced context, stream, query) of a benchmark cell's
traffic: the ``ExecutionContext`` of a cell's configuration with some
choices the cost model would make forced through its fields (``executor``,
``join``, ``policy``, ``dist_join``, ``dist_topk``, ``agg_pushdown``).
A group of cases is one test file. One subprocess per group, with the
group's number of CPU devices, serves every case of the group through
``AnalyticsService`` on data from ``bench/tpch_data.py`` and compares each
answer with ``bench/reference/`` (``bench.checks.compare``); the
parametrised cases assert on its cached result, within the limits in
``bench/limits/<cell>.json`` of the cell whose plans they serve.

No two cases of one (query, stream) lower to the same physical plan
(``planner.explain_physical``), and none lowers to a plan that
``tests/test_mesh_reference.py`` already serves (``COVERED``): a forcing
that changes nothing for a query is not a case. The cases are listed, not
derived, so that a change that makes two forced paths lower alike fails
``check_distinct`` and names them.
"""
from __future__ import annotations

import functools
import hashlib
import json
import math
import os
import sys
from dataclasses import dataclass
from typing import Any, Dict, Mapping, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(REPO, "tests")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SCALE, SEED = 0.005, 2**31 + 17
JOIN = ("q3", "q5", "q18")
POLICIES = ("FIRST_TOUCH", "INTERLEAVE", "LOCAL_ALLOC", "PREFERRED")

Case = Tuple[str, int, str]          # (context name, stream, query)


@dataclass(frozen=True)
class Group:
    """``plans``: the cell whose traffic gives the plans and whose limits
    hold; ``context``: the cell whose configuration gives the base context
    (on ``devices`` CPU devices); ``contexts``: the forced fields of each
    context, by name; ``controls``: (context, stream, query) served only so
    that a case's bits can be compared with them."""
    plans: str
    context: str
    devices: int
    contexts: Mapping[str, Mapping[str, Any]]
    cases: Tuple[Case, ...]
    controls: Tuple[Case, ...] = ()


def _cases(contexts, streams, queries) -> Tuple[Case, ...]:
    return tuple((c, s, q) for c in contexts for s in streams
                 for q in queries)


# the queries whose plan INTERLEAVE's forced push-down changes, by the
# distributed Join lowering and the forced value
PUSHED = {"broadcast": {True: ("q3", "q18"), False: ("q5", "q18")},
          "partitioned": {True: ("q18",), False: ("q5",)}}


def _mesh_join(policies, dists) -> Group:
    """The four-chip cell's join plans with the distributed Join lowering
    forced, under ``policies`` x ``dists``: the base context (the cost
    model's TopK and push-down), TopK forced to "replicated" on q3 (the
    cost model picks "candidates" there), and under INTERLEAVE the
    push-down forced each way where it changes the plan.
    INTERLEAVE/broadcast lowers as the cell does, which
    ``test_mesh_reference.py`` serves, so its base is a control only."""
    contexts, cases, controls = {}, (), ()
    for p in policies:
        for d in dists:
            base = f"{p}/{d}"
            contexts[base] = {"policy": p, "dist_join": d}
            contexts[f"{base}/replicated"] = dict(contexts[base],
                                                  dist_topk="replicated")
            if (p, d) == ("INTERLEAVE", "broadcast"):
                controls += _cases([base], (0, 1), ("q3",))
            else:
                cases += _cases([base], (0, 1), JOIN)
            cases += _cases([f"{base}/replicated"], (0, 1), ("q3",))
            if p != "INTERLEAVE":
                continue
            for v, queries in PUSHED[d].items():
                contexts[f"{base}/pushdown={v}"] = dict(contexts[base],
                                                        agg_pushdown=v)
                cases += _cases([f"{base}/pushdown={v}"], (0, 1), queries)
    return Group("tpch_sf1_x4.join", "tpch_sf1_x4.join", 4, contexts,
                 cases, controls)


GROUPS: Dict[str, Group] = {
    # one chip, the join cell's plans: the Aggregate executor x the join
    # strategy; executor "cost" lowers as "xla" does at this scale
    "local_join": Group(
        "tpch_sf1.join", "tpch_sf1.join", 1,
        {f"{e}/{j}": {"executor": e, "join": j}
         for e in ("xla", "kernel") for j in ("sorted", "kernel")},
        _cases([f"{e}/{j}" for e in ("xla", "kernel")
                for j in ("sorted", "kernel")], (0, 1), JOIN)),
    # one chip, the scan cell's plans: q1 under each Aggregate executor
    # ("cost" picks "kernel"); q6's global sum lowers alike under all three
    "local_scan": Group(
        "tpch_sf10.scan", "tpch_sf10.scan", 1,
        {"xla": {"executor": "xla"}, "kernel": {"executor": "kernel"}},
        _cases(["xla", "kernel"], (0, 1, 2), ("q1",))
        + _cases(["xla"], (0, 1, 2), ("q6",))),
    # four chips, the join cell's plans: the cell's policy under both Join
    # lowerings, and the other three under each
    "mesh_interleave": _mesh_join(["INTERLEAVE"],
                                  ["broadcast", "partitioned"]),
    "mesh_broadcast": _mesh_join(["FIRST_TOUCH", "LOCAL_ALLOC", "PREFERRED"],
                                 ["broadcast"]),
    "mesh_partitioned": _mesh_join(["FIRST_TOUCH", "LOCAL_ALLOC",
                                    "PREFERRED"], ["partitioned"]),
    # four chips, the scan cell's plans under each placement policy; q6's
    # global sum merges alike under all four
    "mesh_scan": Group(
        "tpch_sf10.scan", "tpch_sf1_x4.join", 4,
        {p: {"policy": p} for p in POLICIES},
        _cases(POLICIES, (0, 1, 2), ("q1",))
        + _cases(["INTERLEAVE"], (0, 1, 2), ("q6",))),
}

# what tests/test_mesh_reference.py serves: the four-chip cell's context
# under each forced Exchange layout
COVERED = Group(
    "tpch_sf1_x4.join", "tpch_sf1_x4.join", 4,
    {f"exchange_impl={i}": {"exchange_impl": i} for i in ("radix", "argsort")},
    _cases([f"exchange_impl={i}" for i in ("radix", "argsort")], (0, 1),
           JOIN))


def case_id(case: Case) -> str:
    c, s, q = case
    return f"{c}-{s}-{q}"


def _key(case: Case) -> str:
    return "|".join(map(str, case))


def _context_spec(group: Group, name: str) -> Dict[str, Any]:
    from bench import harness
    spec = dict(harness.load_cell(group.context).config["context"])
    spec.update(group.contexts[name])
    return spec


def context(group: Group, name: str, devices):
    """Context ``name`` of ``group`` on ``devices`` (a mesh if the base
    configuration names one)."""
    from bench import harness
    return harness.context({"context": _context_spec(group, name)}, devices)


# ---------------------------------------------------------------------------
# the plans each case lowers to, without devices
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _host():
    from bench import tpch_data
    return tpch_data.generate(SCALE, SEED)


def _physical(group: Group, case: Case) -> str:
    from bench import harness
    from repro.analytics import planner
    from repro.analytics.planner import ExecutionContext
    name, s, q = case
    spec = _context_spec(group, name)
    mesh = spec.pop("mesh", None)
    ctx = harness.from_json(ExecutionContext, spec)
    plan = harness.build_plans(harness.load_cell(group.plans))[s, q]
    return planner.explain_physical(
        plan, _host(), ctx,
        n_shards=None if mesh is None else math.prod(mesh["shape"]))


@functools.lru_cache(maxsize=None)
def physical_plans() -> Dict[Tuple[str, Case], str]:
    """{(group, case): explain_physical text} of every case of the matrix
    and of ``COVERED``."""
    return {(g, case): _physical(group, case)
            for g, group in dict(GROUPS, covered=COVERED).items()
            for case in group.cases}


def _traffic(group: str) -> str:
    return dict(GROUPS, covered=COVERED)[group].plans.split(".")[1]


def check_distinct(name: str) -> None:
    """No case of group ``name`` lowers to the plan of another case of the
    same (traffic, stream, query), in the matrix or in ``COVERED``."""
    plans = physical_plans()
    for case in GROUPS[name].cases:
        mine = plans[name, case]
        twins = [(g, other) for (g, other), text in plans.items()
                 if text == mine and (g, other) != (name, case)
                 and _traffic(g) == _traffic(name)
                 and other[1:] == case[1:]]
        assert not twins, (case, twins)


# ---------------------------------------------------------------------------
# serving, in a subprocess with the group's devices
# ---------------------------------------------------------------------------
def serve(name: str) -> None:
    """Serve every case and control of group ``name``; print one RESULT
    line of {case key: readings}."""
    import jax
    import numpy as np
    from bench import checks, harness, tpch_data
    from repro.analytics import physical as PH
    from repro.analytics import planner, tpch
    from repro.analytics.service import AnalyticsService

    group = GROUPS[name]
    cell = harness.load_cell(group.plans)
    host = tpch_data.generate(SCALE, SEED)
    tables = jax.block_until_ready(tpch.TPCHData(host, SCALE).as_jax())
    plans = harness.build_plans(cell)
    contexts = {c: context(group, c, jax.devices()) for c in group.contexts}
    svc = AnalyticsService(harness.service_config(
        harness.load_cell(group.context).config)).start()
    rids = {case: svc.submit(plans[case[1:]], tables,
                             context=contexts[case[0]], client_id=case[1])
            for case in group.cases + group.controls}
    out = {}
    for (c, s, q), rid in rids.items():
        res = svc.result(rid, timeout=600)
        got = {k: np.asarray(v) for k, v in (res.value or {}).items()}
        ref = checks.reference_answer(q, host, cell.streams[s][q])
        rel, mismatches = (checks.compare(got, ref,
                                          checks.reference_module(q).EXACT)
                           if res.value is not None else (None, None))
        phys = planner.compile_plan(plans[s, q], tables,
                                    contexts[c]).physical
        out[_key((c, s, q))] = {
            "answered": res.value is not None, "error": res.error,
            "rel_err": rel, "exact_mismatches": mismatches,
            "physical": PH.describe(phys),
            "digest": hashlib.sha256(b"".join(
                got[k].tobytes() for k in sorted(got))).hexdigest()}
    svc.close()
    print("RESULT " + json.dumps(out))


@functools.lru_cache(maxsize=None)
def served(name: str) -> Dict[str, Dict[str, Any]]:
    from conftest import run_with_devices
    code = (f"import sys; sys.path[:0] = [{TESTS!r}, {REPO!r}]\n"
            f"import _path_reference\n_path_reference.serve({name!r})\n")
    out = run_with_devices(code, n_devices=GROUPS[name].devices,
                           timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def check_case(name: str, case: Case) -> None:
    """Case ``case`` of group ``name`` was answered within its cell's
    limits, by the plan ``check_distinct`` holds apart from the others."""
    from bench import checks
    got = served(name)[_key(case)]
    assert got["answered"], got["error"]
    lim = checks.limits(GROUPS[name].plans)
    assert got["rel_err"] <= lim["rel_err"], got["rel_err"]
    assert got["exact_mismatches"] <= lim["exact_mismatches"]
    assert got["physical"] == physical_plans()[name, case]


def topk_bases(name: str) -> Tuple[str, ...]:
    """The contexts of group ``name`` that have a replicated-TopK twin."""
    contexts = GROUPS[name].contexts
    return tuple(c for c in contexts if f"{c}/replicated" in contexts)


def check_topk_bits(name: str, base: str) -> None:
    """q3 under context ``base``, whose TopK the cost model lowers to
    ``candidates``, gives the bits of ``base`` with TopK forced to
    ``replicated`` (the two lowerings are documented bit-identical)."""
    got = served(name)
    for s in (0, 1):
        cand = got[_key((base, s, "q3"))]
        rep = got[_key((f"{base}/replicated", s, "q3"))]
        assert "dist=candidates" in cand["physical"]
        assert "dist=replicated" in rep["physical"]
        assert cand["digest"] == rep["digest"]
