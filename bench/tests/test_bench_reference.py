"""At a tiny scale on the CPU: the answer the service serves for each query
and parameter set of every traffic mix passes the benchmark's comparison
with its numpy reference, and the same answers computed in bfloat16 (the
control) fail the cell's comparison; the data follows TPC-H's rules."""
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, harness, tpch_data  # noqa: E402

SCALE, SEED = 0.01, 2**31 + 17
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# (cell, stream, query) for the first cell of each traffic mix
CASES = []
for mix in sorted({w["traffic"] for w in SPEC["workloads"]}):
    cell = next(w["name"] for w in SPEC["workloads"] if w["traffic"] == mix)
    CASES += [(cell, s, q) for s, q in harness.load_cell(cell).plan_keys()]


@pytest.fixture(scope="module")
def served():
    """{(cell, stream, query): (served answer, host tables)}."""
    import jax
    from repro.analytics import tpch
    from repro.analytics.planner import ExecutionContext
    from repro.analytics.service import AnalyticsService, ServiceConfig

    host = tpch_data.generate(SCALE, SEED)
    tables = tpch.TPCHData(host, SCALE).as_jax()
    out = {}
    with AnalyticsService(ServiceConfig()) as service:
        service.start()
        rids = {}
        for cell, s, q in CASES:
            plan = harness.build_plans(harness.load_cell(cell))[s, q]
            rids[cell, s, q] = service.submit(plan, tables,
                                              context=ExecutionContext())
        for key, rid in rids.items():
            res = service.result(rid, timeout=300)
            out[key] = {k: np.asarray(v) for k, v in res.value.items()}
    jax.block_until_ready(tables)
    return out, host


def control_readings(host, cell):
    """(widest relative gap, exact mismatches) of the bfloat16 control over
    every query and parameter set of ``cell``: the control has to fail the
    cell's comparison, not each query's (at this scale no order passes
    q18's TPC-H threshold, so its answer holds nothing to round)."""
    rel, mismatches = 0.0, 0
    for c, s, q in CASES:
        if c == cell:
            params = harness.load_cell(c).streams[s][q]
            e, m = checks.compare(
                checks.reference_answer(q, host, params, ml_dtypes.bfloat16),
                checks.reference_answer(q, host, params),
                checks.reference_module(q).EXACT)
            rel, mismatches = max(rel, e), mismatches + m
    return rel, mismatches


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"{c[0]}-s{c[1]}-{c[2]}")
def test_served_answer_matches_reference_and_bf16_control_fails(served, case):
    answers, host = served
    cell, s, q = case
    params = harness.load_cell(cell).streams[s][q]
    lim = checks.limits(cell)
    exact = checks.reference_module(q).EXACT
    ref = checks.reference_answer(q, host, params)
    rel, mismatches = checks.compare(answers[case], ref, exact)
    assert rel <= lim["rel_err"] and mismatches == 0, (rel, mismatches)
    c_rel, c_mismatches = control_readings(host, cell)
    assert c_rel > lim["rel_err"] or c_mismatches > 0, (c_rel, c_mismatches)


def test_generator_is_seeded_and_sized():
    a, b = (tpch_data.generate(SCALE, SEED) for _ in range(2))
    c = tpch_data.generate(SCALE, SEED + 1)
    rows = tpch_data.sizes(SCALE)
    for t, cols in a.items():
        for name, arr in cols.items():
            assert len(arr) == rows[t] and arr.itemsize == 4, (t, name)
            assert np.array_equal(arr, b[t][name])
            assert len(c[t][name]) == rows[t]
    assert not np.array_equal(a["lineitem"]["l_orderkey"],
                              c["lineitem"]["l_orderkey"])


def test_generator_follows_tpch_rules():
    """TPC-H v3.0.1 Clause 4.2.3, as the module docstring lists it."""
    t = tpch_data.generate(SCALE, SEED)
    li, o, c = t["lineitem"], t["orders"], t["customer"]
    lines = np.bincount(li["l_orderkey"], minlength=len(o["o_orderkey"]))
    assert lines.min() == 1 and lines.max() == 7 and lines.sum() == len(
        li["l_orderkey"]) == 4 * len(o["o_orderkey"])
    assert ((o["o_custkey"] + 1) % 3 != 0).all()
    assert o["o_custkey"].max() < len(c["c_custkey"])
    assert o["o_orderdate"].min() >= 0 and (
        o["o_orderdate"].max() <= tpch_data.ORDERDATE_HI)
    lag = li["l_shipdate"] - o["o_orderdate"][li["l_orderkey"]]
    assert lag.min() >= 1 and lag.max() <= 121
    assert np.array_equal(np.bincount(t["nation"]["n_regionkey"]),
                          [5] * 5)
    shipped = li["l_shipdate"] > tpch_data.CURRENTDATE
    assert np.array_equal(li["l_linestatus"] == tpch_data.LINESTATUS["O"],
                          shipped)
    n_flag = li["l_returnflag"] == tpch_data.RETURNFLAG["N"]
    assert n_flag[shipped].all()        # receipt after ship, after today
    # q1's four groups: (A, F), (N, F), (N, O), (R, F)
    groups = np.unique(li["l_returnflag"] * 2 + li["l_linestatus"])
    assert set(groups) == {0, 2, 3, 4}
    assert li["l_quantity"].min() >= 1 and li["l_quantity"].max() <= 50
    assert set(np.unique(li["l_discount"])) <= set(
        (np.arange(11) / 100).astype(np.float32))
    cents = tpch_data.retail_cents(np.array([1, 1000, 200000], np.int32))
    assert list(cents) == [90100, 90100, 110000]
    price = li["l_extendedprice"] / li["l_quantity"]
    assert price.min() >= 900 and price.max() <= 2099
    assert li["l_suppkey"].min() >= 0 and (
        li["l_suppkey"].max() < len(t["supplier"]["s_suppkey"]))
