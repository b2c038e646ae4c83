"""The inter-chip wire counter (``planner.exchange_wire``): the bytes a mesh
plan's Exchanges move per call, carried on every ``plan.dispatch`` span.

On four CPU devices, q3, q5 and q18 of the four-chip TPC-H cell are served
through ``AnalyticsService`` at a small scale; the count on each request's
dispatch span must equal a hand count from the plan's Exchange capacities
and the tables' column widths, and the collectives of the traced program
must move exactly that much. The same cross-check runs under the other
placements and join strategies. A one-chip plan carries 0, and the
counter stays out of the plan-cache key and the jitted program."""
import functools
import json
import os

import pytest

from conftest import run_with_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 4

SCRIPT = r"""
import json, math, sys
sys.path.insert(0, {repo!r})
import jax
from bench import harness, tpch_data
from repro.analytics import physical as PH, planner, tpch, tracing
from repro.analytics.service import AnalyticsService
from repro.core.config import PlacementPolicy

def received(jaxpr, n):
    # bytes one device receives from the others through the program's
    # collectives: a tiled all-gather brings (n-1) foreign shards, an
    # all-to-all (n-1)/n of its buffer, a ring all-reduce of a table
    # 2 (n-1)/n of it, a reduce-scatter (n-1)/n; scalar psums (overflow
    # counts) are no Exchange and are left out
    total = 0
    for e in jaxpr.eqns:
        name = e.primitive.name
        a = e.invars[0].aval if e.invars else None
        size = (math.prod(a.shape) * a.dtype.itemsize
                if hasattr(a, "shape") else 0)
        if name == "all_gather":
            total += size * (n - 1)
        elif name == "all_to_all":
            total += size * (n - 1) // n
        elif name == "psum" and len(a.shape) >= 2:
            total += 2 * (n - 1) * size // n
        elif name == "reduce_scatter":
            total += (n - 1) * size // n
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(j, "eqns"):
                    total += received(j, n)
                elif hasattr(getattr(j, "jaxpr", None), "eqns"):
                    total += received(j.jaxpr, n)
    return total

cell = harness.load_cell("tpch_sf1_x4.join")
host = tpch_data.generate({scale!r}, 11)
tables = jax.block_until_ready(tpch.TPCHData(host, {scale!r}).as_jax())
ctx = harness.context(cell.config, jax.devices())
plans = {{q: p for (s, q), p in harness.build_plans(cell).items() if s == 0}}
out = {{"rows": {{t: len(next(iter(c.values()))) for t, c in host.items()}},
        "served": {{}}, "local": {{}}, "other": {{}}}}

svc = AnalyticsService(harness.service_config(cell.config)).start()
with tracing.tracing() as tr:
    rids = {{q: svc.submit(p, tables, context=ctx) for q, p in plans.items()}}
    for q, rid in rids.items():
        assert svc.result(rid, timeout=600).value is not None
    local = planner.ExecutionContext(executor="cost")
    lrids = {{q: svc.submit(p, tables, context=local)
              for q, p in plans.items()}}
    for q, rid in lrids.items():
        assert svc.result(rid, timeout=600).value is not None
    spans = tr.spans()
svc.close()

def dispatch_args(rid):
    return [dict(s.args) for s in spans
            if s.name == "plan.dispatch" and s.trace_id == rid]

for q, p in plans.items():
    cp = planner.compile_plan(p, tables, ctx)
    caps = {{e.key or "partials": e.capacity
             for e in PH.exchanges(cp.physical.root) if e.kind == "hash"}}
    args = dispatch_args(rids[q])
    out["served"][q] = {{
        "bytes": [a["exchange_bytes"] for a in args],
        "exchanges": [a["exchanges"] for a in args],
        "n_exchanges": len(PH.exchanges(cp.physical.root)),
        "caps": caps,
        "collectives": received(jax.make_jaxpr(cp.fn)(tables, {{}}).jaxpr, 4)}}
    out["local"][q] = [(a["exchange_bytes"], a["exchanges"])
                       for a in dispatch_args(lrids[q])]

# the cache: the same plan compiled twice is one entry and one executable
planner.clear_plan_cache()
a = planner.compile_plan(plans["q18"], tables, ctx)
b = planner.compile_plan(plans["q18"], tables, ctx)
out["cache"] = {{"entries": planner.plan_cache_size(), "same_fn": a.fn is b.fn,
                 "key_has_wire": any(isinstance(k, planner.ExchangeWire)
                                     for k in a.cache_key),
                 "wire": list(a.wire)}}

mesh = ctx.mesh
others = {{
    "interleave_partitioned_radix": planner.ExecutionContext(
        mesh=mesh, policy=PlacementPolicy.INTERLEAVE,
        dist_join="partitioned", exchange_impl="radix"),
    "interleave_partitioned_argsort": planner.ExecutionContext(
        mesh=mesh, policy=PlacementPolicy.INTERLEAVE,
        dist_join="partitioned", exchange_impl="argsort"),
    "preferred": planner.ExecutionContext(
        mesh=mesh, policy=PlacementPolicy.PREFERRED),
    "first_touch": planner.ExecutionContext(
        mesh=mesh, policy=PlacementPolicy.FIRST_TOUCH),
    "local_alloc": planner.ExecutionContext(
        mesh=mesh, policy=PlacementPolicy.LOCAL_ALLOC)}}
for name, c in others.items():
    for q, p in plans.items():
        cp = planner.compile_plan(p, tables, c)
        out["other"][name + "/" + q] = [
            cp.wire.bytes,
            received(jax.make_jaxpr(cp.fn)(tables, {{}}).jaxpr, 4)]
print("RESULT " + json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _result():
    out = run_with_devices(SCRIPT.format(repo=REPO, scale=0.002),
                           n_devices=N, timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


# bytes per row of each table's columns (the generator's int32 and float32
# columns) and of what travels beside them: the float32 mask or weights,
# an int32 routing key, a float32 stacked measure
LINEITEM, ORDERS, CUSTOMER, SUPPLIER, NATION = 9 * 4, 3 * 4, 3 * 4, \
    2 * 4, 2 * 4
W = KEY = F = 4


def _shard(rows):
    return -(-rows // N)


def _routed_merge(capacity, groups, c=2):
    """An owner or pushdown merge of ``c`` stacked columns (weights and
    one sum): the routed (key, columns) slots, then the all-gather of the
    merged (ceil(G/n), c) table."""
    return ((N - 1) * capacity * (KEY + c * F)
            + (N - 1) * _shard(groups) * c * F)


def _hand(q, rows, caps):
    r = {t: _shard(n) for t, n in rows.items()}
    if q == "q3":
        return ((N - 1) * r["customer"] * (CUSTOMER + W)    # broadcast
                + (N - 1) * r["orders"] * (ORDERS + W)      # broadcast
                + _routed_merge(caps["l_orderkey"], rows["orders"])
                + (N - 1) * 10 * (F + KEY))                 # top-10 slots
    if q == "q5":
        return ((N - 1) * r["nation"] * (NATION + W)
                + (N - 1) * r["customer"] * (CUSTOMER + W)
                # orders with the customer's nation taken
                + (N - 1) * r["orders"] * (ORDERS + 4 + W)
                + (N - 1) * r["supplier"] * (SUPPLIER + W)
                + _routed_merge(caps["partials"], 25))
    assert q == "q18"
    return (_routed_merge(caps["l_orderkey"], rows["orders"])
            + (N - 1) * r["customer"] * (CUSTOMER + W)
            + _routed_merge(caps["partials"], rows["customer"]))


@pytest.mark.parametrize("q", ["q3", "q5", "q18"])
def test_served_dispatch_carries_the_hand_counted_wire_bytes(q):
    res = _result()
    got = res["served"][q]
    hand = _hand(q, res["rows"], got["caps"])
    assert got["bytes"] and set(got["bytes"]) == {hand}
    assert set(got["exchanges"]) == {got["n_exchanges"]}
    # and the traced program's collectives move exactly that much
    assert got["collectives"] == hand


@pytest.mark.parametrize("q", ["q3", "q5", "q18"])
def test_one_chip_plan_carries_zero(q):
    local = _result()["local"][q]
    assert local and set(map(tuple, local)) == {(0, 0)}


def test_counter_is_outside_the_plan_cache_key():
    cache = _result()["cache"]
    assert cache["entries"] == 1 and cache["same_fn"]
    assert not cache["key_has_wire"] and cache["wire"][0] > 0


@pytest.mark.parametrize("placement", [
    "interleave_partitioned_radix", "interleave_partitioned_argsort",
    "preferred", "first_touch", "local_alloc"])
def test_count_matches_the_collectives_under_each_placement(placement):
    other = _result()["other"]
    for q in ("q3", "q5", "q18"):
        wire, moved = other[f"{placement}/{q}"]
        assert wire == moved and wire > 0, (q, wire, moved)
