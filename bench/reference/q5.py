"""TPC-H Q5, local supplier volume: revenue per supplier nation from
lineitems whose order (dated in [date_lo, date_hi)) comes from a customer of
``region`` in the supplier's own nation."""
from __future__ import annotations

import numpy as np

from bench.reference import finish, pk_lookup, sums

N_NATION = 25
EXACT = ("_count",)
KEYS = ()
READS = {"nation": ("n_nationkey", "n_regionkey"),
         "customer": ("c_custkey", "c_nationkey"),
         "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
         "supplier": ("s_suppkey", "s_nationkey"),
         "lineitem": ("l_orderkey", "l_suppkey", "l_extendedprice",
                      "l_discount")}


def answer(t, dt=np.float64, *, region, date_lo, date_hi):
    n, c, o = t["nation"], t["customer"], t["orders"]
    li, s = t["lineitem"], t["supplier"]
    nrow, nfound = pk_lookup(n["n_nationkey"], c["c_nationkey"])
    c_ok = nfound & (n["n_regionkey"][nrow] == region)
    crow, cfound = pk_lookup(c["c_custkey"], o["o_custkey"])
    o_ok = ((o["o_orderdate"] >= date_lo) & (o["o_orderdate"] < date_hi)
            & cfound & c_ok[crow])
    orow, ofound = pk_lookup(o["o_orderkey"], li["l_orderkey"])
    srow, sfound = pk_lookup(s["s_suppkey"], li["l_suppkey"])
    s_nat = s["s_nationkey"][srow]
    m = (ofound & o_ok[orow] & sfound
         & (s_nat == c["c_nationkey"][crow][orow]))
    one = np.asarray(1, dt)
    rev = (li["l_extendedprice"][m].astype(dt)
           * (one - li["l_discount"][m].astype(dt)))
    return finish({"revenue": sums(s_nat[m], rev, N_NATION),
                   "_count": np.bincount(s_nat[m], minlength=N_NATION)},
                  dt, KEYS)
