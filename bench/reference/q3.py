"""TPC-H Q3, shipping priority: customers of ``segment`` joined to their
orders before ``date`` and those orders' lineitems shipped after it; the ten
orders of highest revenue, ties to the lower order key."""
from __future__ import annotations

import numpy as np

from bench.reference import finish, pk_lookup, sums

EXACT = ("o_orderkey",)
KEYS = ("o_orderkey",)
READS = {"customer": ("c_custkey", "c_mktsegment"),
         "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
         "lineitem": ("l_orderkey", "l_shipdate", "l_extendedprice",
                      "l_discount")}


def answer(t, dt=np.float64, *, segment, date):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    crow, cfound = pk_lookup(c["c_custkey"], o["o_custkey"])
    o_ok = ((o["o_orderdate"] < date) & cfound
            & (c["c_mktsegment"][crow] == segment))
    orow, ofound = pk_lookup(o["o_orderkey"], li["l_orderkey"])
    m = (li["l_shipdate"] > date) & ofound & o_ok[orow]
    n_ord = len(o["o_orderkey"])
    one = np.asarray(1, dt)
    rev = (li["l_extendedprice"][m].astype(dt)
           * (one - li["l_discount"][m].astype(dt)))
    per_order = finish({"r": sums(li["l_orderkey"][m], rev, n_ord)}, dt)["r"]
    top = np.argsort(-per_order, kind="stable")[:10]
    return {"revenue": per_order[top], "o_orderkey": top}
