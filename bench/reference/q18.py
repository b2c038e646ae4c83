"""TPC-H Q18, large volume customer: orders whose lineitem quantity sums
above ``qty_threshold``, their quantity summed per customer."""
from __future__ import annotations

import numpy as np

from bench.reference import finish, pk_lookup, sums

EXACT = ("_count",)
KEYS = ()
READS = {"lineitem": ("l_orderkey", "l_quantity"),
         "orders": ("o_orderkey", "o_custkey"),
         "customer": ("c_custkey",)}


def answer(t, dt=np.float64, *, qty_threshold):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    n_ord, n_cust = len(o["o_orderkey"]), len(c["c_custkey"])
    per_order = finish({"q": sums(li["l_orderkey"],
                                  li["l_quantity"].astype(dt), n_ord)},
                       dt)["q"]
    o_qty = per_order[np.clip(o["o_orderkey"], 0, n_ord - 1)]
    _, cfound = pk_lookup(c["c_custkey"], o["o_custkey"])
    m = (o_qty > qty_threshold) & cfound
    g = np.clip(o["o_custkey"][m], 0, n_cust - 1)
    return finish({"qty": sums(g, o_qty[m], n_cust),
                   "_count": np.bincount(g, minlength=n_cust)}, dt, KEYS)
