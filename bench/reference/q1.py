"""TPC-H Q1, pricing summary: lineitem shipped by ``cutoff``, grouped by
(returnflag, linestatus) as group ``returnflag * 2 + linestatus``."""
from __future__ import annotations

import numpy as np

from bench.reference import finish, sums

EXACT = ("count_order", "_count")
KEYS = ()
READS = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                      "l_quantity", "l_extendedprice", "l_discount",
                      "l_tax")}


def answer(t, dt=np.float64, *, cutoff):
    li = t["lineitem"]
    m = li["l_shipdate"] <= cutoff
    g = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
    qty, price, disc, tax = (li[c][m].astype(dt) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = np.asarray(1, dt)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    cnt = np.bincount(g, minlength=6)
    total = {k: sums(g, v, 6) for k, v in (
        ("qty", qty), ("price", price), ("disc_price", disc_price),
        ("charge", charge))}
    return finish({
        "sum_qty": total["qty"], "sum_base_price": total["price"],
        "sum_disc_price": total["disc_price"], "sum_charge": total["charge"],
        "avg_qty": total["qty"] / np.maximum(cnt, 1),
        "avg_price": total["price"] / np.maximum(cnt, 1),
        "count_order": cnt, "_count": cnt}, dt, KEYS)
