"""TPC-H Q6, forecast revenue change: sum of price * discount over lineitems
shipped in [date_lo, date_hi) with discount within 0.01 of ``disc`` and
quantity below ``qty``."""
from __future__ import annotations

import numpy as np

from bench.reference import finish

EXACT = ()
KEYS = ()
READS = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice")}


def answer(t, dt=np.float64, *, date_lo, date_hi, disc, qty):
    li = t["lineitem"]
    d = li["l_discount"].astype(dt)
    # the predicate as the query states it, on float32 columns (0.011 keeps
    # the float32 discount steps of 0.01 on the inside)
    m = ((li["l_shipdate"] >= date_lo) & (li["l_shipdate"] < date_hi)
         & (np.abs(d - np.asarray(disc, dt)) <= np.asarray(0.011, dt))
         & (li["l_quantity"] < qty))
    rev = (li["l_extendedprice"][m].astype(dt) * d[m]).astype(np.float64)
    return finish({"revenue": np.array([rev.sum()])}, dt, KEYS)
