"""The benchmark's TPC-H data, drawn from ``--seed``.

The engine's schema (``repro.analytics.tpch``): five tables, dates as day
numbers from 1992-01-01, strings dictionary-encoded as int32, 0-based dense
keys, nine int32/float32 lineitem columns. The values follow the
generation rules of the TPC-H specification v3.0.1, Clause 4.2.3:

- NATION is the specification's fixed table: five nations to a region;
- O_CUSTKEY is uniform over the customers whose 1-based key is not a
  multiple of three (a third of the customers place no order);
- O_ORDERDATE is uniform in [STARTDATE, ENDDATE - 151 days];
- each order has 1 to 7 lineitems; L_SHIPDATE is O_ORDERDATE + [1, 121]
  days, L_RECEIPTDATE L_SHIPDATE + [1, 30] (drawn, not stored);
- L_RETURNFLAG is R or A at random where L_RECEIPTDATE <= CURRENTDATE
  (1995-06-17), else N; L_LINESTATUS is O where L_SHIPDATE > CURRENTDATE,
  else F;
- L_EXTENDEDPRICE is L_QUANTITY times the P_RETAILPRICE of a uniform
  part, and L_SUPPKEY one of that part's four suppliers
  (PS_SUPPKEY's formula);
- L_QUANTITY in [1, 50], L_DISCOUNT in [0.00, 0.10], L_TAX in
  [0.00, 0.08], C_MKTSEGMENT, C_NATIONKEY and S_NATIONKEY uniform.

Two choices keep every seed's work the same size, so that one compiled plan
serves every seed: the orders' lineitem counts are a fixed multiset, each
of 1..7 equally often (4 on average, as in the specification), dealt to the
orders in an order drawn from the seed; and lineitem has exactly four rows
per order (the engine's 6,000,000 per scale factor). Lineitem rows come in
an order drawn from the seed: the specification fixes no load order.

The copy lives with the benchmark so that no change to the program can
change the data it is measured on. It draws with numpy's ``Generator``, one
child stream per column, in threads; the same seed gives the same tables.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

N_NATION, N_REGION, N_SEGMENTS = 25, 5, 5
DATE0, DATE1 = 0, 2557          # 1992-01-01 and the day after 1998-12-31
CURRENTDATE = 1263              # 1995-06-17
ORDERDATE_HI = 2556 - 151       # ENDDATE - 151 days, the last order date
# the specification's NATION table: each nation's region, in key order
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], np.int32)
# dictionary codes, in the strings' sort order
RETURNFLAG = {"A": 0, "N": 1, "R": 2}
LINESTATUS = {"F": 0, "O": 1}
# l_discount and l_tax as the engine's generator rounds them: the nearest
# float32 of k / 100
_HUNDREDTHS = (np.arange(11) / 100).astype(np.float32)

Tables = Dict[str, Dict[str, np.ndarray]]


def sizes(scale: float) -> Dict[str, int]:
    """Rows of each table at ``scale`` (the engine's floors included)."""
    orders = max(250, int(1_500_000 * scale))
    return {"lineitem": 4 * orders,
            "orders": orders,
            "customer": max(64, int(150_000 * scale)),
            "supplier": max(16, int(10_000 * scale)),
            "nation": N_NATION}


def parts(scale: float) -> int:
    """Size of the part key domain lineitems draw from (no part table)."""
    return max(200, int(200_000 * scale))


def lines_per_order(n_orders: int) -> np.ndarray:
    """The fixed multiset of lineitem counts: 1..7 equally often, the
    remainder 4, so that they sum to exactly 4 per order."""
    counts = np.full(n_orders, 4, np.int32)
    full = n_orders - n_orders % 7
    counts[:full] = np.tile(np.arange(1, 8, dtype=np.int32), full // 7)
    return counts


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents of 1-based int32 ``partkey`` (Clause 4.2.3;
    at most 209,900)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def part_supplier(partkey: np.ndarray, i: np.ndarray, n_supp: int
                  ) -> np.ndarray:
    """0-based key of supplier ``i`` (0..3) of 1-based int32 ``partkey``:
    the specification's PS_SUPPKEY, less one."""
    step = n_supp // 4 + (partkey - 1) // n_supp
    return (partkey + i.astype(np.int32) * step) % n_supp


def generate(scale: float, seed: int, threads: int = 8) -> Tables:
    """{table: {column: array}} at ``scale``, drawn from ``seed``."""
    n = sizes(scale)
    n_ord, n_li, n_cust = n["orders"], n["lineitem"], n["customer"]
    buyers = np.flatnonzero((np.arange(n_cust) + 1) % 3 != 0).astype(np.int32)

    def l_orderkey(rng):
        keys = np.repeat(np.arange(n_ord, dtype=np.int32),
                         rng.permutation(lines_per_order(n_ord)))
        rng.shuffle(keys)
        return keys

    def ints(lo, hi, rows):
        return lambda rng: rng.integers(lo, hi, rows, dtype=np.int32)

    draws = {
        "c_nationkey": ints(0, N_NATION, n_cust),
        "c_mktsegment": ints(0, N_SEGMENTS, n_cust),
        "s_nationkey": ints(0, N_NATION, n["supplier"]),
        "o_custkey": lambda rng: buyers[
            rng.integers(0, len(buyers), n_ord)],
        "o_orderdate": ints(DATE0, ORDERDATE_HI + 1, n_ord),
        "l_orderkey": l_orderkey,
        "l_quantity": ints(1, 51, n_li),
        "l_discount": lambda rng: _HUNDREDTHS[
            rng.integers(0, 11, n_li, dtype=np.int8)],
        "l_tax": lambda rng: _HUNDREDTHS[
            rng.integers(0, 9, n_li, dtype=np.int8)],
        "partkey": ints(1, parts(scale) + 1, n_li),
        "supplier_i": lambda rng: rng.integers(0, 4, n_li, dtype=np.int8),
        "ship_days": lambda rng: rng.integers(1, 122, n_li, dtype=np.int16),
        "receipt_days": lambda rng: rng.integers(1, 31, n_li,
                                                 dtype=np.int16),
        "returned": lambda rng: rng.integers(0, 2, n_li, dtype=np.int8),
    }
    streams = np.random.SeedSequence(int(seed) % 2**64).spawn(len(draws))
    with ThreadPoolExecutor(max(1, threads)) as pool:
        got = dict(zip(draws, pool.map(
            lambda job: job[0](np.random.default_rng(job[1])),
            zip(draws.values(), streams))))

    def dates():
        ship = got["o_orderdate"][got["l_orderkey"]] + got["ship_days"]
        receipt = ship + got["receipt_days"]
        flag = np.where(
            receipt <= CURRENTDATE,
            np.where(got["returned"] == 1, RETURNFLAG["R"], RETURNFLAG["A"]),
            RETURNFLAG["N"]).astype(np.int32)
        status = np.where(ship > CURRENTDATE, LINESTATUS["O"],
                          LINESTATUS["F"]).astype(np.int32)
        return ship.astype(np.int32), flag, status

    def prices():
        # quantity times cents is at most 10,495,000: exact in int32 and
        # float32
        cents = got["l_quantity"] * retail_cents(got["partkey"])
        return cents.astype(np.float32) / np.float32(100)

    with ThreadPoolExecutor(3) as pool:
        jobs = [pool.submit(dates), pool.submit(prices), pool.submit(
            part_supplier, got["partkey"], got["supplier_i"], n["supplier"])]
        (ship, returnflag, linestatus), price, suppkey = (
            j.result() for j in jobs)
    return {
        "nation": {"n_nationkey": np.arange(N_NATION, dtype=np.int32),
                   "n_regionkey": NATION_REGION.copy()},
        "customer": {"c_custkey": np.arange(n_cust, dtype=np.int32),
                     "c_nationkey": got["c_nationkey"],
                     "c_mktsegment": got["c_mktsegment"]},
        "supplier": {"s_suppkey": np.arange(n["supplier"], dtype=np.int32),
                     "s_nationkey": got["s_nationkey"]},
        "orders": {"o_orderkey": np.arange(n_ord, dtype=np.int32),
                   "o_custkey": got["o_custkey"],
                   "o_orderdate": got["o_orderdate"]},
        "lineitem": {
            "l_orderkey": got["l_orderkey"],
            "l_suppkey": suppkey,
            "l_quantity": got["l_quantity"].astype(np.float32),
            "l_extendedprice": price,
            "l_discount": got["l_discount"],
            "l_tax": got["l_tax"],
            "l_returnflag": returnflag,
            "l_linestatus": linestatus,
            "l_shipdate": ship,
        },
    }
