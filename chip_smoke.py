#!/usr/bin/env python3
"""Smoke run of the TPC-H engine on TPU chips, through its user entry points.

    python chip_smoke.py                 # one chip: queries + serving, SF 10
    python chip_smoke.py --chips 4       # four chips: placement policies

One chip: generates TPC-H at ``--scale`` from ``--seed`` (``tpch.generate``,
``TPCHData.as_jax``), runs the seven queries under ``executor="kernel"`` and
``"cost"`` (``tpch.run_query``; one cold run, then up to three warm runs),
checks every result against a plain numpy reference over the host tables,
shows that the Pallas kernels ran, and serves a mixed burst through
``AnalyticsService`` (``tpch.submit_query``, ``drain``).

Four chips: q3, q5 and q18 on a ``("data",)`` mesh over the host's four
chips, under INTERLEAVE (partitioned joins, hash Exchanges with the argsort
and the radix layout) and FIRST_TOUCH, each compared with the same query run
on one chip in this process.

Before the timed runs every plan of the phase is compiled side by side, in
as many threads as the host's cores and memory allow
(``CompiledPlan.lower``): a TPU sort takes 15-40 s to compile
on one host core and the plans hold several, so compiling them one after
another would take most of the run. Each query's first run then reuses its
executable, and "cold" is that first run. The persistent compile cache
(``repro.core.compile_cache``) keeps the executables for the next process.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed. Without a TPU the script exits non-zero
before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

QUERIES = ("q1", "q3", "q5", "q6", "q18", "qm", "qq")
EXECUTORS = ("kernel", "cost")
WARM_RUNS = 3
WARM_BUDGET_S = 20.0   # a query stops its warm runs once they pass this
RTOL = 1e-5            # f32 sums of up to ~1e10 against a float64 reference
DIST_QUERIES = ("q3", "q5", "q18")
COMPILE_HOST_BYTES = 4 << 30   # host memory one SF 10 plan compile may take

# query parameters, as the repo's TPC-H plans define them
DATE1 = 2557
Q1_CUTOFF = DATE1 - 90
Q3_SEGMENT, Q3_DATE = 1, DATE1 // 2
Q5_REGION, Q5_DATE_LO, Q5_DATE_HI = 2, 0, 365
Q6_DATE_LO, Q6_DATE_HI, Q6_DISC, Q6_QTY = 0, 365, 0.06, 24.0
Q18_QTY = 212.0
N_NATION = 25
EXACT_KEYS = ("count_order", "_count", "o_orderkey")


# ---------------------------------------------------------------------------
# plain numpy reference over the host tables
# ---------------------------------------------------------------------------
def _pk_lookup(pk, fk):
    """Row of ``pk`` holding each ``fk`` value, and whether it exists."""
    import numpy as np
    order = np.argsort(pk, kind="stable")
    sk = pk[order]
    pos = np.clip(np.searchsorted(sk, fk), 0, len(sk) - 1)
    return order[pos], sk[pos] == fk


def _f64(a):
    import numpy as np
    return a.astype(np.float64)


def ref_q1(t):
    import numpy as np
    li = t["lineitem"]
    m = li["l_shipdate"] <= Q1_CUTOFF
    g = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
    qty, price = _f64(li["l_quantity"][m]), _f64(li["l_extendedprice"][m])
    disc_price = price * (1 - _f64(li["l_discount"][m]))
    charge = disc_price * (1 + _f64(li["l_tax"][m]))

    def total(v):
        return np.bincount(g, weights=v, minlength=6)

    cnt = np.bincount(g, minlength=6)
    return {"sum_qty": total(qty), "sum_base_price": total(price),
            "sum_disc_price": total(disc_price), "sum_charge": total(charge),
            "avg_qty": total(qty) / np.maximum(cnt, 1),
            "avg_price": total(price) / np.maximum(cnt, 1),
            "count_order": cnt, "_count": cnt}


def ref_q3(t):
    import numpy as np
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    crow, cfound = _pk_lookup(c["c_custkey"], o["o_custkey"])
    o_ok = ((o["o_orderdate"] < Q3_DATE) & cfound
            & (c["c_mktsegment"][crow] == Q3_SEGMENT))
    orow, ofound = _pk_lookup(o["o_orderkey"], li["l_orderkey"])
    m = (li["l_shipdate"] > Q3_DATE) & ofound & o_ok[orow]
    n_ord = len(o["o_orderkey"])
    rev = _f64(li["l_extendedprice"][m]) * (1 - _f64(li["l_discount"][m]))
    per_order = np.bincount(np.clip(li["l_orderkey"][m], 0, n_ord - 1),
                            weights=rev, minlength=n_ord)
    top = np.argsort(-per_order, kind="stable")[:10]
    return {"revenue": per_order[top], "o_orderkey": top}


def ref_q5(t):
    import numpy as np
    n, c, o = t["nation"], t["customer"], t["orders"]
    li, s = t["lineitem"], t["supplier"]
    nrow, nfound = _pk_lookup(n["n_nationkey"], c["c_nationkey"])
    c_ok = nfound & (n["n_regionkey"][nrow] == Q5_REGION)
    crow, cfound = _pk_lookup(c["c_custkey"], o["o_custkey"])
    o_ok = ((o["o_orderdate"] >= Q5_DATE_LO) & (o["o_orderdate"] < Q5_DATE_HI)
            & cfound & c_ok[crow])
    orow, ofound = _pk_lookup(o["o_orderkey"], li["l_orderkey"])
    srow, sfound = _pk_lookup(s["s_suppkey"], li["l_suppkey"])
    s_nat = s["s_nationkey"][srow]
    m = (ofound & o_ok[orow] & sfound
         & (s_nat == c["c_nationkey"][crow][orow]))
    rev = _f64(li["l_extendedprice"][m]) * (1 - _f64(li["l_discount"][m]))
    return {"revenue": np.bincount(s_nat[m], weights=rev, minlength=N_NATION),
            "_count": np.bincount(s_nat[m], minlength=N_NATION)}


def ref_q6(t):
    import numpy as np
    li = t["lineitem"]
    m = ((li["l_shipdate"] >= Q6_DATE_LO) & (li["l_shipdate"] < Q6_DATE_HI)
         & (np.abs(li["l_discount"] - np.float32(Q6_DISC)) <= np.float32(0.011))
         & (li["l_quantity"] < Q6_QTY))
    rev = (_f64(li["l_extendedprice"][m]) * _f64(li["l_discount"][m])).sum()
    return {"revenue": np.array([rev])}


def ref_q18(t):
    import numpy as np
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    n_ord, n_cust = len(o["o_orderkey"]), len(c["c_custkey"])
    per_order = np.bincount(np.clip(li["l_orderkey"], 0, n_ord - 1),
                            weights=_f64(li["l_quantity"]), minlength=n_ord)
    o_qty = per_order[np.clip(o["o_orderkey"], 0, n_ord - 1)]
    _, cfound = _pk_lookup(c["c_custkey"], o["o_custkey"])
    m = (o_qty > Q18_QTY) & cfound
    g = np.clip(o["o_custkey"][m], 0, n_cust - 1)
    return {"qty": np.bincount(g, weights=o_qty[m], minlength=n_cust),
            "_count": np.bincount(g, minlength=n_cust)}


def _by_returnflag(t, stats):
    """{name: per-returnflag values} of Q1's shipped rows; ``stats`` maps
    name -> (column, fn of that column's group values)."""
    import numpy as np
    li = t["lineitem"]
    m = li["l_shipdate"] <= Q1_CUTOFF
    g = li["l_returnflag"][m]
    order = np.argsort(g, kind="stable")
    bounds = np.searchsorted(g[order], np.arange(4))
    out = {}
    for name, (col, fn) in stats.items():
        v = li[col][m][order]
        out[name] = np.array([fn(v[bounds[i]:bounds[i + 1]])
                              for i in range(3)])
    cnt = np.diff(bounds)
    out["count_order"] = out["_count"] = cnt
    return out


def ref_qm(t):
    import numpy as np
    return _by_returnflag(t, {
        "med_qty": ("l_quantity", np.median),
        "med_price": ("l_extendedprice", np.median),
        "avg_qty": ("l_quantity", lambda v: _f64(v).mean())})


def ref_qq(t):
    import numpy as np
    return _by_returnflag(t, {
        "p90_price": ("l_extendedprice", lambda v: np.quantile(v, 0.9)),
        "p25_qty": ("l_quantity", lambda v: np.quantile(v, 0.25)),
        "med_price": ("l_extendedprice", np.median)})


REFERENCES = {"q1": ref_q1, "q3": ref_q3, "q5": ref_q5, "q6": ref_q6,
              "q18": ref_q18, "qm": ref_qm, "qq": ref_qq}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def compare(got, ref):
    """Mismatch messages for ``got`` against ``ref`` (empty when equal):
    counts and keys exactly (as the result's dtype holds them: an f32 count
    above 2^24 is the nearest f32), float values to RTOL, overflow 0."""
    import numpy as np
    bad = []
    if "_overflow" in got and int(np.asarray(got["_overflow"])) != 0:
        bad.append(f"_overflow={int(np.asarray(got['_overflow']))}")
    for k, r in ref.items():
        if k == "_overflow":
            continue
        g, r = np.asarray(got[k]), np.asarray(r)
        if g.shape != r.shape:
            bad.append(f"{k}: shape {g.shape} != {r.shape}")
        elif k in EXACT_KEYS:
            if not np.array_equal(g, r.astype(g.dtype)):
                bad.append(f"{k}: {int((g != r.astype(g.dtype)).sum())} "
                           f"entries differ")
        elif not np.allclose(g, r, rtol=RTOL, atol=0.0, equal_nan=True):
            err = np.nanmax(np.abs(g - r) / np.maximum(np.abs(r), 1e-30))
            bad.append(f"{k}: max rel err {err:.3e}")
    return bad


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


class Smoke:
    def __init__(self):
        self.failures = []
        self.compiles = 0
        self.cache_hits = 0

    def fail(self, msg):
        self.failures.append(msg)
        print(f"FAIL {msg}", flush=True)

    def on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def precompile(smoke, jobs, tables):
    """Compile each ``(label, query, context)`` job's plan in its own
    thread; returns {label: compiled executable}. A job that fails is
    reported, and its query runs anyway."""
    from repro.analytics import planner, tpch

    def one(job):
        label, name, ctx = job
        t0 = time.perf_counter()
        cp = planner.compile_plan(tpch.LOGICAL_QUERIES[name], tables, ctx)
        return cp.lower(tables).compile(), time.perf_counter() - t0

    free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    threads = max(1, min(len(jobs), os.cpu_count() or 1,
                         free // COMPILE_HOST_BYTES))
    t0 = time.perf_counter()
    compiled, done = {}, []
    with ThreadPoolExecutor(threads) as pool:
        futures = [(job[0], pool.submit(one, job)) for job in jobs]
        for label, fut in futures:
            try:
                compiled[label], dt = fut.result()
                done.append(f"{label} {dt:.1f}")
            except Exception as e:  # the query's own run reports it again
                smoke.fail(f"{label}: compile failed: {e!r:.500}")
    print(f"compiled {len(jobs)} plans in {threads} threads in "
          f"{time.perf_counter() - t0:.3f} s (per plan, s: "
          f"{', '.join(done)}); compiles so far {smoke.compiles}, cache "
          f"hits {smoke.cache_hits}", flush=True)
    return compiled


def run_timed(smoke, label, fn, warm_runs=WARM_RUNS):
    """One cold run and up to ``warm_runs`` warm ones (fewer once they pass
    WARM_BUDGET_S); prints the times, returns the last result, or None
    when the query raised."""
    try:
        out, cold = timed(fn)
        warm = []
        while len(warm) < warm_runs and sum(warm) < WARM_BUDGET_S:
            out, dt = timed(fn)
            warm.append(dt)
    except Exception as e:
        smoke.fail(f"{label} raised {e!r:.500}")
        return None
    print(f"{label}: cold {cold:.6f} s, warm "
          f"{', '.join(f'{w:.6f}' for w in warm) or '-'} s "
          f"(compiles so far {smoke.compiles})", flush=True)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def load(args):
    import jax
    from repro.analytics import tpch
    t0 = time.perf_counter()
    data = tpch.generate(scale=args.scale, seed=args.seed)
    t1 = time.perf_counter()
    tables = jax.block_until_ready(data.as_jax())
    t2 = time.perf_counter()
    rows = {t: len(next(iter(c.values()))) for t, c in data.tables.items()}
    nbytes = sum(a.nbytes for c in data.tables.values() for a in c.values())
    print(f"loaded TPC-H SF {args.scale} (seed {args.seed}): rows {rows}, "
          f"{nbytes} bytes; generate {t1 - t0:.3f} s, to device "
          f"{t2 - t1:.3f} s", flush=True)
    return data, tables


def check_all(smoke, data, results, against):
    """Compare ``{(query, label): result}`` with the numpy reference."""
    t0 = time.perf_counter()
    refs = {name: REFERENCES[name](data.tables)
            for name in {name for name, _ in results}}
    print(f"numpy reference: {time.perf_counter() - t0:.3f} s", flush=True)
    for (name, label), got in results.items():
        if got is None:
            continue
        bad = compare(got, refs[name])
        if bad:
            smoke.fail(f"{name}/{label} vs {against}: {'; '.join(bad)}")
        else:
            print(f"{name}/{label} matches the {against}", flush=True)


def one_chip(smoke, args, data, tables):
    import jax
    import numpy as np
    from repro.analytics import planner, tpch
    from repro.analytics.service import AnalyticsService, ServiceConfig
    from repro.kernels.common import kernel_mode

    mode = kernel_mode()
    print(f"kernel_mode() = {mode!r}", flush=True)
    if mode != "pallas":
        smoke.fail(f"kernel mode resolved to {mode!r}, not 'pallas'")

    precompile(smoke, [(f"{name}/{ex}", name,
                        planner.ExecutionContext(executor=ex))
                       for ex in EXECUTORS for name in QUERIES], tables)
    results = {}
    for ex in EXECUTORS:
        for name in QUERIES:
            results[name, ex] = run_timed(
                smoke, f"{name}/{ex}",
                lambda: tpch.run_query(name, data, executor=ex))
    check_all(smoke, data, results, "numpy reference")

    q1 = planner.compile_plan(tpch.LOGICAL_QUERIES["q1"], tables,
                              planner.ExecutionContext(executor="kernel"))
    has_kernel = "tpu_custom_call" in q1.lower(tables).compile().as_text()
    print(f"q1/kernel compiled HLO contains tpu_custom_call: {has_kernel}",
          flush=True)
    if not has_kernel:
        smoke.fail("q1/kernel plan has no tpu_custom_call")

    service = AnalyticsService(ServiceConfig(n_pools=2))
    burst = [(("q1", "q3", "q6")[i % 3], EXECUTORS[i % 2]) for i in range(20)]
    t0 = time.perf_counter()
    rids = [tpch.submit_query(service, name, data, executor=ex)
            for name, ex in burst]
    served = service.drain(timeout=600.0)
    dt = time.perf_counter() - t0
    stats = service.stats()
    service.close()
    print(f"served burst of {len(burst)}: {dt:.6f} s; completed "
          f"{stats.completed}, failed {stats.failed}, expired "
          f"{stats.expired}, shed {stats.shed}, dispatches "
          f"{stats.dispatches}", flush=True)
    if None in rids:
        smoke.fail("service refused a request")
    if (stats.failed, stats.expired, stats.shed) != (0, 0, 0):
        smoke.fail(f"service failed={stats.failed} expired={stats.expired} "
                   f"shed={stats.shed}")
    for rid, (name, ex) in zip(rids, burst):
        res, want = served.get(rid), results[name, ex]
        if res is None or res.value is None:
            smoke.fail(f"request {rid} ({name}/{ex}) has no value: "
                       f"{None if res is None else res.error}")
        elif want is not None and not all(
                np.array_equal(np.asarray(res.value[k]), np.asarray(want[k]),
                               equal_nan=True) for k in want):
            smoke.fail(f"request {rid} ({name}/{ex}) differs from run_query")
    print(f"peak_bytes_in_use: {peak_bytes(jax.devices()[:1])}", flush=True)


def four_chips(smoke, args, data, tables):
    import jax
    import numpy as np
    from repro.analytics import tpch
    from repro.analytics.planner import ExecutionContext
    from repro.core.config import PlacementPolicy

    devices = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("data",), devices=devices)
    col = tables["lineitem"]["l_orderkey"]
    print(f"tables placed on {sorted(str(d) for d in col.devices())}",
          flush=True)
    contexts = {
        "interleave/argsort": ExecutionContext(
            mesh=mesh, policy=PlacementPolicy.INTERLEAVE,
            dist_join="partitioned", exchange_impl="argsort"),
        "interleave/radix": ExecutionContext(
            mesh=mesh, policy=PlacementPolicy.INTERLEAVE,
            dist_join="partitioned", exchange_impl="radix"),
        "first_touch": ExecutionContext(
            mesh=mesh, policy=PlacementPolicy.FIRST_TOUCH),
    }
    local_ctx = ExecutionContext()
    compiled = precompile(smoke, [(f"{name}/{label}", name, ctx)
                       for name in DIST_QUERIES
                       for label, ctx in [("local", local_ctx),
                                          *contexts.items()]], tables)
    for name in DIST_QUERIES:
        local = run_timed(smoke, f"{name}/local",
                          lambda: tpch.run_query(name, data,
                                                 context=local_ctx),
                          warm_runs=1)
        if local is None:
            continue
        local = {k: np.asarray(v) for k, v in local.items()}
        for label, ctx in contexts.items():
            got = run_timed(smoke, f"{name}/{label}",
                            lambda: tpch.run_query(name, data, context=ctx),
                            warm_runs=1)
            if got is None:
                continue
            bad = compare(got, local)
            if bad:
                smoke.fail(f"{name}/{label} vs local: {'; '.join(bad)}")
            else:
                print(f"{name}/{label} matches the one-chip result",
                      flush=True)
            print(f"  bytes_in_use per device after {name}/{label}: "
                  f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in devices]}",
                  flush=True)
    for label, exe in compiled.items():
        if label.endswith("first_touch"):
            # where the compiled plan takes its input: a single-device
            # sharding means every call spreads the tables from that chip
            print(f"{label} lineitem.l_orderkey input sharding: "
                  f"{exe.input_shardings[0][0]['lineitem']['l_orderkey']}",
                  flush=True)
    print(f"peak_bytes_in_use per device: {peak_bytes(devices)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.core.compile_cache import enable_compile_cache

    smoke = Smoke()
    jax.monitoring.register_event_duration_secs_listener(smoke.on_duration)
    jax.monitoring.register_event_listener(smoke.on_event)
    kind = devices[0].device_kind
    print(f"device_kind {kind!r}, {len(devices)} chips visible, compile "
          f"cache {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    data, tables = load(args)
    (four_chips if args.chips == 4 else one_chip)(smoke, args, data, tables)
    print(f"total {time.perf_counter() - t0:.3f} s, {smoke.compiles} "
          f"compiles, {smoke.cache_hits} compile-cache hits", flush=True)
    if smoke.failures:
        print(f"{len(smoke.failures)} failures", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
