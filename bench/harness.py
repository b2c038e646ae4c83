"""One benchmark run of one cell: set-up, a closed-loop window, the checks.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``: scale, chips, ``ExecutionContext``,
``ServiceConfig``) under a traffic mix (``bench/traffic/<mix>.json``: the
queries and one parameter set per stream). Everything here is general; a new
configuration, mix, query reference (``bench/reference/<query>.py``) or
per-layer metric (``bench/metrics/<name>.py``) is a new file.

The run, in order:

1. set-up (``setup_s``, from the start of the process): generate the data
   from the seed (``bench.tpch_data``), place it with the program's
   ``TPCHData(...).as_jax()``, compile every plan of the cell side by side
   in threads (``CompiledPlan.lower(...).compile()``; the persistent
   compile cache makes this a load after the first run), start an
   ``AnalyticsService`` and serve each plan once through it;
2. the window: the mix's loop (``bench/loops/<loop>.py``, named by the
   traffic file's ``loop``) sends the mix's queries, in the orders the mix
   fixes for each stream (``stream_order``), through
   ``AnalyticsService.submit`` and waits for each ``result``;
3. after the window: wait up to a minute for answers still in flight,
   read the peak device memory, free the program's state, and compare every
   answer with the numpy reference of its (query, parameter set).
"""
from __future__ import annotations

import enum
import gc
import importlib.util
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import typing
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from bench import checks, devtrace, tpch_data

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
GRACE_S = 60.0                  # answers due in the window may come this late
COMPILE_HOST_BYTES = 4 << 30    # host memory one plan compile may take
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class DeviceError(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
@dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def streams(self) -> List[Dict[str, Dict[str, Any]]]:
        return self.traffic["streams"]

    def plan_keys(self) -> List[Tuple[int, str]]:
        return [(s, q) for s, stream in enumerate(self.streams)
                for q in self.traffic["queries"] if q in stream]


def _json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def load_cell(workload: str, spec: Optional[Dict[str, Any]] = None) -> Cell:
    """Cell ``workload`` of ``spec`` (by default BENCHMARK.json), with the
    files it names; a traffic loop without its file is refused here, before
    any work."""
    spec = benchmark() if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    cell = Cell(
        name=workload,
        config=_json(ROOT / files[w["config"]]),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"]
                    if workload in m.get("workloads", [workload])],
        per_layer=[m for m in spec["per_layer"]
                   if workload in m.get("workloads", [workload])])
    loop_module(cell.traffic["loop"])
    return cell


def peaks(kind: str) -> Dict[str, Any]:
    """The published peaks of device kind ``kind``; an unknown kind is an
    error, never a default."""
    table = _json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def accelerator(chips: int):
    """(devices, peaks) of this machine's TPU chips, or DeviceError."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise DeviceError(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices, peaks(devices[0].device_kind)


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``, whatever
    the environment names, so that a checkout shares its compiled programs
    with no other; every program goes in, however fast it compiled, so that
    a second run of a cell compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``; a name without its file is an
    error."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{name!r} has no file bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module("metrics", name).read


def loop_module(name: str):
    """The traffic loop ``bench/loops/<name>.py``: its ``run(client)`` starts
    the threads that send the window's queries through ``client``."""
    return _module("loops", name)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def from_json(cls, kw: Dict[str, Any], devices=()):
    """Dataclass ``cls`` from a configuration's JSON object, each value
    converted by its field's type: a string for an Enum field is the name
    of a member, an object ``{"shape", "axes"}`` for a Mesh field a mesh
    over the first of ``devices``; other values pass as they are."""
    import jax
    from jax.sharding import Mesh
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in kw.items():
        if k not in hints:
            raise KeyError(f"{cls.__name__} has no field {k!r}")
        types = typing.get_args(hints[k]) or (hints[k],)
        enums = [t for t in types
                 if isinstance(t, type) and issubclass(t, enum.Enum)]
        if enums and isinstance(v, str):
            v = enums[0][v]
        elif Mesh in types and isinstance(v, dict):
            n = math.prod(v["shape"])
            v = jax.make_mesh(tuple(v["shape"]), tuple(v["axes"]),
                              devices=devices[:n])
        out[k] = v
    return cls(**out)


def context(config: Dict[str, Any], devices):
    """The configuration's ``ExecutionContext`` on ``devices``."""
    from repro.analytics.planner import ExecutionContext
    return from_json(ExecutionContext, config["context"], devices)


def service_config(config: Dict[str, Any]):
    """The configuration's ``ServiceConfig``."""
    from repro.analytics.service import ServiceConfig
    return from_json(ServiceConfig, config["service"])


def build_plans(cell: Cell) -> Dict[Tuple[int, str], Any]:
    from repro.analytics import tpch
    return {(s, q): getattr(tpch, f"build_{q}")(**cell.streams[s][q])
            for s, q in cell.plan_keys()}


class CompileLog:
    """Times (perf_counter) at which JAX finished getting an executable
    from XLA: a backend compile, or a load from the persistent cache."""

    _instance: Optional["CompileLog"] = None

    def __init__(self):
        self.times: List[float] = []
        self._lock = threading.Lock()

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            import jax
            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on_duration)
        return cls._instance

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        with self._lock:
            return sum(lo <= t <= hi for t in self.times)


def precompile(plans, tables, ctx) -> None:
    """Compile (or load from the persistent cache) every plan, side by side
    in as many threads as the host's cores and memory allow."""
    from repro.analytics import planner

    def one(plan):
        planner.compile_plan(plan, tables, ctx).lower(tables).compile()

    free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    threads = max(1, min(len(plans), os.cpu_count() or 1,
                         free // COMPILE_HOST_BYTES))
    with ThreadPoolExecutor(threads) as pool:
        for fut in [pool.submit(one, p) for p in plans.values()]:
            fut.result()


@dataclass
class Request:
    stream: int
    query: str
    t_submit: float
    t_done: float = math.nan
    value: Optional[Dict[str, Any]] = None
    phases: Optional[Dict[str, float]] = None
    error: Optional[str] = None
    rid: Optional[int] = None


@dataclass
class Window:
    t_start: float
    t_end: float
    requests: List[Request] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def completed(self) -> List[Request]:
        """Requests answered, without error, by the window's close."""
        return [r for r in self.requests if r.value is not None
                and r.error is None and r.t_done <= self.t_end]

    def qps(self) -> float:
        """Queries answered in the window per second of host clock, from
        the window's start to the last of those answers: whole queries
        only, and the window taken as ending on an answer, so that no
        share of a query cut by the close is guessed."""
        done = self.completed()
        if not done:
            return 0.0
        return len(done) / (max(r.t_done for r in done) - self.t_start)


def stream_order(traffic: Dict[str, Any], s: int) -> Iterator[str]:
    """Stream ``s``'s queries, pass after pass: each pass holds every query
    of the stream once, in an order drawn from the mix's ``order_seed`` (as
    TPC-H fixes each stream's order, so every run sends the same work)."""
    qs = [q for q in traffic["queries"] if q in traffic["streams"][s]]
    for p in itertools.count():
        rng = np.random.default_rng([traffic["order_seed"], s, p])
        yield from (qs[i] for i in rng.permutation(len(qs)))


class Client:
    """What a traffic loop drives the service with in one window: ``submit``
    a stream's query, ``wait`` for its answer (each request is recorded
    with its host-clock times), ``order`` of a stream's queries, and the
    window's ``t_end`` once it has started."""

    def __init__(self, service, tables, ctx, plans, traffic: Dict[str, Any],
                 win: Window):
        self.service, self.tables, self.ctx = service, tables, ctx
        self.plans, self.traffic, self.win = plans, traffic, win
        self.n_streams = len(traffic["streams"])
        self._go = threading.Event()
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []

    @property
    def t_end(self) -> float:
        return self.win.t_end

    def order(self, s: int) -> Iterator[str]:
        return stream_order(self.traffic, s)

    def wait_for_start(self) -> None:
        self._go.wait()

    def started(self, threads: List[threading.Thread]) -> None:
        """Threads of the loop that the window waits for at its end."""
        self._threads.extend(threads)

    def submit(self, s: int, q: str) -> Request:
        req = Request(s, q, time.perf_counter())
        req.rid = self.service.submit(self.plans[s, q], self.tables,
                                      context=self.ctx, client_id=s)
        return req

    def wait(self, req: Request) -> None:
        res = None
        if req.rid is not None:
            res = self.service.result(
                req.rid, timeout=self.t_end + GRACE_S - time.perf_counter())
        req.t_done = time.perf_counter()
        if res is None:
            req.error = "refused" if req.rid is None else "no answer"
        else:
            req.value, req.phases, req.error = res.value, res.phases, res.error
        with self._lock:
            self.win.requests.append(req)

    def _open(self) -> None:
        self._go.set()

    def _join(self) -> None:
        for t in self._threads:
            t.join(GRACE_S + 30.0)
        if any(t.is_alive() for t in self._threads):
            raise RuntimeError("a client of the traffic loop did not finish")


def run_window(service, tables, ctx, plans, traffic: Dict[str, Any],
               seconds: float, trace_dir: Optional[str] = None) -> Window:
    """The mix's loop sends queries for ``seconds``; then every answer it
    waits for comes in (up to ``GRACE_S`` late)."""
    import jax
    win = Window(0.0, 0.0)
    client = Client(service, tables, ctx, plans, traffic, win)
    loop_module(traffic["loop"]).run(client)
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            win.t_start = time.perf_counter()
            win.t_end = win.t_start + seconds
            client._open()
            time.sleep(max(0.0, win.t_end - time.perf_counter()))
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    client._join()
    return win


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def peak_memory(devices) -> int:
    return max([(d.memory_stats() or {}).get("peak_bytes_in_use", 0) or 0
                for d in devices] + [0])


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------
def answers_on_host(win: Window) -> None:
    for r in win.requests:
        if r.value is not None:
            r.value = {k: np.asarray(v) for k, v in r.value.items()}


def reference_answers(cell: Cell, host_tables, keys, dt=np.float64
                      ) -> Dict[Tuple[int, str], Dict[str, np.ndarray]]:
    def one(key):
        s, q = key
        return key, checks.reference_answer(q, host_tables,
                                            cell.streams[s][q], dt)
    with ThreadPoolExecutor(max(1, min(len(keys), os.cpu_count() or 1))
                            ) as pool:
        return dict(pool.map(one, sorted(keys)))


def readings(cell: Cell, win: Window, refs) -> Dict[str, float]:
    rel, mismatches, missing = 0.0, 0, 0
    for r in win.requests:
        if r.value is None or r.error is not None:
            missing += 1
            continue
        exact = checks.reference_module(r.query).EXACT
        e, m = checks.compare(r.value, refs[r.stream, r.query], exact)
        rel, mismatches = max(rel, e), mismatches + m
    return {"rel_err": rel, "exact_mismatches": float(mismatches),
            "missing": float(missing)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclass
class RunView:
    """What a per-layer metric's reader gets."""
    cell: Cell
    window_s: float
    completed: List[Request]
    compiles_in_window: int
    trace: Optional[Dict[str, Any]]
    table_rows: Dict[str, int]
    column_bytes: Dict[str, Dict[str, int]]
    peaks: Dict[str, Any]
    reads: Dict[str, Dict[str, Tuple[str, ...]]]


class Session:
    """The program under test for one cell: its tables, context, compiled
    plans and running service. ``load`` may be called again with another
    seed; the plans compiled for the first tables serve any tables of the
    same shapes."""

    def __init__(self, cell: Cell, devices, t0: float):
        self.cell, self.devices, self.t0 = cell, devices, t0
        self.ctx = context(cell.config, devices)
        self.plans = build_plans(cell)
        self.host = self.tables = self.service = None

    def log(self, what: str) -> None:
        log(f"{what} at {time.perf_counter() - self.t0:.3f} s "
            f"({len(CompileLog.get().times)} compiles so far)")

    def load(self, seed: int) -> None:
        """Generate the seed's data and place it as the program does."""
        import jax
        from repro.analytics import tpch
        scale = self.cell.config["scale"]
        self.tables = None
        self.host = tpch_data.generate(scale, seed)
        self.log(f"generated SF {scale} from seed {seed}")
        self.tables = jax.block_until_ready(
            tpch.TPCHData(self.host, scale).as_jax())
        self.log("tables on the device")

    def start(self) -> None:
        """Compile every plan side by side, then start the service."""
        from repro.analytics.service import AnalyticsService
        precompile(self.plans, self.tables, self.ctx)
        self.log(f"{len(self.plans)} plans compiled")
        self.service = AnalyticsService(
            service_config(self.cell.config)).start()

    def warm(self) -> None:
        """Serve each plan once on the loaded tables (builds their join
        indexes; a plan's first call loads its executable)."""
        rids = {k: self.service.submit(p, self.tables, context=self.ctx)
                for k, p in self.plans.items()}
        for k, rid in rids.items():
            res = self.service.result(rid, timeout=600.0)
            if res is None or res.value is None:
                raise RuntimeError(f"warm-up of {k} gave no answer: "
                                   f"{None if res is None else res.error}")
        self.log("every plan served once")

    def window(self, seconds: float, trace_dir: Optional[str] = None
               ) -> Window:
        return run_window(self.service, self.tables, self.ctx, self.plans,
                          self.cell.traffic, seconds, trace_dir)

    def close(self) -> None:
        """Stop the service and let go of the program's device state."""
        from repro.analytics import planner
        if self.service is not None:
            self.service.close()
        self.service = self.tables = None
        planner.clear_plan_cache()
        gc.collect()


def execute_summary(win: Window) -> str:
    """Per query of the window: answers, median and longest execute phase
    (a stall shows as a longest far above the median)."""
    by_query: Dict[str, List[float]] = {}
    for r in win.completed():
        if r.phases:
            by_query.setdefault(r.query, []).append(r.phases["execute"])
    return "; ".join(f"{q} n={len(v)} median {np.median(v):.4f} "
                     f"max {max(v):.4f}" for q, v in sorted(by_query.items()))


def check(cell: Cell, host, win: Window) -> Dict[str, float]:
    """The checks' readings of every answer the window got."""
    answered = {(r.stream, r.query) for r in win.requests
                if r.value is not None}
    t = time.perf_counter()
    refs = reference_answers(cell, host, answered)
    out = readings(cell, win, refs)
    log(f"window {win.seconds:.3f} s: {len(win.requests)} requests; "
        f"reference of {len(refs)} answers {time.perf_counter() - t:.3f} s")
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, device_peaks: Dict[str, Any], t0: float) -> Dict:
    """One run; returns the result object. ``t0`` is the process start."""
    compiles = CompileLog.get()
    session = Session(cell, devices, t0)
    try:
        session.load(seed)
        session.start()
        session.warm()
        setup_s = time.perf_counter() - t0
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            win = session.window(seconds, trace_dir)
            reduced = (devtrace.reduce(devtrace.load_xplane(trace_dir))
                       if trace else None)
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        memory_peak = peak_memory(devices[:cell.chips])
        answers_on_host(win)
        host = session.host
    finally:
        session.close()
    n_compiles = compiles.between(win.t_start, win.t_end)
    log(f"{n_compiles} executables compiled or loaded in the window")
    log(f"qps {win.qps():.6f}; execute phase, s: {execute_summary(win)}")
    read = check(cell, host, win)
    lim = checks.limits(cell.name)
    completed = win.completed()

    if trace:
        view = RunView(
            cell, win.seconds, completed, n_compiles, reduced,
            {t: len(next(iter(c.values()))) for t, c in host.items()},
            {t: {c: a.dtype.itemsize for c, a in cols.items()}
             for t, cols in host.items()},
            device_peaks,
            {q: checks.reference_module(q).READS
             for q in cell.traffic["queries"]})
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"qps": win.qps(), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    out = {"correct": checks.judge(read, lim),
           "attempted": len(win.requests),
           "failed": sum(r.value is None or r.error is not None
                         for r in win.requests),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": read[k], "limit": lim[k]}
                     for k in checks.NAMES}
    return out
