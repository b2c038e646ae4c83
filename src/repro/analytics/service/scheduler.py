"""Morsel-driven scheduler: socket-pinned worker pools + work stealing.

The execution analog of the paper's thread-placement axis (Figs 3/4):

  * A **WorkerPool** is the NUMA-socket analog — it owns a CONTIGUOUS
    slice of the device mesh (shard range) and a small set of worker
    threads pinned to it. On the single-controller JAX runtime the
    pinning is an affinity *model* (which pool's threads dispatch which
    work, and which shard slice that work is accounted against); on a
    real multi-host deployment the pool maps 1:1 to a host's devices.
  * A **morsel** is a contiguous row range of a scan (engine.morsel_slices)
    — the work unit that makes load balancing possible at all. Plans
    whose root is a distributive Aggregate over a Scan/Filter/Project
    chain are split into per-morsel partial aggregations merged in morsel
    order (engine.merge_morsel_partials — deterministic under stealing).
    Join-probe pipelines the planner marked ``morsel_split`` take the
    SPLIT-PROBE path (_probe_split_decompose): the build sides run once
    per task, each worker pool probes against its OWN replica of the
    pooled build index (JoinIndexPool.replica — the paper's socket-local
    working set, built once per pool, never per morsel), and the
    per-morsel intermediate tables concatenate in morsel order so the
    served result stays bit-identical to serial execution. Everything
    else (kernel joins, distributed contexts, sub-threshold probes)
    executes as one whole-plan morsel through the planner's CompiledPlan
    handle, which is bit-identical to a serial ``run_query`` by
    construction.
  * **ThreadPlacement** mirrors benchmarks/fig3_fig4_thread_placement.py:
    OS_DEFAULT round-robins morsels over pools in arrival order (the
    topology-oblivious baseline), DENSE packs a query's morsels onto one
    pool (contiguous shards, minimal cross-pool traffic), SPARSE stripes
    them across every pool (maximal aggregate bandwidth).
  * **Work stealing** is the AutoNUMA / kernel-load-balancing analog: an
    idle pool steals from the longest backlog; every steal is counted
    per pool and surfaced in SchedulerStats.
  * **Fault tolerance** ports runtime/ft.py's idiom to serving: workers
    stamp per-pool heartbeats and EWMA morsel-service times; a pool that
    dies (``kill_pool``, the drill analog of a lost host) or straggles
    past ``straggler_threshold`` x the fleet-median EWMA is QUARANTINED —
    its queued morsels are requeued onto surviving pools (counted in
    ``requeued``) and new dispatches avoid it, so the service keeps
    serving on a shrunk pool set. Results stay deterministic because
    whole-plan dispatch is idempotent and morsel partials merge in morsel
    order regardless of which pool ran them. All fault hooks sit behind
    one ``if self.faults is not None`` check — zero cost when disabled.
"""
from __future__ import annotations

import enum
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analytics import plan as L
from repro.analytics import planner
from repro.analytics import tracing
from repro.analytics.columnar import Table, finalize_stacked, stacked_columns
from repro.analytics.engine import (merge_morsel_partials, morsel_group_sums,
                                    morsel_slice_columns, morsel_slices)
from repro.analytics.planner import ExecutionContext


class ThreadPlacement(enum.Enum):
    """Pool-to-work affinity strategies (the Fig 3/4 axis).

    OS_DEFAULT  arrival-order round-robin, no affinity (the "OS free to
                migrate" baseline — MeshLayout.NONE's serving analog).
    DENSE       a query's morsels packed onto ONE pool: contiguous shard
                slice, minimal cross-pool hops (Fig 4's dense pinning).
    SPARSE      a query's morsels striped across ALL pools: maximal
                aggregate bandwidth per query (Fig 3/4's sparse pinning).
    """

    OS_DEFAULT = "os_default"
    DENSE = "dense"
    SPARSE = "sparse"


# Multi-device (mesh-context) computations must be dispatched by one
# thread at a time: concurrent shard_map dispatch from worker threads can
# interleave per-device enqueue order (A before B on dev0, B before A on
# dev1) and deadlock the collectives. A distributed plan owns the WHOLE
# mesh anyway — serializing its dispatch loses no parallelism; pools keep
# overlapping single-device work freely.
_MESH_DISPATCH_LOCK = threading.Lock()


@dataclass
class _Morsel:
    task: "QueryTask"
    seq: int                      # position in the task's morsel order
    lo: int
    length: int
    home_pool: int = -1           # assigned pool (stamped at dispatch)


class QueryTask:
    """One dispatch: a whole plan or a set of morsel partial-aggregations.

    ``wait()`` blocks until every morsel completed and the merged result
    is available. Exceptions raised by any morsel are captured and
    re-raised to the waiter."""

    def __init__(self, compiled: Optional[planner.CompiledPlan], tables,
                 morsel_fn: Optional[Callable] = None,
                 finalize: Optional[Callable] = None,
                 morsels: Optional[List[Tuple[int, int]]] = None):
        self.compiled = compiled            # None iff morsel-decomposed
        self.tables = tables
        self.morsel_fn = morsel_fn          # (tables, lo, length) -> partial
        self.finalize = finalize            # (sums, overflow) -> result dict
        self._partials: Dict[int, Any] = {}
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._poison: Optional[BaseException] = None
        self.fault_ordinal: Optional[int] = None
        self.result: Optional[Dict[str, jax.Array]] = None
        self.submit_t: float = 0.0          # scheduler.submit stamp
        self.merge_t: float = 0.0           # last morsel done, merge begins
        self.done_t: float = 0.0            # completion stamp (perf_counter)
        self.trace_id: int = -1             # owning request id (service)
        if morsel_fn is None:
            self.morsels = [_Morsel(self, 0, 0, 0)]
        else:
            self.morsels = [_Morsel(self, i, lo, hi - lo)
                            for i, (lo, hi) in enumerate(morsels)]
        self._pending = len(self.morsels)

    def poison(self, error: BaseException) -> None:
        """Fault-injection hook: the next morsel to run raises ``error``,
        so every ``wait()`` on this task raises (a deterministic stand-in
        for a dispatch that dies inside the executor)."""
        with self._lock:
            self._poison = error

    @property
    def split(self) -> bool:
        return self.morsel_fn is not None

    @property
    def physical(self):
        """The explicit physical plan a whole-plan task dispatches (the
        plan-cache value compile_plan resolved); None for morsel-split
        tasks, whose unit is the per-morsel partial executable."""
        return None if self.compiled is None else self.compiled.physical

    def _dispatch(self, m: _Morsel, pool_id: int):
        """Enqueue one morsel's executable; returns its unready outputs."""
        if self.morsel_fn is None:
            if not tracing.tracing_enabled():
                return self.compiled(self.tables)
            with tracing.working_for(self.trace_id):
                return self.compiled(self.tables)
        # the EXECUTING pool's id, not home_pool: a stolen morsel must
        # probe against the thief's build replica
        if not tracing.tracing_enabled():
            return self.morsel_fn(self.tables, m.lo, length=m.length,
                                  pool=pool_id)
        t0 = time.perf_counter()
        out = self.morsel_fn(self.tables, m.lo, length=m.length,
                             pool=pool_id)
        # morsel tasks are split only without a mesh: nothing on the wire
        tracing.tracer().add_complete(
            "plan.dispatch", "plan", t0, time.perf_counter(),
            trace_id=self.trace_id, pid=f"pool{pool_id}", seq=m.seq,
            exchange_bytes=0, exchanges=0)
        return out

    def _ready(self, out, pool_id: Optional[int] = None):
        """block_until_ready, spanned as ``plan.device_wait``."""
        if not tracing.tracing_enabled():
            return jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = jax.block_until_ready(out)
        tracing.tracer().add_complete(
            "plan.device_wait", "plan", t0, time.perf_counter(),
            trace_id=self.trace_id,
            pid="service" if pool_id is None else f"pool{pool_id}")
        return out

    def _run_morsel(self, m: _Morsel, pool_id: int = 0) -> None:
        try:
            with self._lock:
                if self._poison is not None:
                    raise self._poison
            if self.compiled is not None and \
                    self.compiled.ctx.mesh is not None:
                with _MESH_DISPATCH_LOCK:
                    out = self._ready(self._dispatch(m, pool_id), pool_id)
            else:
                out = self._ready(self._dispatch(m, pool_id), pool_id)
            with self._lock:
                if self.morsel_fn is None:
                    self.result = out
                else:
                    self._partials[m.seq] = out
        except BaseException as e:  # noqa: BLE001 — surfaced to waiter
            with self._lock:
                self._error = e
        finally:
            with self._lock:
                self._pending -= 1
                last = self._pending == 0
            if last:
                self._finish()

    def _finish(self) -> None:
        # the merge phase begins when the LAST morsel lands — everything
        # between merge_t and done_t is morsel-order merge + finalize
        self.merge_t = time.perf_counter()
        if self._error is None and self.morsel_fn is not None:
            try:
                # merge in MORSEL order, not completion order: the served
                # result must not depend on which pool finished first
                sums, ovf = merge_morsel_partials(
                    [self._partials[i] for i in range(len(self.morsels))])
                self.result = self._ready(self.finalize(sums, ovf))
            except BaseException as e:  # noqa: BLE001
                self._error = e
        # stamp completion HERE, not when a waiter gets around to joining:
        # per-query latency must not include time spent waiting on other
        # tasks in the drain loop
        self.done_t = time.perf_counter()
        if tracing.tracing_enabled() and self.morsel_fn is not None:
            tracing.tracer().add_complete(
                "merge.partials", "scheduler", self.merge_t, self.done_t,
                trace_id=self.trace_id, n_morsels=len(self.morsels))
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> Dict[str, jax.Array]:
        if not self._done.wait(timeout):
            raise TimeoutError("query task did not complete in time")
        if self._error is not None:
            raise self._error
        return self.result


@dataclass
class WorkerPool:
    """The NUMA-socket analog: a contiguous shard slice + pinned workers."""

    pool_id: int
    shard_lo: int                 # [shard_lo, shard_hi) of the device mesh
    shard_hi: int
    executed: int = 0             # morsels run by this pool's workers
    steals: int = 0               # morsels this pool stole from another
    queue: deque = field(default_factory=deque, repr=False)
    # fault-tolerance state (mutated under the scheduler's condition)
    dead: bool = False            # killed: workers exited, no new work
    quarantined: bool = False     # straggler/hang: avoided by dispatch
    heartbeat_t: float = 0.0      # last worker take/finish (perf_counter)
    inflight: int = 0             # morsels currently executing
    ewma_s: float = 0.0           # EWMA morsel service time (ft.py idiom)
    samples: int = 0

    @property
    def live(self) -> bool:
        return not (self.dead or self.quarantined)


class WorkerLeakError(RuntimeError):
    """close() could not join every worker thread — a wedged pool would
    otherwise leak threads invisibly across tests/sessions."""

    def __init__(self, unjoined: List[str]):
        super().__init__(f"unjoined worker threads after close(): "
                         f"{', '.join(unjoined)}")
        self.unjoined = list(unjoined)


@dataclass
class SchedulerStats:
    morsels_dispatched: int = 0
    tasks: int = 0
    executed_per_pool: Tuple[int, ...] = ()
    steals_per_pool: Tuple[int, ...] = ()
    requeued: int = 0             # morsels moved off dead/quarantined pools
    dead_pools: Tuple[int, ...] = ()
    quarantined_pools: Tuple[int, ...] = ()   # includes dead pools
    pool_ewma_s: Tuple[float, ...] = ()

    @property
    def steals(self) -> int:
        return sum(self.steals_per_pool)


class MorselScheduler:
    """Dispatch QueryTasks to socket-pinned pools under a ThreadPlacement.

    ``submit(task)`` enqueues the task's morsels per the placement policy
    and returns immediately; ``task.wait()`` joins. Pools steal from the
    longest backlog when their own deque runs dry (counted). The
    scheduler can be constructed ``started=False`` so tests can stage a
    backlog before any worker runs."""

    def __init__(self, n_pools: int = 2, workers_per_pool: int = 2,
                 placement: ThreadPlacement = ThreadPlacement.OS_DEFAULT,
                 morsel_rows: Optional[int] = None, steal: bool = True,
                 n_shards: Optional[int] = None, started: bool = True,
                 faults=None, straggler_threshold: float = 4.0,
                 straggler_warmup: int = 3, hang_after_s: float = 30.0):
        if n_pools < 1 or workers_per_pool < 1:
            raise ValueError("need at least one pool and one worker")
        self.placement = placement
        self.morsel_rows = morsel_rows
        self.steal = steal
        self.faults = faults                # ServiceFaultInjector | None
        self.straggler_threshold = straggler_threshold
        self.straggler_warmup = straggler_warmup
        self.hang_after_s = hang_after_s
        shards = jax.device_count() if n_shards is None else n_shards
        per = max(1, shards // n_pools)
        now = time.perf_counter()
        self.pools = [WorkerPool(i, min(i * per, shards),
                                 min((i + 1) * per, shards) if i < n_pools - 1
                                 else shards, heartbeat_t=now)
                      for i in range(n_pools)]
        self._cv = threading.Condition()
        self._rr = 0                        # OS_DEFAULT round-robin cursor
        self._sparse_base = 0               # SPARSE per-task stripe offset
        self._tasks = 0
        self._dispatched = 0
        self._requeued = 0
        self._closed = False
        self._threads: List[threading.Thread] = []
        self._workers_per_pool = workers_per_pool
        if started:
            self.start()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._threads:
            return
        now = time.perf_counter()
        for pool in self.pools:
            pool.heartbeat_t = now
            for w in range(self._workers_per_pool):
                t = threading.Thread(
                    target=self._worker, args=(pool,),
                    name=f"pool{pool.pool_id}-w{w}", daemon=True)
                t.start()
                self._threads.append(t)

    def close(self, timeout: float = 5.0) -> List[str]:
        """Stop workers, drain, join. Returns the names of worker threads
        that did NOT join within ``timeout`` — a wedged pool must be a
        visible report, never a silent daemon-thread leak (the facade
        raises WorkerLeakError on a non-empty report)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        unjoined: List[str] = []
        for t in self._threads:
            t.join(timeout=timeout)
            if t.is_alive():
                unjoined.append(t.name)
        self._threads = []
        return unjoined

    def __enter__(self) -> "MorselScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- task construction --------------------------------------------------
    def build_task(self, plan: L.LogicalPlan, tables,
                   ctx: Optional[ExecutionContext] = None) -> QueryTask:
        """Compile (through the plan cache) and wrap a plan as a task.

        Decomposable plans (distributive Aggregate over a Scan chain, no
        mesh) become per-morsel partials when ``morsel_rows`` is set;
        planner-marked join-probe pipelines become split-probe tasks
        (build sides once per task, probe morsels per pool — see the
        module docstring); all others become a single whole-plan morsel
        whose result is bit-identical to serial execution by
        construction. Whole-plan dispatch goes through
        ``planner.compile_plan`` and therefore the EXPLICIT physical plan
        (lowered once, cached as the plan-cache value; inspectable via
        ``task.physical``) — the scheduler never re-derives strategy
        decisions at dispatch time. The whole-plan executable is only
        compiled on that fallback path — a split task must not push a
        never-invoked entry into the bounded plan cache."""
        ctx = ctx or ExecutionContext()
        # fault hook: one dispatch ordinal per build attempt (retries
        # re-tick); an injected build failure raises HERE, before any
        # compile work, exactly like a plan naming a missing table
        ordinal = (self.faults.begin_dispatch()
                   if self.faults is not None else None)
        if self.morsel_rows is not None and ctx.mesh is None:
            split = (_morsel_decompose(plan, tables, ctx)
                     or _probe_split_decompose(plan, tables, ctx))
            if split is not None:
                morsel_fn, finalize, n_rows = split
                task = QueryTask(None, tables, morsel_fn, finalize,
                                 morsel_slices(n_rows, self.morsel_rows))
                task.fault_ordinal = ordinal
                return task
        task = QueryTask(planner.compile_plan(plan, tables, ctx), tables)
        task.fault_ordinal = ordinal
        return task

    # -- dispatch -----------------------------------------------------------
    def _live_pools(self) -> List[WorkerPool]:
        """Call under the condition: pools eligible for new work."""
        return [p for p in self.pools if p.live]

    def submit(self, task: QueryTask) -> QueryTask:
        with self._cv:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            live = self._live_pools()
            if not live:
                raise RuntimeError("no live worker pools — every pool is "
                                   "dead or quarantined")
            self._tasks += 1
            task.submit_t = time.perf_counter()
            dense_pool = min(live, key=lambda p: len(p.queue)).pool_id
            # SPARSE stripes a task's morsels across every live pool,
            # starting from a per-task rotating base — otherwise
            # single-morsel (whole-plan) tasks would all land on pool 0
            # (seq is always 0) and the other pools could only work via
            # steals
            sparse_base = self._sparse_base
            self._sparse_base += 1
            for m in task.morsels:
                if self.placement == ThreadPlacement.DENSE:
                    m.home_pool = dense_pool
                elif self.placement == ThreadPlacement.SPARSE:
                    m.home_pool = live[(sparse_base + m.seq)
                                       % len(live)].pool_id
                else:                       # OS_DEFAULT: arrival order
                    m.home_pool = live[self._rr % len(live)].pool_id
                    self._rr += 1
                self.pools[m.home_pool].queue.append(m)
                self._dispatched += 1
            self._cv.notify_all()
        # fault hook AFTER enqueue: a pool kill scheduled at this ordinal
        # fires mid-round — the task's morsels may sit on the killed
        # pool's queue until check_pools() requeues them
        if self.faults is not None and task.fault_ordinal is not None:
            self.faults.on_submit(task.fault_ordinal, task, self)
        return task

    # -- fault tolerance ----------------------------------------------------
    def kill_pool(self, pool_id: int) -> None:
        """Drill analog of losing a socket/host: the pool's workers exit
        (in-flight morsels finish — threads cannot be preempted — but no
        new morsel is taken) and its backlog waits for check_pools() to
        requeue it onto survivors."""
        with self._cv:
            self.pools[pool_id].dead = True
            self._cv.notify_all()

    def quarantine_pool(self, pool_id: int) -> None:
        """Mark a pool unschedulable and requeue its backlog (manual
        override of the straggler/hang detectors)."""
        with self._cv:
            pool = self.pools[pool_id]
            if sum(p.live for p in self.pools) > 1 or not pool.live:
                pool.quarantined = True
            self._requeue_locked()
            self._cv.notify_all()

    def _requeue_locked(self) -> None:
        """Move every morsel queued on a non-live pool onto live pools,
        round-robin, preserving order (call under the condition)."""
        moved: List[_Morsel] = []
        for p in self.pools:
            if not p.live and p.queue:
                moved.extend(p.queue)
                p.queue.clear()
        if not moved:
            return
        live = self._live_pools()
        if not live:                 # nothing to requeue onto; put back
            self.pools[moved[0].home_pool].queue.extend(moved)
            return
        for i, m in enumerate(moved):
            target = live[i % len(live)]
            m.home_pool = target.pool_id
            target.queue.append(m)
        self._requeued += len(moved)

    def check_pools(self, now: Optional[float] = None) -> List[int]:
        """Heartbeat + EWMA sweep (the serving port of ft.py's
        StragglerDetector): quarantine pools that are dead, hung (backlog
        but no heartbeat within ``hang_after_s``), or straggling (EWMA
        morsel time > ``straggler_threshold`` x the live-pool median),
        then requeue their backlogs onto survivors. Never quarantines the
        last live pool. Returns newly quarantined pool ids."""
        now = time.perf_counter() if now is None else now
        newly: List[int] = []
        with self._cv:
            for p in self.pools:
                if not p.live:
                    continue
                if sum(q.live for q in self.pools) <= 1:
                    break
                if p.dead:
                    continue
                if p.queue and now - p.heartbeat_t > self.hang_after_s:
                    p.quarantined = True
                    newly.append(p.pool_id)
            ready = [p for p in self.pools
                     if p.live and p.samples >= self.straggler_warmup]
            if len(ready) >= 2:
                for p in ready:
                    if sum(q.live for q in self.pools) <= 1:
                        break
                    # median of the PEERS, not the whole fleet: with few
                    # pools a fleet median that includes the straggler is
                    # dragged up by it (2 pools: median == mean, and the
                    # threshold could mathematically never trip)
                    med = float(np.median([q.ewma_s for q in ready
                                           if q is not p]))
                    if med > 0 and p.ewma_s > self.straggler_threshold * med:
                        p.quarantined = True
                        newly.append(p.pool_id)
            self._requeue_locked()
            if newly:
                self._cv.notify_all()
        if newly and tracing.tracing_enabled():
            tr = tracing.tracer()
            for pid in newly:
                tr.instant("pool.quarantine", "scheduler",
                           pid=f"pool{pid}")
            tr.flight_dump("pool.quarantine", pools=list(newly))
        return newly

    def run(self, plan: L.LogicalPlan, tables,
            ctx: Optional[ExecutionContext] = None) -> Dict[str, jax.Array]:
        """Convenience: build, submit, wait."""
        return self.submit(self.build_task(plan, tables, ctx)).wait()

    # -- workers ------------------------------------------------------------
    def _take(self, pool: WorkerPool) -> Optional[_Morsel]:
        """Called under the lock: own head first, else steal the tail of
        the longest LIVE backlog (classic work stealing). A dead pool
        takes nothing (its workers are exiting); a quarantined pool only
        drains its own queue — a straggler must not slow other pools'
        work by stealing it."""
        if pool.dead:
            return None
        if pool.queue:
            return pool.queue.popleft()
        if not self.steal or pool.quarantined:
            return None
        victim = max((p for p in self.pools if p is not pool and p.live),
                     key=lambda p: len(p.queue), default=None)
        if victim is not None and victim.queue:
            pool.steals += 1
            m = victim.queue.pop()
            if tracing.tracing_enabled():
                tracing.tracer().instant(
                    "morsel.steal", "scheduler", trace_id=m.task.trace_id,
                    pid=f"pool{pool.pool_id}", victim=victim.pool_id,
                    seq=m.seq)
            return m
        return None

    def _worker(self, pool: WorkerPool) -> None:
        while True:
            with self._cv:
                m = self._take(pool)
                while m is None and not self._closed and not pool.dead:
                    self._cv.wait(timeout=0.1)
                    m = self._take(pool)
                if m is None:               # closed and drained, or killed
                    return
                pool.executed += 1
                pool.inflight += 1
                pool.heartbeat_t = time.perf_counter()
            delay = (self.faults.morsel_delay(pool.pool_id)
                     if self.faults is not None else 0.0)
            if delay > 0.0:
                time.sleep(delay)
            t0 = time.perf_counter()
            m.task._run_morsel(m, pool.pool_id)
            t1 = time.perf_counter()
            if tracing.tracing_enabled():
                tracing.tracer().add_complete(
                    "morsel.run", "scheduler", t0, t1,
                    trace_id=m.task.trace_id, pid=f"pool{pool.pool_id}",
                    seq=m.seq, rows=m.length)
            dt = t1 - t0 + delay                # EWMA must see the straggle
            with self._cv:
                pool.inflight -= 1
                pool.heartbeat_t = time.perf_counter()
                pool.samples += 1
                pool.ewma_s = (dt if pool.samples == 1
                               else 0.3 * dt + 0.7 * pool.ewma_s)

    def stats(self) -> SchedulerStats:
        with self._cv:
            return SchedulerStats(
                morsels_dispatched=self._dispatched, tasks=self._tasks,
                executed_per_pool=tuple(p.executed for p in self.pools),
                steals_per_pool=tuple(p.steals for p in self.pools),
                requeued=self._requeued,
                dead_pools=tuple(p.pool_id for p in self.pools if p.dead),
                quarantined_pools=tuple(p.pool_id for p in self.pools
                                        if not p.live),
                pool_ewma_s=tuple(p.ewma_s for p in self.pools))


# ---------------------------------------------------------------------------
# morsel decomposition of distributive-aggregate plans
# ---------------------------------------------------------------------------
_DISTRIBUTIVE = ("sum", "avg", "count")


def _scan_chain(root: L.Node) -> Optional[Tuple[L.Scan, List[L.Node]]]:
    """(scan, [transforms leaf->root]) when root's child chain is pure
    Scan/Filter/Project; None otherwise."""
    chain: List[L.Node] = []
    node = root
    while True:
        if isinstance(node, L.Scan):
            return node, list(reversed(chain))
        if isinstance(node, (L.Filter, L.Project)):
            chain.append(node)
            node = node.child
            continue
        return None


def _morsel_decompose(plan: L.LogicalPlan, tables, ctx: ExecutionContext):
    """(morsel_fn, finalize, n_rows) for a decomposable plan, else None.

    Decomposable = root Aggregate whose aggregates are all distributive
    sums (sum/avg/count) over a Scan/Filter/Project chain. The morsel
    partial is the stacked (n_groups, C) sums table over one row range —
    the same physical primitive the planner lowers Aggregates onto — so
    merged morsel results reuse finalize_stacked and can never drift from
    the planner's semantics. NOTE: per-morsel partial sums merge in morsel
    order, which is a DIFFERENT float summation order than the one-pass
    serial plan — the split path trades bit-identity for intra-query
    parallelism (the whole-plan path keeps bit-identity)."""
    root = plan.root
    if not isinstance(root, L.Aggregate):
        return None
    if any(op not in _DISTRIBUTIVE for _, (op, _c) in root.aggs):
        return None
    chain = _scan_chain(root.child)
    if chain is None:
        return None
    scan_node, transforms = chain
    # snapshot the cost profile ONCE: it keys the cache and is baked into
    # the traced closure (same stale-constants hazard as compile_plan)
    profile = planner.current_cost_profile()
    n_rows = next(iter(tables[scan_node.table].values())).shape[0]
    if root.key is None:
        n_groups = 1
    elif isinstance(root.n_groups, L.TableRows):
        n_groups = next(iter(
            tables[root.n_groups.table].values())).shape[0]
    else:
        n_groups = int(root.n_groups)
    aggs = dict(root.aggs)

    def partial(tbls, lo, *, length):
        t = Table(morsel_slice_columns(tbls[scan_node.table], lo, length))
        for node in transforms:
            if isinstance(node, L.Filter):
                t = t.filter(planner.eval_expr(node.pred, t))
            else:
                t = t.with_columns(**{n: planner.eval_expr(e, t)
                                      for n, e in node.cols})
        if root.key is None:
            t = t.with_columns(_g0=jnp.zeros((length,), jnp.int32))
            key = "_g0"
        else:
            key = root.key
        keys, cols, src = stacked_columns(t, key, n_groups, aggs)
        layout = planner.choose_aggregate(length, n_groups, len(cols),
                                          ctx.executor, profile)
        return morsel_group_sums(keys, cols, n_groups, layout=layout,
                                 mode=ctx.mode,
                                 n_partitions=ctx.n_partitions,
                                 capacity_factor=ctx.capacity_factor)

    # one jitted executable per (plan, ctx, signature); per-morsel widths
    # specialize via the static ``length`` argument
    fn = planner.cached_executable(
        ("morsel", plan, ctx.cache_key(), planner.table_signature(tables),
         profile),
        lambda: jax.jit(partial, static_argnames=("length",)))

    def morsel_fn(tbls, lo, *, length, pool=0):
        del pool             # partial sums need no pool-local structures
        return fn(tbls, lo, length=length)

    src = [c for _, (op, c) in root.aggs
           if op in ("sum", "avg")]
    src = list(dict.fromkeys(src))          # distinct, insertion order

    def finalize(sums, overflow):
        out = finalize_stacked(aggs, src, sums, _no_order_stats)
        out["_overflow"] = overflow.astype(jnp.int32)
        if plan.outputs is not None:
            out = {k: out[k] for k in plan.outputs}
        return out

    return morsel_fn, finalize, n_rows


def _no_order_stats(op, col):
    raise ValueError(f"order statistic {op!r} is not distributive — "
                     "plan should not have been morsel-decomposed")


# ---------------------------------------------------------------------------
# split-probe decomposition of planner-marked join pipelines
# ---------------------------------------------------------------------------
def _build_probe_split(plan: L.LogicalPlan, ctx: ExecutionContext, tables,
                       profile):
    """Plan-cache value for a split-probe candidate: the string "whole"
    when the planner declines (cached, so repeat dispatches skip the
    re-analysis), else (probe_split, prelude_jit, morsel_jit, final_jit).

    Three executables because the three phases run at different
    cadences: the prelude (join build sides, Attach sources) once per
    TASK, the probe pipeline once per MORSEL (row-range specialized via
    the static ``length``, like the distributive-aggregate path), and
    the finalize (aggregate + TopK over the merged intermediate table)
    once per task after the morsel-order merge."""
    phys = planner.lower(plan, ctx,
                         {t: next(iter(c.values())).shape[0]
                          for t, c in tables.items()}, profile)
    split = planner.probe_split(phys)
    if split is None:
        return "whole"
    preludes = split.preludes

    def run_prelude(tbls, indexes):
        ex = planner._LocalExecutor(tbls, ctx, indexes, profile)
        vals = []
        for p in preludes:
            v = ex.run(p.node)
            # Tables serialize as (columns, mask) across the jit
            # boundary — index_cache is host state and is re-seeded per
            # morsel from the pool replicas instead
            vals.append((v.columns, v.mask) if p.is_table else v)
        return vals, ex.overflow

    def run_morsel(tbls, prelude_vals, replicas, lo, *, length):
        ex = planner._LocalExecutor(tbls, ctx, {}, profile)
        ri = 0
        for p, v in zip(preludes, prelude_vals):
            if p.is_table:
                cols, mask = v
                cache = {}
                if p.index is not None:
                    # the pool-local build replica seeds key_index, so a
                    # sorted join never re-argsorts inside a morsel
                    cache = {p.index[1]: replicas[ri]}
                    ri += 1
                ex._memo[p.node] = Table(dict(cols), mask, cache)
            else:
                ex._memo[p.node] = v
        ex._memo[split.scan] = Table(
            morsel_slice_columns(tbls[split.scan.table], lo, length))
        t = ex.run(split.pipeline_root)
        return (t.columns, t.mask), ex.overflow

    def run_final(merged, overflow):
        cols, mask = merged
        ex = planner._LocalExecutor({}, ctx, {}, profile)
        ex._memo[split.pipeline_root] = Table(dict(cols), mask)
        ex.overflow = ex.overflow + overflow
        out = dict(ex.run(split.root))
        out["_overflow"] = ex.overflow
        if split.outputs is not None:
            out = {k: out[k] for k in split.outputs}
        return out

    return (split, jax.jit(run_prelude),
            jax.jit(run_morsel, static_argnames=("length",)),
            jax.jit(run_final))


def _probe_split_decompose(plan: L.LogicalPlan, tables,
                           ctx: ExecutionContext):
    """(morsel_fn, finalize, n_rows) for a planner-marked split-probe
    join pipeline, else None.

    The division of labor mirrors the paper's socket-local working sets:
    the build side is materialized ONCE per task (prelude), its pooled
    sort index replicated ONCE per worker pool
    (JoinIndexPool.replica), and every probe morsel — wherever stealing
    lands it — probes the executing pool's replica. Per-morsel outputs
    are row slices of the serial intermediate table, so the morsel-order
    concat + finalize reproduces serial ``run_query`` bit-for-bit (the
    distributive-aggregate path cannot promise that; this path can,
    because the merge is a concat, not a float re-ordering)."""
    profile = planner.current_cost_profile()
    bundle = planner.cached_executable(
        ("morsel-probe", plan, ctx.cache_key(),
         planner.table_signature(tables), profile),
        lambda: _build_probe_split(plan, ctx, tables, profile))
    if bundle == "whole":
        return None
    split, prelude_jit, morsel_jit, final_jit = bundle
    join_pool = planner.join_index_pool()
    indexes = {f"{t}.{c}": join_pool.get(t, c, tables[t][c])
               for t, c in planner.required_indexes(plan.root)}
    # the prelude runs ONCE per task — its values are closed over by
    # every morsel of this task
    prelude_vals, prelude_ovf = prelude_jit(tables, indexes)
    specs = [p.index for p in split.preludes if p.index is not None]

    def morsel_fn(tbls, lo, *, length, pool=0):
        # per-POOL build replicas (an LRU hit after each pool's first
        # morsel), fetched by the EXECUTING pool — including on steals
        replicas = [join_pool.replica(t, c, tbls[t][c], pool)
                    for t, c in specs]
        return morsel_jit(tbls, prelude_vals, replicas, lo, length=length)

    def finalize(merged, overflow):
        return final_jit(merged, overflow + prelude_ovf)

    return morsel_fn, finalize, split.n_rows
