"""Cost-based physical planner for the logical plan IR (plan.py).

This is the "execution strategy changes underneath" half of the paper's
application-agnostic thesis: one logical plan, many physical realizations.
Since PR 5 the planner is a genuine THREE-LAYER pipeline:

  logical plan  --lower(plan, ctx)-->  PHYSICAL PLAN  --walk-->  executors

``lower`` turns each logical node into an explicit physical operator
(physical.py) with every strategy decision resolved to a plain field —
join algorithm, aggregate layout, Exchange kind, compaction point — from
static shape metadata and the ``ExecutionContext``:

  Aggregate   -> XLA segment ops | dense-chunked fused kernel |
                 range-partitioned fused kernel (``choose_aggregate``, a
                 documented cost model over (n_rows, n_groups, n_cols))
  Join        -> sorted-index searchsorted gather (build argsorts hoisted
                 out of the compiled plan by ``JoinIndexPool``) | the
                 kernels/join_probe broadcast-compare kernel when the MXU
                 executes it (``choose_join``)
  dist Join   -> PJoin over Exchange(broadcast) | PJoin over two
                 Exchange(hash) routings, chosen by a wire-cost model
                 (``dist_join_costs``) over physical row counts
  dist Agg    -> PPartialAggregate + per-policy merge collectives; under
                 INTERLEAVE the record routing is an explicit
                 Exchange(hash) that three movement REWRITES then improve:

  (1) aggregate PUSH-DOWN: a distributive Aggregate splits into
      PPartialAggregate below a hash Exchange + merge above it, shipping
      ~n_groups partial rows per shard instead of n_rows records
      (physical.pushdown_profitable prices the split);
  (2) ROUTE-ONCE: structurally identical hash Exchanges deduplicate via
      executor memoization, and an Exchange whose child is already
      co-located by the same key (an upstream partitioned join on that
      key) is elided entirely — join AND aggregate route one time
      (physical.routes_once / placed_key);
  (3) occupancy-aware COMPACT: a routed buffer is cut back to
      COMPACT_MARGIN x its estimated alive rows before being routed
      again (engine.compact_routed_rows), so chained partitioned joins
      stop growing padding by a capacity_factor per hop
      (physical.maybe_compact).

``explain`` reports one Decision per physical Join/Aggregate/Exchange/
Compact (estimated moved rows included); ``explain_physical`` renders the
whole physical tree (golden-snapshot tested). The executors
(_LocalExecutor / _DistributedExecutor) are thin walkers over the
physical IR: they dispatch on node type and call the engine/columnar
primitives the node names — every placement policy, median strategy, and
routing plan that existed before the physical layer executes the same
primitives in the same order (the parity grids pin this).

The cost model is deliberately simple — everything is expressed in
equivalent passes over the input rows:

  cost(xla)         = C                       (one segment op per stacked
                                               column; C = count + distinct
                                               sum/avg sources)
  cost(dense)       = 1.2 + 0.45 * C          (one fused sweep; per-column
                                               slope for the wider MXU dot;
                                               valid iff n_groups <=
                                               profile.dense_group_limit)
  cost(partitioned) = cost(dense)
                      + 0.25 * log2(n_rows)   (the range-partition argsort)

Compiled plans live in a bounded LRU cache keyed by (logical plan
structure, context key, table shape signature, cost profile); the cache
VALUE is the (physical plan, jitted executable, wire volume, kernel
joins) tuple, so the physical tree is inspectable for every cached entry.
Join build-side argsort indexes, and the kernel join's partition layouts
of base-column keys, are pooled across calls keyed on column-array
*identity* and enter the compiled plan as traced arguments.
"""
from __future__ import annotations

import functools
import json
import math
import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.analytics import physical as PH
from repro.analytics import plan as L
from repro.analytics import telemetry
from repro.analytics import tracing
from repro.analytics.columnar import (DENSE_GROUP_LIMIT,
                                      KERNEL_JOIN_MAX_ROWS, JoinSide, Table,
                                      finalize_stacked, group_aggregate,
                                      join_layout, join_side_layout,
                                      pkfk_join, pkfk_join_kernel,
                                      segment_distinct, segment_median,
                                      segment_order_stat, segment_quantile,
                                      stacked_columns, stacked_group_sums)
from repro.analytics.engine import (compact_routed_rows, gather_rows,
                                    interleave_group_median,
                                    interleave_group_sums,
                                    merge_partial_table,
                                    placed_group_median,
                                    pushdown_group_sums,
                                    radix_route_table_rows,
                                    replicated_group_median, route_owner,
                                    route_table_rows, routing_capacity)
from repro.analytics.plan import (holistic_selector, is_holistic,
                                  parse_quantile)
from repro.core.config import PlacementPolicy
from repro.kernels.common import kernel_mode


# ---------------------------------------------------------------------------
# execution context
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ExecutionContext:
    """Everything the planner may vary without touching the logical plan.

    ``executor``: "xla" forces segment ops, "kernel" forces the fused
    sweeps (the Fig 8/9 untuned/tuned axis), "cost" lets the cost model
    choose per Aggregate. ``join``: None = cost-based, or force "sorted" /
    "kernel". A (mesh, policy) pair selects the distributed placement
    backend; ``axis`` names the sharded mesh axis. ``dist_join``: None =
    the wire-cost model chooses per distributed Join, or force
    "broadcast" / "partitioned". ``dist_route`` picks the owner function
    for partitioned-join routing: "hash" (default; multiplicative hash,
    robust to clustered/strided key spaces) or "modulo" (the legacy
    dense-id map — dist_hash_join pins it to reproduce the retired W3
    plans bit-identically). ``exchange_impl`` picks the routing LAYOUT
    pass of key-routing hash Exchanges: "cost" (default; exchange_costs
    chooses per Exchange from the routed rows), or force "argsort" /
    "radix" (the radix-partition histogram kernel path — bit-identical
    results, different layout cost). ``agg_pushdown``: None = push
    distributive aggregates below the exchange when n_groups < per-shard
    rows, or force True/False. ``route_once``: elide exchanges whose
    child is already placed by the same key (False disables).
    ``compact``: None = insert occupancy-aware Compact nodes before
    re-routing padded buffers (COMPACT_MARGIN occupancy headroom), False
    disables, a float overrides the margin. ``dist_topk`` picks the
    distributed TopK lowering: "cost" (default; topk_costs chooses from
    the group-table size vs the candidate volume), or force "replicated"
    (select on the merged replicated group table) / "candidates" (each
    shard selects local top-k candidates over the group slots it owns
    and a gather Exchange converges only k * n_shards candidate rows —
    bit-identical results, no group-table replication priced on the
    TopK)."""

    executor: str = "cost"
    mode: Optional[str] = None               # kernel lowering mode
    mesh: Optional[Mesh] = None
    policy: Optional[PlacementPolicy] = None
    axis: str = "data"
    join: Optional[str] = None
    n_partitions: int = 64
    capacity_factor: float = 2.0
    dist_join: Optional[str] = None
    dist_route: str = "hash"
    exchange_impl: str = "cost"
    dist_topk: str = "cost"
    agg_pushdown: Optional[bool] = None
    route_once: bool = True
    compact: Union[None, bool, int, float] = None

    def __post_init__(self):
        if self.executor not in ("xla", "kernel", "cost"):
            raise ValueError(f"unknown executor {self.executor!r}")
        if self.join not in (None, "sorted", "kernel"):
            raise ValueError(f"unknown join strategy {self.join!r}")
        if self.dist_join not in (None, "broadcast", "partitioned"):
            raise ValueError(
                f"unknown distributed join strategy {self.dist_join!r}")
        if self.dist_route not in ("hash", "modulo"):
            raise ValueError(f"unknown routing method {self.dist_route!r}")
        if self.exchange_impl not in ("argsort", "radix", "cost"):
            raise ValueError(
                f"unknown exchange impl {self.exchange_impl!r}")
        if self.dist_topk not in ("cost", "replicated", "candidates"):
            raise ValueError(
                f"unknown distributed TopK lowering {self.dist_topk!r}")
        if (not isinstance(self.compact, bool) and self.compact is not None
                and (not isinstance(self.compact, (int, float))
                     or self.compact < 1.0)):
            raise ValueError("compact must be None, a bool, or a numeric "
                             f"margin >= 1.0; got {self.compact!r}")

    def cache_key(self) -> Tuple:
        mesh_key = None
        if self.mesh is not None:
            mesh_key = (tuple(self.mesh.shape.items()),
                        tuple(str(d) for d in self.mesh.devices.flat))
        # compact keys by its RESOLVED margin (None when disabled):
        # compact=True, None and 1.5 lower to identical physical plans,
        # while the raw values would collide bool/int spellings of
        # DIFFERENT margins (True == 1 == 1.0 in Python)
        return (self.executor, self.mode, mesh_key, self.policy, self.axis,
                self.join, self.n_partitions, self.capacity_factor,
                self.dist_join, self.dist_route, self.exchange_impl,
                self.dist_topk, self.agg_pushdown, self.route_once,
                self.compact_margin())

    # -- rewrite-knob resolution -------------------------------------------
    def compact_margin(self) -> Optional[float]:
        """Occupancy headroom for Compact nodes, or None when disabled."""
        if self.compact is False:
            return None
        if self.compact is None or self.compact is True:
            return COMPACT_MARGIN
        return float(self.compact)           # numeric margin override


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
FUSED_FIXED = 1.2        # fused sweep: one-hot build + table merge overhead
FUSED_PER_COL = 0.45     # marginal pass-equivalent per stacked column
SORT_PASS_FACTOR = 0.25  # argsort pass-equivalents per log2(n_rows)
DIST_ROUTE_FACTOR = 1.5  # partitioned-join routing overhead per moved row
#   (the argsort-by-owner layout + capacity padding both sides pay, relative
#   to the raw all-gather bytes of the broadcast lowering; measured by
#   scripts/calibrate_costs.py --dist from the observed crossover)
COMPACT_MARGIN = 1.5     # Compact budget: margin x estimated alive rows.
#   Routing capacity_factor absorbs per-destination ROUTING skew; this
#   margin absorbs occupancy-estimate error of an already-routed buffer.
#   Alive rows beyond the budget surface as _overflow, never vanish.
RADIX_ROUTE_FACTOR = 2.5  # radix Exchange layout: flat pass-equivalents
#   (block histograms + prefix sums are O(n) regardless of n_rows), vs the
#   argsort layout's sort_pass_factor * log2(n_rows) — crossover at
#   2^(radix/sort) ~ 1024 per-shard rows with the hand-set constants;
#   scripts/calibrate_costs.py --exchange fits it from the measured one.
FILTER_SELECTIVITY = 0.75  # est alive fraction surviving one PFilter.
#   Feeds three pricing decisions: Exchange.moved_rows (the priced wire
#   payload), the aggregate push-down crossover (pushdown_profitable is
#   priced on est * selectivity^filters, not physical rows), and the
#   Compact budget (maybe_compact folds it into the margin, CLAMPED at
#   1.0 x est so a selectivity prior can never shrink a buffer below its
#   estimated alive rows and surface phantom overflow — alive rows beyond
#   any budget still land in _overflow, never vanish).
#   telemetry.refresh_profile replaces it with the observed
#   alive_out/alive_in ratio, so all three decisions adapt to drift.
MORSEL_SPLIT_ROWS = 2048  # smallest LOCAL sorted-join probe side worth
#   splitting into per-pool morsels: below this, per-morsel dispatch
#   overhead (a jit call + partial merge per morsel) beats the
#   parallelism. Marks PJoin.morsel_split during lowering; the serving
#   scheduler's probe_split path honors the mark. Fitted by
#   scripts/calibrate_costs.py --morsel from the measured crossover.


@dataclass(frozen=True)
class CostProfile:
    """Pass-equivalent cost constants, either the hand-set defaults or a
    measured profile (scripts/calibrate_costs.py). Frozen/hashable so the
    active profile participates in the plan-cache key — plans compiled
    under one profile are never served after the constants change.
    ``dense_group_limit`` bounds the dense fused layout's key domain
    (measured by the --sweep-groups calibration; defaults to the VMEM
    model constant) and ``partition_capacity_factor``, when fitted,
    overrides the context's capacity factor for the range-partitioned
    aggregate layout only (routing capacities stay on the context).
    ``compact_margin``, when set (telemetry.refresh_profile fits it from
    observed Compact occupancy), replaces the hand-set COMPACT_MARGIN for
    contexts that leave ``compact`` at its default None — an explicit
    context override always wins."""

    fused_fixed: float = FUSED_FIXED
    fused_per_col: float = FUSED_PER_COL
    sort_pass_factor: float = SORT_PASS_FACTOR
    dist_route_factor: float = DIST_ROUTE_FACTOR
    radix_route_factor: float = RADIX_ROUTE_FACTOR
    filter_selectivity: float = FILTER_SELECTIVITY
    dense_group_limit: int = DENSE_GROUP_LIMIT
    morsel_split_rows: int = MORSEL_SPLIT_ROWS
    partition_capacity_factor: Optional[float] = None
    compact_margin: Optional[float] = None
    source: str = "builtin"


_COST_PROFILE = CostProfile()
_COST_PROFILE_LOCK = threading.Lock()


def current_cost_profile() -> CostProfile:
    return _COST_PROFILE


def set_cost_profile(profile: Optional[CostProfile]) -> CostProfile:
    """Install a cost profile (None restores the hand-set defaults)."""
    global _COST_PROFILE
    with _COST_PROFILE_LOCK:
        _COST_PROFILE = profile or CostProfile()
    return _COST_PROFILE


def load_cost_profile(path: str) -> CostProfile:
    """Install the measured constants written by scripts/calibrate_costs.py.

    The JSON carries {"fused_fixed", "fused_per_col", "sort_pass_factor"}
    plus, when the respective sweeps ran, "dist_route_factor",
    "dense_group_limit" and "partition_capacity_factor" (extra keys —
    backend, raw timings — are kept as provenance in ``source``); when
    present they replace the hand-set defaults for every subsequent
    planning decision."""
    with open(path) as f:
        raw = json.load(f)
    pcf = raw.get("partition_capacity_factor")
    cm = raw.get("compact_margin")
    return set_cost_profile(CostProfile(
        compact_margin=(None if cm is None else float(cm)),
        fused_fixed=float(raw["fused_fixed"]),
        fused_per_col=float(raw["fused_per_col"]),
        sort_pass_factor=float(raw.get("sort_pass_factor", SORT_PASS_FACTOR)),
        dist_route_factor=float(raw.get("dist_route_factor",
                                        DIST_ROUTE_FACTOR)),
        radix_route_factor=float(raw.get("radix_route_factor",
                                         RADIX_ROUTE_FACTOR)),
        filter_selectivity=float(raw.get("filter_selectivity",
                                         FILTER_SELECTIVITY)),
        dense_group_limit=int(raw.get("dense_group_limit",
                                      DENSE_GROUP_LIMIT)),
        morsel_split_rows=int(raw.get("morsel_split_rows",
                                      MORSEL_SPLIT_ROWS)),
        partition_capacity_factor=(None if pcf is None else float(pcf)),
        source=str(raw.get("backend", path))))


def aggregate_costs(n_rows: int, n_groups: int, n_cols: int,
                    profile: Optional[CostProfile] = None
                    ) -> Dict[str, float]:
    """Pass-equivalent cost of each physical Aggregate layout (see module
    docstring for the formulas). ``n_cols`` counts the measure columns:
    1 (COUNT/weights) + distinct sum/avg source columns. The constants come
    from ``profile`` — callers that cache on a profile snapshot must pass
    it explicitly so a concurrent recalibration cannot leak into a plan
    keyed under the old profile — or the active CostProfile."""
    p = profile or _COST_PROFILE
    fused = p.fused_fixed + p.fused_per_col * n_cols
    return {
        "xla": float(n_cols),
        "dense": fused if n_groups <= p.dense_group_limit else math.inf,
        "partitioned": fused + p.sort_pass_factor * math.log2(max(n_rows, 2)),
    }


def choose_aggregate(n_rows: int, n_groups: int, n_cols: int,
                     executor: str = "cost",
                     profile: Optional[CostProfile] = None) -> str:
    """Physical layout for one Aggregate: "xla" | "dense" | "partitioned"."""
    p = profile or _COST_PROFILE
    if executor == "xla":
        return "xla"
    if executor == "kernel":     # the tuned-path preference: always fused
        return "dense" if n_groups <= p.dense_group_limit else "partitioned"
    costs = aggregate_costs(n_rows, n_groups, n_cols, p)
    return min(costs, key=costs.get)


def choose_join(n_probe: int, n_build: int, ctx: ExecutionContext) -> str:
    """"sorted" (searchsorted gather) vs "kernel" (join_probe probe).

    The broadcast-compare probe only beats the gather when the MXU actually
    executes it — its reference lowering is an O(n_probe * n_build / P)
    compare — so the cost rule requires a compiled Pallas backend plus a
    probe side large enough to amortize the partitioning pass, and both
    sides below the kernel's KERNEL_JOIN_MAX_ROWS."""
    if ctx.join is not None:
        return ctx.join
    if (kernel_mode(ctx.mode) == "pallas" and ctx.executor != "xla"
            and n_probe >= (1 << 14) and n_build >= 512
            and max(n_probe, n_build) < KERNEL_JOIN_MAX_ROWS):
        return "kernel"
    return "sorted"


def dist_join_costs(n_probe: int, n_build: int, n_shards: int,
                    profile: Optional[CostProfile] = None
                    ) -> Dict[str, float]:
    """Row-transfer-equivalent cost of each distributed Join lowering.

    broadcast    all-gathers the build side: every shard receives the
                 (n-1)/n of the build rows it does not already hold —
                 n_build * (n-1) rows on the wire, independent of the
                 probe side. Cheap while the build side fits a socket's
                 share; it is the cross-socket traffic the paper's Fig 5-7
                 placement results penalize once it does not.
    partitioned  routes BOTH sides by join-key hash (all-to-all): each row
                 moves once with probability (n-1)/n, and both sides pay
                 the routing layout pass (argsort by owner + capacity
                 padding), modeled by the dist_route_factor multiplier.

    The crossover: partitioned wins once the build side outgrows roughly
    probe/(n-1) rows — i.e. for large build sides on wide meshes."""
    p = profile or _COST_PROFILE
    n = max(int(n_shards), 2)
    return {
        "broadcast": float(n_build) * (n - 1),
        "partitioned": (float(n_probe) + float(n_build)) * (n - 1) / n
                       * p.dist_route_factor,
    }


def choose_dist_join(n_probe: int, n_build: int, n_shards: int,
                     ctx: "ExecutionContext",
                     profile: Optional[CostProfile] = None) -> str:
    """"broadcast" (all-gather build) vs "partitioned" (route both sides)
    for one distributed Join, from global row counts.

    The lowering prices the PHYSICAL row counts each side holds BEFORE
    the movement rewrites touch them — for a probe that is itself the
    output of an upstream partitioned join, that includes the routed
    buffer's full capacity padding. Compact is inserted after this
    choice, so the partitioned estimate is conservative (pads the cost of
    rows compaction will reclaim), biasing borderline chained joins
    toward broadcast; pricing post-compact rows is a ROADMAP
    refinement."""
    if ctx.dist_join is not None:
        return ctx.dist_join
    if n_shards < 2:
        return "broadcast"       # nothing to move: routing is pure waste
    costs = dist_join_costs(n_probe, n_build, n_shards, profile)
    return min(costs, key=costs.get)


def exchange_costs(n_rows: int, profile: Optional[CostProfile] = None
                   ) -> Dict[str, float]:
    """Pass-equivalent LAYOUT cost of each hash-Exchange routing impl for
    ``n_rows`` per-shard routed rows. Both paths ship the same bytes and
    produce bit-identical buffers; what differs is how the send layout is
    built: "argsort" pays a stable sort (sort_pass_factor * log2(n)),
    "radix" pays a flat histogram + prefix-sum pass (radix_route_factor,
    measured by scripts/calibrate_costs.py --exchange). argsort wins small
    buffers, radix wins past the crossover."""
    p = profile or _COST_PROFILE
    return {
        "argsort": p.sort_pass_factor * math.log2(max(n_rows, 2)),
        "radix": p.radix_route_factor,
    }


def choose_exchange_impl(n_rows: int, ctx: "ExecutionContext",
                         profile: Optional[CostProfile] = None) -> str:
    """"argsort" vs "radix" for one key-routing hash Exchange."""
    if ctx.exchange_impl != "cost":
        return ctx.exchange_impl
    costs = exchange_costs(n_rows, profile)
    return min(costs, key=costs.get)


def topk_costs(n_groups: int, k: int, n_shards: int,
               profile: Optional[CostProfile] = None) -> Dict[str, float]:
    """Row-transfer-equivalent cost of each distributed TopK lowering.

    replicated   selects on the merged group table, which must therefore
                 be replicated on every shard: the TopK is charged the
                 (n-1)/n of the G group rows each shard receives beyond
                 the slots it owns (the replication the merge collective
                 would otherwise not need — LOCAL_ALLOC's reduce_scatter,
                 for instance, is owner-sharded by nature).
    candidates   each shard selects its local top-k over the ~G/n group
                 slots it owns; a gather Exchange converges k rows per
                 shard — k * n_shards candidate rows on the wire,
                 independent of the group-table size.

    The crossover: candidates wins once G(n-1)/n > kn, i.e. for any group
    domain meaningfully larger than k * n (the common case — a TopK's k
    is tiny next to its group table)."""
    del profile                      # priced in raw rows, no fitted factor
    n = max(int(n_shards), 2)
    return {
        "replicated": float(n_groups) * (n - 1) / n,
        "candidates": float(k) * n,
    }


def choose_dist_topk(n_groups: int, k: int, n_shards: int,
                     ctx: "ExecutionContext",
                     profile: Optional[CostProfile] = None) -> str:
    """"replicated" vs "candidates" for one distributed TopK."""
    if ctx.dist_topk != "cost":
        return ctx.dist_topk
    if n_shards < 2:
        return "replicated"          # nothing to move: candidates is waste
    costs = topk_costs(n_groups, k, n_shards, profile)
    return min(costs, key=costs.get)


def stacked_width(aggs: Tuple[Tuple[str, Tuple[str, str]], ...]) -> int:
    """Number of measure columns: weights + distinct sum/avg."""
    return 1 + len({c for _, (op, c) in aggs if op in ("sum", "avg")})


def _stacked_src(aggs) -> list:
    """Distinct sum/avg source columns, insertion order — the static twin
    of the ``src`` list stacked_columns derives from data."""
    src: list = []
    for _name, (op, c) in aggs:
        if op in ("sum", "avg") and c not in src:
            src.append(c)
    return src


@dataclass(frozen=True)
class Decision:
    """One planner choice, for ``explain`` output and tests."""
    node: str            # "Aggregate" | "Join" | "DistJoin" | "Exchange" ...
    detail: str
    choice: str
    costs: Optional[Tuple[Tuple[str, float], ...]] = None

    def describe(self) -> str:
        c = ""
        if self.costs:
            c = " (" + ", ".join(f"{k}={v:.2f}" for k, v in self.costs) + ")"
        return f"{self.node}[{self.detail}] -> {self.choice}{c}"


# ---------------------------------------------------------------------------
# bounded LRU plan cache
# ---------------------------------------------------------------------------
class CacheInfo(NamedTuple):
    hits: int
    misses: int
    maxsize: int
    currsize: int


class LRUCache:
    """Bounded LRU, safe for concurrent get/put/evict.

    The service's worker pools hit the plan cache and join-index pool from
    many threads at once; unlocked, an interleaved move_to_end/popitem pair
    can race an eviction and raise KeyError, and the hit/miss counters can
    drop increments. Every mutation (including the counters, so
    ``plan_cache_info()`` is race-free) happens under one re-entrant lock —
    the critical sections are dict operations, far cheaper than the plan
    dispatch they guard."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._d: "OrderedDict" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self._lock = threading.RLock()

    def get(self, key):
        with self._lock:
            hit = self._d.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._d.move_to_end(key)
            self.hits += 1
            return hit

    def put(self, key, value) -> None:
        with self._lock:
            self._d[key] = value
            self._d.move_to_end(key)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def resize(self, maxsize: int) -> None:
        with self._lock:
            self.maxsize = maxsize
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self.hits = self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def info(self) -> CacheInfo:
        with self._lock:
            return CacheInfo(self.hits, self.misses, self.maxsize,
                             len(self._d))


DEFAULT_PLAN_CACHE_ENTRIES = 64
_PLAN_CACHE = LRUCache(DEFAULT_PLAN_CACHE_ENTRIES)


def configure_plan_cache(max_entries: int) -> None:
    """Bound the compiled-plan LRU (evicts oldest immediately if needed)."""
    if max_entries < 1:
        raise ValueError("plan cache needs at least one entry")
    _PLAN_CACHE.resize(max_entries)


def plan_cache_info() -> CacheInfo:
    return _PLAN_CACHE.info()


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# join build-side index pool
# ---------------------------------------------------------------------------
class JoinIndexPool:
    """(order, sorted_keys) argsorts keyed on column-array IDENTITY.

    The per-Table index cache (columnar.Table.index_cache) only lives for
    one trace: every compiled plan re-ran its build argsorts at dispatch
    time, and rebuilding the Tables pytree dropped the cache entirely. The
    pool keys on the underlying column array (``id`` plus an identity check
    against a WEAK reference, so recycled ids can never alias and the pool
    never keeps a dropped dataset alive on device), computes the argsort
    ONCE eagerly, and feeds it to the compiled plan as a traced argument —
    so the index survives Table reconstruction and is shared by every
    query/plan that joins through the same build column.

    ``layout`` does the same for the kernel join's per-side partition
    layouts (``columnar.join_side_layout``); ``layout_builds`` counts
    them as ``builds`` counts argsorts."""

    def __init__(self, maxsize: int = 256):
        self._lru = LRUCache(maxsize)
        self._building: Dict[Tuple, threading.Lock] = {}
        self.builds = 0
        self.replicas = 0
        self.layout_builds = 0

    def get(self, table: str, column: str, arr) -> Tuple[jax.Array, jax.Array]:
        key = (table, column, id(arr))
        hit = self._lru.get(key)
        if hit is not None and hit[0]() is arr:
            return hit[1]
        # the argsort runs outside the lock: concurrent first-touchers of
        # the same column may both build (harmless — one entry survives),
        # but never block every other pool on an O(N log N) sort
        order = jnp.argsort(jnp.asarray(arr))
        idx = (order, jnp.asarray(arr)[order])
        with self._lru._lock:
            self._lru.put(key, (weakref.ref(arr), idx))
            self.builds += 1
            self._sweep_dead()
        return idx

    def replica(self, table: str, column: str, arr,
                pool_id: int) -> Tuple[jax.Array, jax.Array]:
        """A per-worker-pool copy of ``get``'s (order, sorted_keys) pair —
        the build-side replication of the paper's socket-local working
        sets. The base index is computed ONCE (``builds`` counts sorts);
        each pool then gets its own buffer copy (``replicas`` counts
        them), so every probe morsel a pool executes hits a pool-local
        build structure instead of contending on one shared buffer.
        Values are bit-identical to the base index by construction."""
        key = (table, column, id(arr), "replica", int(pool_id))
        hit = self._lru.get(key)
        if hit is not None and hit[0]() is arr:
            return hit[1]
        order, sk = self.get(table, column, arr)     # base: built once
        with self._lru._lock:
            # double-check under the lock: two workers of the SAME pool
            # can race their pool's first morsel, and "one replica per
            # pool" is the accounting invariant tests pin down
            hit = self._lru.get(key)
            if hit is not None and hit[0]() is arr:
                return hit[1]
            idx = (jnp.copy(order), jnp.copy(sk))
            self._lru.put(key, (weakref.ref(arr), idx))
            self.replicas += 1
            self._sweep_dead()
        return idx

    def layout(self, table: str, column: str, arr, n_partitions: int,
               cap: int, side: str) -> JoinSide:
        """The kernel join's ``side`` layout of ``arr`` at (P, cap), keyed
        as ``get`` is, plus (P, cap, side). The builds of one key are
        serialized and double-checked, so plans compiled side by side
        build each layout once (``layout_builds``); distinct layouts
        build (and compile) side by side."""
        key = (table, column, id(arr), "layout", n_partitions, cap, side)
        hit = self._lru.get(key)
        if hit is not None and hit[0]() is arr:
            return hit[1]
        with self._lru._lock:
            lock = self._building.setdefault(key, threading.Lock())
        with lock:
            hit = self._lru.get(key)
            if hit is None or hit[0]() is not arr:
                hit = (weakref.ref(arr), join_side_layout(
                    jnp.asarray(arr), n_partitions, cap, side))
                with self._lru._lock:
                    self._lru.put(key, hit)
                    self.layout_builds += 1
                    self._sweep_dead()
        with self._lru._lock:
            if self._building.get(key) is lock:
                del self._building[key]
        return hit[1]

    def _sweep_dead(self) -> None:
        with self._lru._lock:
            dead = [k for k, (ref, _) in self._lru._d.items()
                    if ref() is None]
            for k in dead:
                del self._lru._d[k]

    def info(self) -> CacheInfo:
        return self._lru.info()

    def clear(self) -> None:
        self._lru.clear()
        self.builds = 0
        self.replicas = 0
        self.layout_builds = 0


_INDEX_POOL = JoinIndexPool()


def join_index_pool() -> JoinIndexPool:
    return _INDEX_POOL


def required_indexes(root: L.Node) -> Tuple[Tuple[str, str], ...]:
    """(table, column) build-side sort indexes the plan's joins can use."""
    out: List[Tuple[str, str]] = []
    for node in L.walk(root):
        if isinstance(node, L.Join):
            sc = L.base_scan(node.build, node.build_key)
            if sc is not None and (sc.table, node.build_key) not in out:
                out.append((sc.table, node.build_key))
    return tuple(out)


# ---------------------------------------------------------------------------
# morsel-split probe analysis (the serving scheduler's split-probe oracle)
# ---------------------------------------------------------------------------
def _physical_base_scan(node: PH.PNode, column: str) -> Optional[PH.PScan]:
    """The PScan whose ``column`` reaches ``node`` value-identical (same
    rows, same order, never overwritten), or None. The physical twin of
    L.base_scan: it certifies that the pooled (order, sorted_keys) index
    built from the base table's column array is valid for this node's
    Table — Filter only masks, a local Join's output rows ARE its probe
    rows, Project/Attach only add columns (unless they shadow
    ``column``)."""
    while True:
        if isinstance(node, PH.PScan):
            return node
        if isinstance(node, PH.PFilter):
            node = node.child
        elif isinstance(node, PH.PProject):
            if any(n == column for n, _ in node.cols):
                return None
            node = node.child
        elif isinstance(node, PH.PJoin):
            if node.dist is not None or any(n == column
                                            for n, _ in node.take):
                return None
            node = node.probe
        elif isinstance(node, PH.PAttach):
            if any(n == column for n, _ in node.cols):
                return None
            node = node.child
        else:
            return None


@dataclass(frozen=True)
class PreludeSpec:
    """One subtree of a split-probe plan that executes ONCE per task (not
    per morsel): a join build side or an Attach source. ``is_table`` says
    whether its result is a Table (serialized as (columns, mask) across
    the jit boundary) or a replicated dict of group arrays; ``index`` is
    the (table, column) pooled sort index a pool-local replica must seed
    into the reconstructed build Table's index_cache (None for Attach
    sources, which need no index)."""
    node: PH.PNode
    is_table: bool
    index: Optional[Tuple[str, str]]


@dataclass(frozen=True)
class ProbeSplit:
    """probe_split()'s answer: the pieces the serving scheduler needs to
    run a marked join-probe pipeline as per-pool morsels. ``scan`` is the
    probe-side base scan (the morsel axis), ``pipeline_root`` the
    aggregate's input (the per-morsel pipeline: every node between scan
    and aggregate is per-row deterministic, so concatenating the morsel
    outputs in morsel order reproduces the serial intermediate table
    bit-for-bit), ``preludes`` the once-per-task subtrees, ``root`` /
    ``outputs`` what the finalize step runs over the merged table."""
    root: PH.PNode
    outputs: Optional[Tuple[str, ...]]
    scan: PH.PScan
    pipeline_root: PH.PNode
    preludes: Tuple[PreludeSpec, ...]
    n_rows: int


def probe_split(phys: PH.PhysicalPlan) -> Optional[ProbeSplit]:
    """Decompose a LOCAL physical plan into a morsel-splittable probe
    pipeline, or None when the plan must run whole.

    Splittable = (optional PTopK over) a PAggregate whose child chain
    down to one PScan is Filter/Project/Join/Attach where EVERY join is
    ``morsel_split``-marked (sorted strategy, probe side past the
    cost-model crossover) with a resolvable base-scan build index. Each
    on-path operator is per-row deterministic over the probe rows, so a
    row-range slice of the scan yields exactly that slice of the serial
    intermediate table — the bit-identity guarantee the whole-plan path
    already had, kept under intra-query parallelism. Declines (returns
    None) rather than degrade: an unresolvable build index would force a
    per-morsel argsort (defeating once-per-pool replication), and a
    kernel-strategy join changes overflow semantics under slicing."""
    if phys.n_shards != 1:
        return None
    node = phys.root
    while isinstance(node, PH.PTopK):
        node = node.child
    if not isinstance(node, PH.PAggregate):
        return None
    preludes: List[PreludeSpec] = []
    path: List[PH.PNode] = []
    cur = node.child
    while not isinstance(cur, PH.PScan):
        path.append(cur)
        if isinstance(cur, (PH.PFilter, PH.PProject)):
            cur = cur.child
        elif isinstance(cur, PH.PJoin):
            if not cur.morsel_split:
                return None          # cost model declined (or kernel join)
            base = _physical_base_scan(cur.build, cur.build_key)
            if base is None:
                return None          # no poolable build index: stay whole
            preludes.append(PreludeSpec(
                cur.build, True, (base.table, cur.build_key)))
            cur = cur.probe
        elif isinstance(cur, PH.PAttach):
            src = cur.source
            preludes.append(PreludeSpec(
                src, not isinstance(src, (PH.PAggregate, PH.PTopK)), None))
            cur = cur.child
        else:
            return None
    if not any(p.index is not None for p in preludes):
        return None                  # no join probe to parallelize
    scan = cur
    path.append(scan)
    # a prelude subtree structurally EQUAL to a path node would collide
    # in the executor's structural memo (the path is seeded with
    # morsel-sliced values, the prelude with whole-table ones) — decline
    # such self-join-like shapes instead of guessing
    path_set = set(path)
    if any(p.node in path_set for p in preludes):
        return None
    return ProbeSplit(phys.root, phys.outputs, scan, node.child,
                      tuple(preludes), scan.rows)


# ---------------------------------------------------------------------------
# kernel-join layouts hoisted into the JoinIndexPool
# ---------------------------------------------------------------------------
class LayoutSpec(NamedTuple):
    """One pooled kernel-join layout: ``side`` of ``table.column`` at
    (``n_partitions``, ``cap``) (``JoinIndexPool.layout``)."""
    table: str
    column: str
    n_partitions: int
    cap: int
    side: str

    @property
    def key(self) -> str:
        return (f"{self.side}:{self.table}.{self.column}:"
                f"{self.n_partitions}x{self.cap}")


def pooled_layouts(node: PH.PJoin, ctx: "ExecutionContext"
                   ) -> Optional[Tuple[LayoutSpec, LayoutSpec]]:
    """(probe, build) layouts of a local kernel join whose keys both reach
    it from a base scan (``_physical_base_scan``: the layout built from
    the base column array is then the join's own), at ``join_layout``'s
    sizes for the scans' static rows; None for any other join, which
    builds its layouts in the plan."""
    if node.strategy != "kernel" or node.dist is not None:
        return None
    probe = _physical_base_scan(node.probe, node.probe_key)
    build = _physical_base_scan(node.build, node.build_key)
    if probe is None or build is None:
        return None
    P, build_cap, probe_cap = join_layout(probe.rows, build.rows,
                                          ctx.n_partitions,
                                          ctx.capacity_factor)
    return (LayoutSpec(probe.table, node.probe_key, P, probe_cap, "probe"),
            LayoutSpec(build.table, node.build_key, P, build_cap, "build"))


class KernelJoins(NamedTuple):
    """A plan's kernel joins, static per plan-cache entry: the pooled
    layouts the plan takes as arguments, and how many joins run on them
    (``hoisted``) or build their layouts in the plan (``inline``)."""
    layouts: Tuple[LayoutSpec, ...] = ()
    hoisted: int = 0
    inline: int = 0


def kernel_joins(phys: PH.PhysicalPlan,
                 ctx: "ExecutionContext") -> KernelJoins:
    specs: List[LayoutSpec] = []
    hoisted = inline = 0
    for node in PH.walk_unique(phys.root):
        if not (isinstance(node, PH.PJoin) and node.strategy == "kernel"):
            continue
        pair = pooled_layouts(node, ctx)
        if pair is None:
            inline += 1
            continue
        hoisted += 1
        specs.extend(sp for sp in pair if sp not in specs)
    return KernelJoins(tuple(specs), hoisted, inline)


# ---------------------------------------------------------------------------
# expression evaluation
# ---------------------------------------------------------------------------
def eval_expr(e: L.Expr, table: Table):
    if isinstance(e, L.Col):
        return table.col(e.name)
    if isinstance(e, L.Lit):
        return e.value
    if isinstance(e, L.UnOp):
        v = eval_expr(e.operand, table)
        if e.op == "abs":
            return jnp.abs(v)
        if e.op == "neg":
            return -v
        if e.op == "not":
            return ~v
        raise ValueError(f"unknown unary op {e.op!r}")
    if isinstance(e, L.BinOp):
        a, b = eval_expr(e.lhs, table), eval_expr(e.rhs, table)
        ops = {"add": lambda: a + b, "sub": lambda: a - b,
               "mul": lambda: a * b, "div": lambda: a / b,
               "le": lambda: a <= b, "lt": lambda: a < b,
               "ge": lambda: a >= b, "gt": lambda: a > b,
               "eq": lambda: a == b, "ne": lambda: a != b,
               "and": lambda: a & b, "or": lambda: a | b}
        try:
            return ops[e.op]()
        except KeyError:
            raise ValueError(f"unknown binary op {e.op!r}") from None
    raise TypeError(f"not an expression: {e!r}")


# ---------------------------------------------------------------------------
# lowering: logical plan -> physical plan
# ---------------------------------------------------------------------------
def lower(plan: L.LogicalPlan, ctx: ExecutionContext,
          rows: Dict[str, int], profile: Optional[CostProfile] = None,
          n_shards: Optional[int] = None,
          observed=None) -> PH.PhysicalPlan:
    """Cost-driven lowering pass: resolve every strategy decision into an
    explicit physical tree, then let the movement rewrites (push-down,
    route-once, compaction — see module docstring) improve it.

    ``rows`` maps table name -> true row count (the shape signature the
    plan-cache key already carries). ``n_shards`` overrides the mesh width
    — lowering is pure shape arithmetic, so tests and explain can lower
    distributed plans without materializing fake devices. ``observed`` is
    the adaptive re-planning hook: an ``observed(probe_key, build_key) ->
    (probe_alive, build_alive) | None`` lookup (telemetry's recorded
    GLOBAL alive rows) consulted ONLY by the distributed-join cost choice
    — estimates and buffer shapes are untouched, so a re-lowering with
    unchanged decisions is structurally identical to the original."""
    profile = profile or current_cost_profile()
    if n_shards is None:
        n_shards = ctx.mesh.shape[ctx.axis] if ctx.mesh is not None else 1
        distributed = ctx.mesh is not None
    else:
        distributed = True
    lo = _Lowering(ctx, rows, profile, n_shards, distributed, observed)
    root = lo.node(plan.root)
    return PH.PhysicalPlan(root, plan.outputs,
                           n_shards if distributed else 1)


class _Lowering:
    """One lower() pass: shape propagation + strategy choice per node."""

    def __init__(self, ctx, rows, profile, n, distributed, observed=None):
        self.ctx = ctx
        self.rows = rows
        self.profile = profile
        self.n = n
        self.distributed = distributed
        self.observed = observed             # adaptive re-plan lookup
        margin = ctx.compact_margin()        # None = compaction disabled
        if ctx.compact is None and profile.compact_margin is not None:
            # context left the margin at its default: the profile's
            # telemetry-fitted margin replaces the hand-set constant
            margin = profile.compact_margin
        self.margin = margin

    def groups(self, card: L.Cardinality) -> int:
        if isinstance(card, L.TableRows):
            return self.rows[card.table]
        return int(card)

    def node(self, node: L.Node) -> PH.PNode:
        method = getattr(self, "_" + type(node).__name__.lower())
        return method(node)

    # -- relational nodes ---------------------------------------------------
    def _scan(self, node: L.Scan) -> PH.PScan:
        r = self.rows[node.table]
        per = (r + (-r % self.n)) // self.n if self.distributed else r
        return PH.PScan(node.table, rows=per, est=per)

    def _filter(self, node: L.Filter) -> PH.PNode:
        c = self.node(node.child)
        pushed = self._filter_below_exchange(c, node.pred)
        if pushed is not None:
            return pushed
        return PH.PFilter(c, node.pred, rows=c.rows, est=c.est)

    def _filter_below_exchange(self, c: PH.PNode,
                               pred: L.Expr) -> Optional[PH.PNode]:
        """Filter-below-Exchange peephole: a Filter over a partitioned
        PJoin whose predicate reads only PRE-ROUTE columns (none of the
        join's take columns, so every referenced column already exists on
        the probe side below its hash Exchange) is pushed beneath the
        probe routing. Rows the predicate kills become dead padding BEFORE
        the all-to-all — they re-route round-robin with zero weight — so
        the wire carries fewer alive rows, not just a cheaper layout.
        Results are bit-identical: the filter mask multiplies into the
        same selection weights either side of the routing, and dead rows
        can never match a join key or enter an aggregate. The Exchange's
        ``moved_rows`` estimate shrinks by the profile's
        filter_selectivity per pushed filter (capacity and est are
        untouched — occupancy budgets stay safe); telemetry's observed
        alive_in/alive_out refreshes the selectivity."""
        if not (self.distributed and isinstance(c, PH.PJoin)
                and c.dist == "partitioned"):
            return None
        ex = c.probe
        if not (isinstance(ex, PH.Exchange) and ex.kind == "hash"
                and ex.key is not None):
            return None
        cols = L.expr_cols(pred)
        if not cols or any(name in cols for name, _src in c.take):
            return None              # predicate reads a post-join column
        inner = PH.PFilter(ex.child, pred, rows=ex.child.rows,
                           est=ex.child.est, pushed=True)
        sel = self.profile.filter_selectivity ** PH.filters_below(inner)
        moved = int(ex.est * sel) * (self.n - 1) // self.n
        routed = PH.Exchange(inner, "hash", key=ex.key,
                             capacity=ex.capacity, method=ex.method,
                             rows=ex.rows, est=ex.est, moved_rows=moved,
                             impl=ex.impl)
        return PH.PJoin(routed, c.build, c.probe_key, c.build_key, c.take,
                        c.strategy, c.dist, rows=c.rows, est=c.est)

    def _project(self, node: L.Project) -> PH.PProject:
        c = self.node(node.child)
        return PH.PProject(c, node.cols, rows=c.rows, est=c.est)

    def _attach(self, node: L.Attach) -> PH.PAttach:
        c = self.node(node.child)
        src = self.node(node.source)
        return PH.PAttach(c, src, node.key, node.cols, rows=c.rows,
                          est=c.est)

    def _topk(self, node: L.TopK) -> PH.PTopK:
        c = self.node(node.child)
        if not self.distributed:
            return PH.PTopK(c, node.col, node.k, node.index_name,
                            rows=node.k, est=node.k)
        # distributed TopK: the child aggregate's merged group table is
        # replicated, so selecting on it directly ("replicated") is
        # correct but charges the TopK the table's replication. The
        # "candidates" lowering instead selects each shard's local top-k
        # over the ~G/n group slots it owns and converges only k rows per
        # shard through an explicit gather Exchange — k * n_shards
        # candidate rows on the wire, bit-identical results (within-shard
        # ties keep ascending global slot order, the shard-major gather
        # preserves it, and lax.top_k's lowest-index tie-break matches
        # the replicated selection).
        G = c.rows
        choice = choose_dist_topk(G, node.k, self.n, self.ctx, self.profile)
        if choice == "candidates":
            ex = PH.Exchange(c, "gather", rows=node.k * self.n,
                             est=node.k * self.n,
                             moved_rows=node.k * (self.n - 1))
            return PH.PTopK(ex, node.col, node.k, node.index_name,
                            dist="candidates", rows=node.k, est=node.k)
        return PH.PTopK(c, node.col, node.k, node.index_name,
                        dist="replicated", rows=node.k, est=node.k)

    # -- joins --------------------------------------------------------------
    def _join(self, node: L.Join) -> PH.PJoin:
        probe = self.node(node.probe)
        build = self.node(node.build)
        if not self.distributed:
            strategy = choose_join(probe.rows, build.rows, self.ctx)
            # morsel-splittable probe phase: the sorted-index gather is
            # per-probe-row deterministic against a fixed build index, so
            # the serving scheduler may slice the probe side into
            # per-pool morsels (build side replicated per pool) with
            # bit-identical results. The kernel join's partition-overflow
            # semantics change under row slicing, so only the sorted
            # strategy is markable; small probes stay whole-plan (the
            # per-morsel dispatch overhead loses below the fitted
            # morsel_split_rows crossover).
            split = (strategy == "sorted"
                     and probe.rows >= self.profile.morsel_split_rows)
            return PH.PJoin(probe, build, node.probe_key, node.build_key,
                            node.take, strategy, None,
                            rows=probe.rows, est=probe.est,
                            morsel_split=split)
        n_probe, n_build = probe.rows * self.n, build.rows * self.n
        if self.observed is not None:
            obs = self.observed(node.probe_key, node.build_key)
            if obs is not None:
                # re-plan: price the join from the alive rows execution
                # actually saw (filter selectivity, padding occupancy)
                # instead of the static physical buffer sizes
                n_probe, n_build = obs
        choice = choose_dist_join(n_probe, n_build,
                                  self.n, self.ctx, self.profile)
        if choice == "broadcast":
            b = PH.Exchange(build, "broadcast", rows=build.rows * self.n,
                            est=build.est * self.n,
                            moved_rows=build.rows * (self.n - 1))
            return PH.PJoin(probe, b, node.probe_key, node.build_key,
                            node.take, "sorted", "broadcast",
                            rows=probe.rows, est=probe.est)
        p_in = self._routed(probe, node.probe_key)
        b_in = self._routed(build, node.build_key)
        return PH.PJoin(p_in, b_in, node.probe_key, node.build_key,
                        node.take, "sorted", "partitioned",
                        rows=p_in.rows, est=probe.est)

    def _routed(self, side: PH.PNode, key: str) -> PH.PNode:
        """One partitioned-join side: route-once elision, else
        compact-then-hash-Exchange to the key's owner shards."""
        method = self.ctx.dist_route
        if (self.ctx.route_once
                and PH.placed_key(side) == (key, method)):
            return side              # rule 2: an upstream routing suffices
        side = PH.maybe_compact(
            side, self.margin or 0.0, self.margin is not None,
            self.profile.filter_selectivity
            ** PH.filters_below(side))                         # rule 3
        cap = routing_capacity(side.rows, self.n, self.ctx.capacity_factor)
        sel = self.profile.filter_selectivity ** PH.filters_below(side)
        return PH.Exchange(side, "hash", key=key, capacity=cap,
                           method=method, rows=self.n * cap, est=side.est,
                           moved_rows=int(side.est * sel)
                           * (self.n - 1) // self.n,
                           impl=choose_exchange_impl(side.rows, self.ctx,
                                                     self.profile))

    # -- aggregates ---------------------------------------------------------
    def _aggregate(self, node: L.Aggregate) -> PH.PAggregate:
        child = self.node(node.child)
        if node.key is None:
            merge = "scalar" if self.distributed else None
            return PH.PAggregate(child, None, 1, node.aggs, "xla", merge,
                                 None, rows=1, est=1)
        G = self.groups(node.n_groups)
        C = stacked_width(node.aggs)
        has_med = any(is_holistic(op) for _, (op, _c) in node.aggs)
        if not self.distributed:
            layout = choose_aggregate(child.rows, G, C, self.ctx.executor,
                                      self.profile)
            return PH.PAggregate(child, node.key, G, node.aggs, layout,
                                 None, None, rows=G, est=G)
        policy = self.ctx.policy or PlacementPolicy.FIRST_TOUCH
        if not has_med:
            med = None
        elif self.ctx.route_once and PH.routes_once(child, node.key):
            # rows already co-located by the group key (route-once): the
            # order statistic selects on the owner shard directly and the
            # merge is an owner-masked psum — O(G) wire rows instead of
            # re-routing O(N) records through a fresh Exchange
            med = "placed"
        else:
            med = ("route" if policy == PlacementPolicy.INTERLEAVE
                   else "replicate")
        dist_aggs = tuple((nm, oc) for nm, oc in node.aggs
                          if not is_holistic(oc[0]))
        if not dist_aggs:
            # holistic-only: counts come from the selection path, no
            # stacked-sums merge at all
            return PH.PAggregate(child, node.key, G, node.aggs, "xla",
                                 "holistic", med, rows=G, est=G)
        if policy in (PlacementPolicy.FIRST_TOUCH,
                      PlacementPolicy.LOCAL_ALLOC):
            layout = self._occupancy_safe(child, choose_aggregate(
                child.rows, G, C, self.ctx.executor, self.profile))
            partial = PH.PPartialAggregate(child, node.key, G, dist_aggs,
                                           layout, rows=G, est=G)
            # the merge collective is a first-class Exchange node, so
            # explain() prices EVERY policy's wire volume on the same
            # axis (pushdown already had one): FT's psum is a ring
            # allreduce over the (G, C) partial tables (reduce-scatter +
            # all-gather, ~2 G (n-1)/n partial rows on the wire), LA's
            # reduce_scatter is the first half only. Both execute FUSED
            # in PAggregate (merge_partial_table), like "gather".
            if policy == PlacementPolicy.FIRST_TOUCH:
                merge, kind = "psum", "allreduce"
                moved = 2 * G * (self.n - 1) // self.n
            else:
                merge, kind = "reduce_scatter", "reduce_scatter"
                moved = G * (self.n - 1) // self.n
            ex = PH.Exchange(partial, kind, rows=G, est=G,
                             moved_rows=moved)
            return PH.PAggregate(ex, node.key, G, node.aggs, layout,
                                 merge, med, rows=G, est=G)
        if policy == PlacementPolicy.PREFERRED:
            ex = PH.Exchange(child, "gather", rows=child.rows * self.n,
                             est=child.est * self.n,
                             moved_rows=child.rows * (self.n - 1))
            layout = self._occupancy_safe(child, choose_aggregate(
                child.rows * self.n, G, C, self.ctx.executor,
                self.profile))
            return PH.PAggregate(ex, node.key, G, node.aggs, layout,
                                 "gather", med, rows=G, est=G)
        return self._interleave_aggregate(node, child, G, C, dist_aggs, med)

    def _interleave_aggregate(self, node, child, G, C, dist_aggs, med):
        """INTERLEAVE grouped aggregation: route-once elision, push-down,
        or the record-routing Exchange — in that preference order."""
        ctx = self.ctx
        if ctx.route_once and PH.routes_once(child, node.key):
            # rule 2: rows already co-located by the group key — each
            # group's table is complete on one shard, merge is a psum of
            # disjoint tables. Records route ONE time, join + aggregate.
            layout = self._occupancy_safe(child, choose_aggregate(
                child.rows, G, C, ctx.executor, self.profile))
            return PH.PAggregate(child, node.key, G, node.aggs, layout,
                                 "placed", med, rows=G, est=G)
        # the push-down crossover is priced on the estimated ALIVE input
        # (est discounted by the telemetry-refreshed filter selectivity
        # per stacked filter), not the physical buffer rows: a heavily
        # filtered input ships fewer records than its buffer suggests,
        # which moves the G-vs-records crossover
        alive = max(int(child.est
                        * self.profile.filter_selectivity
                        ** PH.filters_below(child)), 1)
        pushdown = (ctx.agg_pushdown is True
                    or (ctx.agg_pushdown is None
                        and PH.pushdown_profitable(G, alive)))
        if pushdown:
            # rule 1: partial-aggregate below the exchange, ship ~G
            # partial rows instead of the records
            layout = self._occupancy_safe(child, choose_aggregate(
                child.rows, G, C, ctx.executor, self.profile))
            partial = PH.PPartialAggregate(child, node.key, G, dist_aggs,
                                           layout, rows=G, est=G)
            cap = routing_capacity(G, self.n, ctx.capacity_factor)
            ex = PH.Exchange(partial, "hash", key=None, capacity=cap,
                             rows=self.n * cap, est=G,
                             moved_rows=G * (self.n - 1) // self.n)
            return PH.PAggregate(ex, node.key, G, node.aggs, layout,
                                 "pushdown", med, rows=G, est=G)
        # record routing: the classic INTERLEAVE all-to-all of the data
        rchild = PH.maybe_compact(child, self.margin or 0.0,
                                  self.margin is not None,
                                  self.profile.filter_selectivity
                                  ** PH.filters_below(child))
        cap = routing_capacity(rchild.rows, self.n, ctx.capacity_factor)
        sel = self.profile.filter_selectivity ** PH.filters_below(rchild)
        ex = PH.Exchange(rchild, "hash", key=node.key, capacity=cap,
                         method="modulo", rows=self.n * cap, est=rchild.est,
                         moved_rows=int(rchild.est * sel)
                         * (self.n - 1) // self.n,
                         impl=choose_exchange_impl(rchild.rows, self.ctx,
                                                   self.profile))
        n_slots = (G + (-G % self.n)) // self.n
        layout = choose_aggregate(self.n * cap, n_slots + 1, C,
                                  ctx.executor, self.profile)
        if layout == "partitioned":
            # the routed buffer masses its padding on one drop slot; the
            # partitioned layout's capacity accounting counts those rows,
            # so fall back to the occupancy-independent segment ops
            layout = "xla"
        return PH.PAggregate(ex, node.key, G, node.aggs, layout, "owner",
                             med, rows=G, est=G)

    def _occupancy_safe(self, child: PH.PNode, layout: str) -> str:
        """Range-partitioned layouts size per-partition capacity from row
        COUNTS — on a routed buffer the padding would eat it (phantom
        overflow, dropped records), so fall back to segment ops there."""
        if layout == "partitioned" and PH.has_routed_buffer(child):
            return "xla"
        return layout


# ---------------------------------------------------------------------------
# physical execution: thin walkers over the physical IR
# ---------------------------------------------------------------------------
class _LocalExecutor:
    """Single-device walker over a physical plan (trace-time recursion).

    Memoization is by NODE STRUCTURE (physical nodes are frozen
    dataclasses), so structurally identical subtrees — including
    deduplicated Exchanges — execute exactly once."""

    def __init__(self, tables, ctx: ExecutionContext, indexes,
                 profile: Optional[CostProfile] = None,
                 record: bool = False,
                 layouts: Optional[Dict[str, JoinSide]] = None):
        self.tables = tables
        self.ctx = ctx
        self.indexes = indexes           # {"table.column": (order, sk)}
        self.layouts = layouts or {}     # {LayoutSpec.key: JoinSide}
        self.profile = profile
        # fitted partitioned-layout capacity (profile) falls back to ctx
        self.agg_cf = ((profile.partition_capacity_factor
                        if profile is not None else None)
                       or ctx.capacity_factor)
        self.overflow = jnp.zeros((), jnp.int32)
        self._memo: Dict[PH.PNode, object] = {}
        # telemetry: traced per-node counters, keyed by walk_unique id.
        # record=False adds ZERO traced ops — every recording site is
        # behind `if self.record`.
        self.record = record
        self.stats: Dict[int, Dict[str, jax.Array]] = {}
        self._ids: Dict[PH.PNode, int] = {}
        # name scope of each node, ``<NodeType>_<preorder index>``: the
        # ops a node lowers to carry it in their metadata (a profile's
        # per-op name path)
        self._scopes: Dict[PH.PNode, str] = {}

    def run(self, node: PH.PNode):
        hit = self._memo.get(node)
        if hit is None:
            scope = self._scopes.get(node)
            if scope is None:
                hit = self._eval(node)
            else:
                with jax.named_scope(scope):
                    hit = self._eval(node)
            self._memo[node] = hit
        return hit

    def _note(self, node: PH.PNode, **vals) -> None:
        """Stash one node's observed counters (traced int32 scalars).
        Memoized subtrees note once — exactly like they execute once."""
        i = self._ids.get(node)
        if i is not None:
            self.stats[i] = {k: jnp.asarray(v).astype(jnp.int32)
                             for k, v in vals.items()}

    def _eval(self, node: PH.PNode):
        method = getattr(self, "_" + type(node).__name__.lower())
        return method(node)

    # -- node lowerings -----------------------------------------------------
    def _pscan(self, node: PH.PScan) -> Table:
        cols = dict(self.tables[node.table])
        cache = {}
        for (key, idx) in self.indexes.items():
            t, _, c = key.partition(".")
            if t == node.table and c in cols:
                cache[c] = idx
        return Table(cols, None, cache)

    def _pfilter(self, node: PH.PFilter) -> Table:
        t = self.run(node.child)
        out = t.filter(eval_expr(node.pred, t))
        self._record_filter(node, t, out)
        return out

    def _record_filter(self, node: PH.PFilter, t: Table,
                       out: Table) -> None:
        if self.record:
            # observed selectivity (alive_out / alive_in) is what
            # telemetry.refresh_profile fits filter_selectivity from
            self._note(node, alive_in=(t.weights() > 0).sum(),
                       alive_out=(out.weights() > 0).sum())

    def _pproject(self, node: PH.PProject) -> Table:
        t = self.run(node.child)
        return t.with_columns(**{n: eval_expr(e, t) for n, e in node.cols})

    def _pjoin(self, node: PH.PJoin) -> Table:
        probe = self.run(node.probe)
        build = self.run(node.build)
        if node.strategy == "kernel":
            pair = pooled_layouts(node, self.ctx)
            layouts = None
            if pair is not None and all(sp.key in self.layouts
                                        for sp in pair):
                layouts = tuple(self.layouts[sp.key] for sp in pair)
            joined, ovf = pkfk_join_kernel(
                probe, build, node.probe_key, node.build_key,
                dict(node.take), mode=self.ctx.mode,
                n_partitions=self.ctx.n_partitions,
                capacity_factor=self.ctx.capacity_factor, layouts=layouts)
            self.overflow = self.overflow + ovf
        else:
            joined = pkfk_join(probe, build, node.probe_key,
                               node.build_key, dict(node.take))
        self._record_join(node, probe, build, joined)
        return joined

    def _record_join(self, node: PH.PJoin, probe: Table, build: Table,
                     joined: Table) -> None:
        if self.record:
            self._note(node, out_alive=(joined.weights() > 0).sum())

    def _pattach(self, node: PH.PAttach) -> Table:
        t = self.run(node.child)
        src = self.run(node.source)
        first = src[node.cols[0][1]]
        pos = jnp.clip(t.col(node.key), 0, first.shape[0] - 1)
        return t.with_columns(**{new: src[s][pos] for new, s in node.cols})

    def _ptopk(self, node: PH.PTopK) -> Dict[str, jax.Array]:
        g = self.run(node.child)
        vals, idx = jax.lax.top_k(g[node.col], node.k)
        return {node.col: vals, node.index_name: idx}

    def _exchange(self, node: PH.Exchange):
        raise TypeError("Exchange in a single-device physical plan")

    def _compact(self, node: PH.Compact):
        raise TypeError("Compact in a single-device physical plan")

    def _ppartialaggregate(self, node: PH.PPartialAggregate):
        raise TypeError("PPartialAggregate in a single-device plan")

    def _paggregate(self, node: PH.PAggregate) -> Dict[str, jax.Array]:
        t = self.run(node.child)
        if node.key is None:
            return self._scalar_aggregate(node, t)
        out = self._grouped(node, t)
        self.overflow = self.overflow + out["_overflow"]
        if self.record:
            self._note(node, groups_occupied=(out["_count"] > 0).sum())
        return out

    def _grouped(self, node: PH.PAggregate, t: Table) -> Dict[str, jax.Array]:
        aggs = dict(node.aggs)
        if node.layout == "xla":
            return group_aggregate(t, node.key, node.n_groups, aggs,
                                   executor="xla")
        return group_aggregate(t, node.key, node.n_groups, aggs,
                               executor="kernel", layout=node.layout,
                               mode=self.ctx.mode,
                               n_partitions=self.ctx.n_partitions,
                               capacity_factor=self.agg_cf)

    def _scalar_aggregate(self, node: PH.PAggregate,
                          t: Table) -> Dict[str, jax.Array]:
        w = t.weights()
        cnt = w.sum()[None]
        out: Dict[str, jax.Array] = {}
        for name, (op, col) in node.aggs:
            if op == "count":
                out[name] = cnt
                continue
            v = t.col(col).astype(jnp.float32)
            if op == "sum":
                out[name] = (v * w).sum()[None]
            elif op == "avg":
                out[name] = (v * w).sum()[None] / jnp.maximum(cnt, 1.0)
            elif op == "max":
                out[name] = jnp.where(w > 0, v, -jnp.inf).max()[None]
            elif op == "min":
                out[name] = jnp.where(w > 0, v, jnp.inf).min()[None]
            elif op == "median":
                k = jnp.where(w > 0, 0, -1)
                out[name] = segment_median(k, v, 1)[0]
            elif op == "distinct":
                k = jnp.where(w > 0, 0, -1)
                out[name] = segment_distinct(k, v, 1)[0]
            elif parse_quantile(op) is not None:
                k = jnp.where(w > 0, 0, -1)
                out[name] = segment_quantile(k, v, 1, parse_quantile(op))[0]
            else:
                raise ValueError(f"unknown agg op {op!r}")
        out["_count"] = cnt
        out["_overflow"] = jnp.zeros((), jnp.int32)
        return out

    # -- plan root ----------------------------------------------------------
    def execute(self, phys: PH.PhysicalPlan) -> Dict[str, jax.Array]:
        for i, n in enumerate(PH.walk(phys.root)):
            self._scopes.setdefault(n, f"{type(n).__name__}_{i}")
        if self.record:
            # node id = walk_unique enumerate order: deterministic for a
            # fixed tree, shared with the StatsRegistry's accounting
            self._ids = {n: i
                         for i, n in enumerate(PH.walk_unique(phys.root))}
        res = self.run(phys.root)
        if isinstance(res, Table):
            raise TypeError("plan root must be an Aggregate or TopK node")
        out = dict(res)
        out["_overflow"] = self.overflow
        if phys.outputs is not None:
            out = {k: out[k] for k in phys.outputs}
        if self.record:
            # reserved key, attached AFTER output filtering: the stats
            # ride the jit out alongside the results (replicated — every
            # distributed counter is psum'd or computed from replicated
            # tables) and are stripped at dispatch by CompiledPlan
            out["_stats"] = self.stats
        return out


class _DistributedExecutor(_LocalExecutor):
    """Placement-policy walker: runs inside an open shard_map over
    ``ctx.axis``. Tables arrive row-sharded (zero-padded, with a ``_valid``
    weight column folded into each Scan's mask); Exchange nodes execute
    the engine collectives (broadcast all-gathers, hash routes through
    route_table_rows), Compact nodes re-compact routed buffers, and
    PAggregate's ``merge`` field names the per-policy combine. The merged
    group tables (and therefore every post-aggregation node) are
    replicated.

    Two Exchange kinds execute FUSED inside their consuming aggregate
    rather than standalone: "gather" (the keys and measure columns are
    gathered, not the whole table — fewer columns on the wire, and the
    holistic path must see the un-gathered records exactly once) and the
    partial-sums hash exchange of a pushed-down aggregate (the routing and
    owner-merge are one engine primitive, pushdown_group_sums)."""

    def __init__(self, tables, ctx: ExecutionContext, n_shards,
                 profile: Optional[CostProfile] = None,
                 record: bool = False):
        super().__init__(tables, ctx, {}, profile, record)
        self.n = n_shards

    def _alive(self, w) -> jax.Array:
        """GLOBAL alive-row count of a row-sharded weight vector."""
        return jax.lax.psum((w > 0).sum(), self.ctx.axis)

    def _pscan(self, node: PH.PScan) -> Table:
        cols = {c: a for c, a in self.tables[node.table].items()
                if c != "_valid"}
        return Table(cols, self.tables[node.table]["_valid"])

    def _exchange(self, node: PH.Exchange) -> Table:
        if node.kind in ("gather", "allreduce", "reduce_scatter"):
            raise TypeError(f"{node.kind} Exchange executes fused in "
                            f"PAggregate")
        child = self.run(node.child)
        if node.kind == "broadcast":
            if self.record:
                alive = self._alive(child.weights())
                # every alive row lands on the n-1 shards that did not
                # already hold it (the all-gather's wire traffic)
                self._note(node, alive_in=alive,
                           moved=alive * (self.n - 1))
            cols = gather_rows(child.columns, self.ctx.axis)
            mask = (None if child.mask is None
                    else gather_rows(child.mask, self.ctx.axis))
            return Table(cols, mask)
        # hash: all-to-all route the table's rows to their key's owner.
        # Routed padding rows carry weight 0 and key -1, so they can never
        # match a real join key; routing overflow is surfaced through the
        # plan's ``_overflow`` accumulator, never dropped silently.
        keys = child.col(node.key).astype(jnp.int32)
        w0 = child.weights()
        owner = route_owner(keys, w0 > 0, self.n, node.method)
        if node.impl == "radix":
            cols, w, ovf = radix_route_table_rows(
                child.columns, w0, owner, self.n, node.capacity,
                self.ctx.axis, mode=self.ctx.mode)
        else:
            cols, w, ovf = route_table_rows(child.columns, w0, owner,
                                            self.n, node.capacity,
                                            self.ctx.axis)
        ovf_total = jax.lax.psum(ovf, self.ctx.axis).astype(jnp.int32)
        self.overflow = self.overflow + ovf_total
        if self.record:
            # "moved" counts ALIVE rows whose owner is another shard —
            # dead (padding) rows also travel in their round-robin slots,
            # but the estimate prices payload, so the observation does too
            me = jax.lax.axis_index(self.ctx.axis)
            moved = jax.lax.psum(
                ((w0 > 0) & (owner != me)).sum(), self.ctx.axis)
            self._note(node, alive_in=self._alive(w0), moved=moved,
                       alive_out=self._alive(w), overflow=ovf_total)
        return Table(cols, w)

    def _record_filter(self, node: PH.PFilter, t: Table,
                       out: Table) -> None:
        if self.record:
            self._note(node, alive_in=self._alive(t.weights()),
                       alive_out=self._alive(out.weights()))

    def _compact(self, node: PH.Compact) -> Table:
        t = self.run(node.child)
        cols, w, ovf = compact_routed_rows(t.columns, t.weights(),
                                           node.capacity)
        ovf_total = jax.lax.psum(ovf, self.ctx.axis).astype(jnp.int32)
        self.overflow = self.overflow + ovf_total
        if self.record:
            self._note(node, alive_in=self._alive(t.weights()),
                       alive_out=self._alive(w), overflow=ovf_total)
        return Table(cols, w)

    def _record_join(self, node: PH.PJoin, probe: Table, build: Table,
                     joined: Table) -> None:
        if not self.record:
            return
        build_alive = (self._alive(build.weights())
                       if node.dist != "broadcast"
                       # broadcast already gathered the build side: the
                       # local count IS the (replicated) global count
                       else (build.weights() > 0).sum())
        self._note(node, probe_alive=self._alive(probe.weights()),
                   build_alive=build_alive,
                   out_alive=self._alive(joined.weights()))

    def _ptopk(self, node: PH.PTopK) -> Dict[str, jax.Array]:
        if node.dist != "candidates":
            # "replicated": select on the merged (replicated) group table
            # — the inherited single-device lowering is already correct
            return super()._ptopk(node)
        # candidates: the child is a gather Exchange over the aggregate.
        # Each shard owns a contiguous slot range of the group table
        # (ceil(G/n) slots), selects its local top-k with GLOBAL slot
        # indices, and only the (k,) candidate pairs converge. Bit-exact
        # vs the replicated lowering: within a shard lax.top_k breaks
        # ties by ascending index, the shard-major all_gather preserves
        # ascending global index among equal values across shards, and
        # the final lax.top_k over the k*n candidates breaks its ties by
        # candidate position — which is exactly ascending global index.
        ex = node.child
        g = self.run(ex.child)
        vals = g[node.col]
        G = vals.shape[0]
        n, axis = self.n, self.ctx.axis
        slots = (G + (-G % n)) // n
        me = jax.lax.axis_index(axis)
        owned = (jnp.arange(G) // slots) == me
        local_vals, local_idx = jax.lax.top_k(
            jnp.where(owned, vals, -jnp.inf), node.k)
        cand_vals = jax.lax.all_gather(local_vals, axis, tiled=True)
        cand_idx = jax.lax.all_gather(local_idx, axis, tiled=True)
        if self.record:
            # the gather's wire volume: k candidate rows per shard, each
            # landing on the n-1 shards that did not produce it
            self._note(ex, alive_in=node.k * n,
                       moved=node.k * (n - 1) * n)
        top_vals, pos = jax.lax.top_k(cand_vals, node.k)
        return {node.col: top_vals, node.index_name: cand_idx[pos]}

    def _ppartialaggregate(self, node: PH.PPartialAggregate):
        """Local (n_groups, C) stacked partial sums — the below-the-
        exchange half of push-down and of the FT/LA partial-table merges."""
        t = self.run(node.child)
        keys, cols, _src = stacked_columns(t, node.key, node.n_groups,
                                           dict(node.aggs))
        return stacked_group_sums(
            keys, cols, node.n_groups, layout=node.layout,
            mode=self.ctx.mode, n_partitions=self.ctx.n_partitions,
            capacity_factor=self.agg_cf)

    def _table_source(self, node: PH.PNode) -> PH.PNode:
        """The table-producing node under an aggregate's movement/partial
        wrappers — order statistics and holistic medians must see the
        records exactly once, BEFORE any exchange."""
        while isinstance(node, (PH.Exchange, PH.PPartialAggregate)):
            node = node.child
        return node

    def _paggregate(self, node: PH.PAggregate) -> Dict[str, jax.Array]:
        if node.key is None:
            return self._dist_scalar_aggregate(node,
                                               self.run(node.child))
        t = self.run(self._table_source(node.child))
        G = node.n_groups
        dist_aggs = tuple((nm, oc) for nm, oc in node.aggs
                          if not is_holistic(oc[0]))
        med_out, med_counts, med_ovf = self._dist_medians(node, t, G)
        if not dist_aggs:
            # holistic-only aggregate: counts come from the selection path
            # — no second routing/merge pass just for _count
            out = dict(med_out)
            out["_count"] = med_counts
            out["_overflow"] = med_ovf
            self.overflow = self.overflow + med_ovf
            if self.record:
                self._note(node,
                           groups_occupied=(out["_count"] > 0).sum())
            return out
        sums, overflow = self._merged_sums(node, t, G, dist_aggs)
        out = finalize_stacked(dict(dist_aggs), _stacked_src(dist_aggs),
                               sums, self._order_stat_fn(t, node, G))
        out.update(med_out)
        out["_overflow"] = overflow.astype(jnp.int32) + med_ovf
        self.overflow = self.overflow + out["_overflow"]
        if self.record:
            self._note(node, groups_occupied=(out["_count"] > 0).sum())
        return out

    def _merged_sums(self, node: PH.PAggregate, t: Table, G: int,
                     dist_aggs) -> Tuple[jax.Array, jax.Array]:
        """The distributive stacked-sums table under ``node.merge``."""
        axis, n = self.ctx.axis, self.n
        merge = node.merge
        if merge in ("psum", "reduce_scatter"):
            # child is the fused allreduce/reduce_scatter Exchange (the
            # priced movement node); the partial table comes from BELOW it
            partial, ovf = self.run(node.child.child)
            policy = (PlacementPolicy.FIRST_TOUCH if merge == "psum"
                      else PlacementPolicy.LOCAL_ALLOC)
            return (merge_partial_table(partial, policy, axis, n),
                    jax.lax.psum(ovf, axis))
        if merge == "pushdown":
            partial, ovf = self.run(node.child.child)
            sums, route_ovf = pushdown_group_sums(
                partial, G, axis, n,
                capacity_factor=self.ctx.capacity_factor,
                capacity=node.child.capacity)
            return sums, jax.lax.psum(ovf, axis) + route_ovf
        if merge == "placed":
            # route-once: every group's rows are co-located, so the
            # per-shard tables are DISJOINT and the psum is exact
            keys, cols, _ = stacked_columns(t, node.key, G, dict(dist_aggs))
            sums, ovf = self._stacked(keys, cols, G, node.layout)
            return jax.lax.psum(sums, axis), jax.lax.psum(ovf, axis)
        if merge == "owner":
            keys, cols, _ = stacked_columns(t, node.key, G, dict(dist_aggs))
            agg_fn = functools.partial(self._stacked, layout=node.layout)
            # the Exchange node's capacity drives the routing: execution
            # can never drift from the rendered physical plan
            return interleave_group_sums(
                keys, jnp.stack(cols, axis=1), G, axis, n, agg_fn,
                capacity_factor=self.ctx.capacity_factor,
                capacity=node.child.capacity)
        if merge == "gather":
            keys, cols, _ = stacked_columns(t, node.key, G, dict(dist_aggs))
            ak, acols = gather_rows((keys, cols), axis)
            return self._stacked(ak, acols, G, node.layout)
        raise ValueError(f"unknown aggregate merge {merge!r}")

    def _stacked(self, keys, cols, n_groups, layout):
        return stacked_group_sums(
            keys, cols, n_groups, layout=layout, mode=self.ctx.mode,
            n_partitions=self.ctx.n_partitions, capacity_factor=self.agg_cf)

    def _order_stat_fn(self, t: Table, node: PH.PAggregate, G: int):
        keys = jnp.clip(t.col(node.key), 0, G - 1).astype(jnp.int32)

        def order_stat(op, col):
            # local segment op, then a cross-shard tree reduction
            local = segment_order_stat(t, keys, G, op, col)
            reduce = jax.lax.pmax if op == "max" else jax.lax.pmin
            return reduce(local, self.ctx.axis)

        return order_stat

    def _dist_medians(self, node: PH.PAggregate, t: Table, G: int
                      ) -> Tuple[Dict[str, jax.Array], Optional[jax.Array],
                                 jax.Array]:
        """Per-policy lowering of an Aggregate's holistic (median/
        quantile) aggs.

        Order statistics cannot merge from partials, so they bypass the
        stacked-sums collectives entirely: ``med_strategy`` "replicate"
        gathers the records (the paper's holistic worst case), "route"
        sends each group's records to its owner and selects there
        (distributed selection). Returns ({name: (G,) stats},
        counts-or-None, overflow), all replicated in natural group
        order."""
        axis, n = self.ctx.axis, self.n
        med_aggs = tuple((nm, oc) for nm, oc in node.aggs
                         if is_holistic(oc[0]))
        if not med_aggs:
            return {}, None, jnp.zeros((), jnp.int32)
        keys = jnp.clip(t.col(node.key), 0, G - 1).astype(jnp.int32)
        w = t.weights()
        cols = {name: t.col(colname).astype(jnp.float32)
                for name, (_op, colname) in med_aggs}
        ranks = {name: holistic_selector(op)
                 for name, (op, _c) in med_aggs}          # None = median
        if node.med_strategy == "route":
            meds, counts, ovf = interleave_group_median(
                keys, cols, w, G, axis, n,
                capacity_factor=self.ctx.capacity_factor, ranks=ranks)
            return meds, counts, ovf.astype(jnp.int32)
        if node.med_strategy == "placed":
            # route-once: the child is already placed by the group key,
            # select on the owner shard and psum the masked results
            meds, counts = placed_group_median(keys, cols, w, G, axis,
                                               ranks=ranks)
            return meds, counts, jnp.zeros((), jnp.int32)
        meds, counts = replicated_group_median(keys, cols, w, G, axis,
                                               ranks=ranks)
        return meds, counts, jnp.zeros((), jnp.int32)

    def _dist_scalar_aggregate(self, node: PH.PAggregate,
                               t: Table) -> Dict[str, jax.Array]:
        """Global aggregate: merge the SUMS across shards (an average of
        per-shard averages would weight shards, not rows)."""
        axis = self.ctx.axis
        w = t.weights()
        cnt = jax.lax.psum(w.sum(), axis)[None]
        out: Dict[str, jax.Array] = {}
        med_cols: Dict[str, jax.Array] = {}
        med_ranks: Dict[str, object] = {}    # holistic_selector values
        for name, (op, col) in node.aggs:
            if op == "count":
                out[name] = cnt
                continue
            v = t.col(col).astype(jnp.float32)
            if op in ("sum", "avg"):
                s = jax.lax.psum((v * w).sum(), axis)[None]
                out[name] = s if op == "sum" else s / jnp.maximum(cnt, 1.0)
            elif op == "max":
                out[name] = jax.lax.pmax(
                    jnp.where(w > 0, v, -jnp.inf).max(), axis)[None]
            elif op == "min":
                out[name] = jax.lax.pmin(
                    jnp.where(w > 0, v, jnp.inf).min(), axis)[None]
            elif is_holistic(op):
                med_cols[name] = v       # batched below: gather rows once
                med_ranks[name] = holistic_selector(op)
            else:
                raise ValueError(f"unknown agg op {op!r}")
        if med_cols:
            # holistic: converge the records ONCE, select per column
            meds, _ = replicated_group_median(
                jnp.zeros_like(w, jnp.int32), med_cols, w, 1, axis,
                ranks=med_ranks)
            out.update(meds)
        out["_count"] = cnt
        out["_overflow"] = jnp.zeros((), jnp.int32)
        return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def _signature(tables) -> Tuple:
    return tuple(sorted((t, c, tuple(a.shape), str(a.dtype))
                        for t, cols in tables.items()
                        for c, a in cols.items()))


def table_signature(tables) -> Tuple:
    """Public shape signature of a {table: {column: array}} pytree — the
    axis of the plan-cache key that identifies "structurally identical
    data" (stable across dict rebuilds; the serving batcher groups on
    it)."""
    return _signature(tables)


def cached_executable(key: Tuple, build):
    """Fetch-or-build an executable in the shared bounded plan LRU.

    Public seam for auxiliary executables that must live under the same
    cache bound and thread-safety as compiled plans (e.g. the serving
    scheduler's per-morsel partial-aggregation functions). ``key`` should
    start with a distinguishing tag so it can never collide with
    compile_plan's (plan, ctx, signature, profile) keys."""
    fn = _PLAN_CACHE.get(key)
    if fn is None:
        fn = build()
        _PLAN_CACHE.put(key, fn)
    return fn


def _true_rows(tables) -> Dict[str, int]:
    return {t: next(iter(cols.values())).shape[0]
            for t, cols in tables.items()}


# ---------------------------------------------------------------------------
# wire volume: what a mesh plan's Exchanges move between chips per call
# ---------------------------------------------------------------------------
class ExchangeWire(NamedTuple):
    """Bytes one chip receives from the other chips per call through a
    plan's Exchanges, and the number of distinct Exchange nodes."""
    bytes: int
    exchanges: int


_F32 = jnp.dtype(jnp.float32)
_I32 = jnp.dtype(jnp.int32)


def exchange_wire(phys: PH.PhysicalPlan, tables) -> ExchangeWire:
    """The wire volume of ``phys`` from its static buffer shapes and the
    column dtypes of ``tables`` (arrays or ShapeDtypeStructs), counted as
    the distributed executor moves the data. With n shards, per call and
    per chip:

    - broadcast: (n-1) x the child's rows per shard x (its columns + the
      float32 mask);
    - hash on a key, run alone (partitioned-join sides): (n-1) x
      ``capacity`` x (the child's columns + the float32 weights);
    - hash fused into an owner or pushdown merge: (n-1) x ``capacity`` x
      (int32 key + C float32 stacked columns, weights first), plus the
      all-gather that republishes the merged (ceil(G/n), C) table;
    - gather fused into a PREFERRED PAggregate: (n-1) x rows x (int32 key
      + C float32 columns); the candidates TopK's gather: (n-1) x k x
      (float32 value + int32 slot);
    - allreduce and reduce_scatter merges of the (G, C) partial table,
      and the route-once (``placed``) merge's psum of it, which replaced
      an Exchange: 2 (n-1)/n of the table, as a ring all-reduce receives
      it.

    ``capacity`` counts every slot, padding included: this is what the
    links carry, where ``explain``'s ``moved~`` estimates the live rows
    only. Scalar psums and the order-statistic (median, quantile) routes
    are not counted. Host-side arithmetic: nothing of it enters the
    jitted program."""
    n = phys.n_shards
    count = len(PH.exchanges(phys.root))
    if n == 1:
        return ExchangeWire(0, count)
    dtypes = {t: {c: jnp.dtype(a.dtype) for c, a in cols.items()}
              for t, cols in tables.items()}
    cols_memo: Dict[PH.PNode, Dict[str, jnp.dtype]] = {}
    seen = set()
    total = 0

    def cols(node) -> Dict[str, jnp.dtype]:
        """Column dtypes of a table-producing node's output."""
        hit = cols_memo.get(node)
        if hit is not None:
            return hit
        if isinstance(node, PH.PScan):
            out = dtypes[node.table]
        elif isinstance(node, (PH.PFilter, PH.Compact, PH.Exchange)):
            out = cols(node.child)
        elif isinstance(node, PH.PProject):
            out = dict(cols(node.child))
            out.update({name: _expr_dtype(e, cols(node.child))
                        for name, e in node.cols})
        elif isinstance(node, PH.PJoin):
            build = cols(node.build)
            out = dict(cols(node.probe))
            out.update({new: build[src] for new, src in node.take})
        elif isinstance(node, PH.PAttach):
            out = dict(cols(node.child))
            index = (node.source.index_name
                     if isinstance(node.source, PH.PTopK) else None)
            out.update({new: _I32 if src == index else _F32
                        for new, src in node.cols})
        else:
            raise TypeError(f"{type(node).__name__} is no table")
        cols_memo[node] = out
        return out

    def row_bytes(node) -> int:
        return sum(d.itemsize for d in cols(node).values()) + _F32.itemsize

    def visit(node) -> None:
        nonlocal total
        if node in seen:
            return
        seen.add(node)
        if isinstance(node, PH.Exchange):
            # a standalone table Exchange (the fused kinds are counted by
            # the PAggregate / PTopK that runs them)
            per_shard = (node.child.rows if node.kind == "broadcast"
                         else node.capacity)
            total += (n - 1) * per_shard * row_bytes(node.child)
        elif isinstance(node, PH.PAggregate) and node.key is not None:
            total += _merge_bytes(node, n)
            while isinstance(node, (PH.PAggregate, PH.Exchange,
                                    PH.PPartialAggregate)):
                node = node.child          # the records the merge reads
            visit(node)
            return
        elif isinstance(node, PH.PTopK) and node.dist == "candidates":
            total += (n - 1) * node.k * (_F32.itemsize + _I32.itemsize)
            visit(node.child.child)
            return
        for c in PH.children(node):
            visit(c)

    visit(phys.root)
    return ExchangeWire(total, count)


def _merge_bytes(node: PH.PAggregate, n: int) -> int:
    """Wire bytes of a grouped PAggregate's fused merge (see
    ``exchange_wire``)."""
    C = stacked_width(tuple(a for a in node.aggs if not is_holistic(a[1][0])))
    G, f = node.n_groups, _F32.itemsize
    if node.merge in ("owner", "pushdown"):
        republish = (n - 1) * -(-G // n) * C * f
        return (n - 1) * node.child.capacity * (_I32.itemsize + C * f) \
            + republish
    if node.merge == "gather":
        return (n - 1) * node.child.child.rows * (_I32.itemsize + C * f)
    if node.merge in ("psum", "placed"):
        return 2 * (n - 1) * G * C * f // n
    if node.merge == "reduce_scatter":
        return 2 * (n - 1) * (G + (-G % n)) * C * f // n
    return 0                        # holistic: order statistics only


def _expr_dtype(e: L.Expr, dtypes: Dict[str, jnp.dtype]) -> jnp.dtype:
    """The dtype ``eval_expr`` gives ``e`` over columns of ``dtypes``,
    by abstract evaluation (no device work)."""
    shapes = {c: jax.ShapeDtypeStruct((1,), d) for c, d in dtypes.items()}
    out = jax.eval_shape(lambda t: eval_expr(e, Table(t)), shapes)
    return jnp.dtype(out.dtype)


def _run_local(phys: PH.PhysicalPlan, ctx: ExecutionContext, profile,
               record, tables, indexes, layouts):
    ex = _LocalExecutor(tables, ctx, indexes, profile, record, layouts)
    return ex.execute(phys)


def _run_distributed(phys: PH.PhysicalPlan, ctx: ExecutionContext, profile,
                     record, tables, indexes, layouts):
    # full-table indexes and layouts don't survive the row padding
    del indexes, layouts
    mesh, axis = ctx.mesh, ctx.axis
    n = mesh.shape[axis]
    rows = _true_rows(tables)
    padded = {}
    for t, cols in tables.items():
        r = rows[t]
        pad = -r % n
        pcols = {c: jnp.pad(jnp.asarray(a), [(0, pad)] + [(0, 0)]
                            * (jnp.asarray(a).ndim - 1))
                 for c, a in cols.items()}
        pcols["_valid"] = (jnp.arange(r + pad) < r).astype(jnp.float32)
        padded[t] = pcols

    def local_fn(local_tables):
        ex = _DistributedExecutor(local_tables, ctx, n, profile, record)
        return ex.execute(phys)

    specs = jax.tree_util.tree_map(lambda _: P(axis), padded)
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(specs,),
                         out_specs=P(), check_vma=False)(padded)


def _run_plan(phys: PH.PhysicalPlan, ctx: ExecutionContext, profile,
              record, tables, indexes, layouts):
    run = _run_local if ctx.mesh is None else _run_distributed
    return run(phys, ctx, profile, record, tables, indexes, layouts)


def _jit_plan(plan: L.LogicalPlan, phys: PH.PhysicalPlan,
              ctx: ExecutionContext, profile, record: bool):
    """The plan's executable, as a function named ``plan_<name>``: its
    XLA module reads ``jit_plan_<name>`` in a profile (a bare partial
    would read ``jit__unknown``). ``layouts`` are the pooled kernel-join
    layouts by ``LayoutSpec.key``; a join whose layouts are absent builds
    them in the plan."""
    def run(tables, indexes, layouts=None):
        return _run_plan(phys, ctx, profile, record, tables, indexes,
                         layouts)
    run.__name__ = run.__qualname__ = (f"plan_{plan.name}" if plan.name
                                       else "plan")
    return jax.jit(run)


class CompiledPlan:
    """Re-entrant dispatch handle for one (plan, context, shape signature).

    ``compile_plan`` resolves the plan-cache entry ONCE; the handle can then
    be called from any worker thread without touching the planner again —
    only the join-index pool is consulted per call (a lock-protected LRU
    hit), so concurrent dispatch never re-plans, re-jits, or races an
    eviction. This is the entry point the serving scheduler pins into its
    worker pools. ``physical`` is the explicit physical plan the
    executable walks — the plan-cache value, inspectable per handle.

    When compiled under telemetry (``record``), each call strips the
    reserved ``"_stats"`` output, materializes it (one device_get — the
    price of observing), and folds it into the StatsRegistry under
    ``cache_key`` together with the dispatch wall time. Every dispatch
    path — serial execute_plan, the serving scheduler's whole-plan morsel
    tasks — goes through this one __call__, so the registry sees them
    all.

    ``wire`` is the plan's ``exchange_wire``, computed with the cache
    entry; each ``plan.dispatch`` span carries it as ``exchange_bytes``
    and ``exchanges``. ``joins`` is the plan's ``kernel_joins``: the
    layouts each call takes from the JoinIndexPool, and the counts each
    ``plan.dispatch`` span carries as ``hoisted_joins`` and
    ``inline_joins``."""

    __slots__ = ("plan", "ctx", "fn", "index_specs", "physical",
                 "cache_key", "record", "wire", "joins")

    def __init__(self, plan: L.LogicalPlan, ctx: ExecutionContext, fn,
                 index_specs: Tuple[Tuple[str, str], ...],
                 physical: PH.PhysicalPlan, cache_key: Tuple = (),
                 record: bool = False,
                 wire: ExchangeWire = ExchangeWire(0, 0),
                 joins: KernelJoins = KernelJoins()):
        self.plan = plan
        self.ctx = ctx
        self.fn = fn
        self.index_specs = index_specs
        self.physical = physical
        self.cache_key = cache_key
        self.record = record
        self.wire = wire
        self.joins = joins

    def __call__(self, tables) -> Dict[str, jax.Array]:
        # the tracing flag is read HERE, per dispatch — it is deliberately
        # NOT part of the plan-cache key: plan.dispatch is a host-side span
        # around an unchanged executable, so flipping it must never re-jit
        # (only telemetry's ``record`` adds traced operations). The span
        # ends when the call returns: the index lookups and the enqueue,
        # not the device's work (the caller's block_until_ready waits
        # for that); the request is the one the calling thread works for
        if not tracing.tracing_enabled():
            return self._execute(tables)
        t0 = time.perf_counter()
        out = self._execute(tables)
        tracing.tracer().add_complete(
            "plan.dispatch", "plan", t0, time.perf_counter(),
            trace_id=tracing.current_trace_id(), pid="plan",
            plan=self.plan.name, key=hash(self.cache_key),
            recorded=self.record, exchange_bytes=self.wire.bytes,
            exchanges=self.wire.exchanges, hoisted_joins=self.joins.hoisted,
            inline_joins=self.joins.inline)
        return out

    def _indexes(self, tables) -> Tuple[Dict[str, Tuple[jax.Array,
                                                        jax.Array]],
                                        Dict[str, JoinSide]]:
        """(build argsorts, kernel-join layouts) from the JoinIndexPool;
        a mesh plan takes neither."""
        if self.ctx.mesh is not None:
            return {}, {}
        return ({f"{t}.{c}": _INDEX_POOL.get(t, c, tables[t][c])
                 for t, c in self.index_specs},
                {sp.key: _INDEX_POOL.layout(sp.table, sp.column,
                                            tables[sp.table][sp.column],
                                            sp.n_partitions, sp.cap, sp.side)
                 for sp in self.joins.layouts})

    def lower(self, tables):
        """The executable's lowering for ``tables``, join indexes and
        layouts built as a dispatch builds them: ``.compile()`` on it
        compiles the plan ahead of its first run (into the persistent
        compile cache, where that is on)."""
        return self.fn.lower(tables, *self._indexes(tables))

    def _execute(self, tables) -> Dict[str, jax.Array]:
        indexes, layouts = self._indexes(tables)
        if not self.record:
            return self.fn(tables, indexes, layouts)
        t0 = time.perf_counter()
        out = dict(self.fn(tables, indexes, layouts))
        stats = out.pop("_stats", None)
        if stats is not None:
            concrete = {int(i): {k: int(v) for k, v in
                                 jax.device_get(vals).items()}
                        for i, vals in stats.items()}
            telemetry.registry().record(self.cache_key, self.physical,
                                        concrete,
                                        time.perf_counter() - t0)
        return out


def compile_plan(plan: L.LogicalPlan, tables,
                 ctx: Optional[ExecutionContext] = None) -> CompiledPlan:
    """Lower to a physical plan and resolve (or build) its executable.

    ``tables`` supplies only the shape signature — the returned handle runs
    on ANY tables pytree of the same shapes. The active CostProfile is
    snapshotted ONCE: it keys the cache AND parameterizes the lowering, so
    a concurrent recalibration can never plan under the new constants but
    cache under the old key. The cache VALUE is the (physical plan, jitted
    executable, wire volume, kernel joins) tuple — the physical tree is
    the product, the jit its interpretation, ``exchange_wire`` its
    Exchanges' bytes and ``kernel_joins`` its pooled join layouts
    (outside the key and the jit)."""
    ctx = ctx or ExecutionContext()
    profile = current_cost_profile()
    record = telemetry.telemetry_enabled()
    # the telemetry flag keys the cache: a recording jit carries extra
    # traced outputs, so it can never be served to an untracked caller
    key = (plan, ctx.cache_key(), _signature(tables), profile, record)
    entry = _PLAN_CACHE.get(key)
    if entry is None:
        traced = tracing.tracing_enabled()
        t0 = time.perf_counter() if traced else 0.0
        L.validate(plan)     # fail fast (and once) instead of mid-trace
        phys = lower(plan, ctx, _true_rows(tables), profile)
        fn = _jit_plan(plan, phys, ctx, profile, record)
        entry = (phys, fn, exchange_wire(phys, tables),
                 kernel_joins(phys, ctx))
        _PLAN_CACHE.put(key, entry)
        if traced:
            # lowering + jit construction, which a cache hit amortizes
            # away; XLA compiles at the first call (or ``lower().compile()``)
            tracing.tracer().add_complete(
                "plan.lower", "plan", t0, time.perf_counter(), pid="plan",
                plan=plan.name, key=hash(key))
    elif record:
        entry = _maybe_replan(key, entry, plan, ctx, profile, tables)
    phys, fn, wire, joins = entry
    return CompiledPlan(plan, ctx, fn, required_indexes(plan.root), phys,
                        key, record, wire, joins)


def _maybe_replan(key, entry, plan, ctx, profile, tables):
    """Adaptive re-planning on a plan-cache HIT: when the registry marked
    this plan as drifting, re-lower with the OBSERVED per-join alive rows
    and swap the cache entry if any Decision flipped. Results stay
    bit-identical — the observed hook only steers the broadcast-vs-
    partitioned cost choice, never the relational answer — and a
    re-lowering whose decisions all stand produces a structurally
    identical tree, so the existing jit keeps serving."""
    reg = telemetry.registry()
    if not reg.should_replan(key):
        return entry
    reg.note_replan_checked(key)
    phys = lower(plan, ctx, _true_rows(tables), profile,
                 observed=reg.observed_joins(key))
    if phys == entry[0]:
        return entry
    fn = _jit_plan(plan, phys, ctx, profile, True)
    entry = (phys, fn, exchange_wire(phys, tables), kernel_joins(phys, ctx))
    _PLAN_CACHE.put(key, entry)
    reg.note_replanned(key, phys)
    return entry


def execute_plan(plan: L.LogicalPlan, tables,
                 ctx: Optional[ExecutionContext] = None
                 ) -> Dict[str, jax.Array]:
    """Compile (through the LRU plan cache) and run a logical plan.

    ``tables``: {table: {column: array}} pytree, passed to the compiled
    plan as traced arguments — one compilation serves any data of the same
    shape signature. Build-side join indexes are pulled from the
    JoinIndexPool and traced in alongside."""
    return compile_plan(plan, tables, ctx)(tables)


# ---------------------------------------------------------------------------
# explain: decisions + physical-tree rendering
# ---------------------------------------------------------------------------
def _strip_movement(node: PH.PNode) -> PH.PNode:
    """The record-producing node under movement/partial wrappers — what
    explain() reports row counts from (a split aggregate's input is its
    records, not its (n_groups, C) partial table)."""
    while isinstance(node, (PH.Exchange, PH.Compact,
                            PH.PPartialAggregate)):
        node = node.child
    return node


def explain(plan: L.LogicalPlan, tables,
            ctx: Optional[ExecutionContext] = None) -> List[Decision]:
    """The planner's choices from shape metadata alone (no execution):
    one Decision per Join / grouped Aggregate — plus, since the physical
    layer, per Exchange (kind + estimated moved rows) and per Compact —
    in plan order. Decisions are derived from the SAME lower() pass that
    produces the executed physical plan, so explain can never drift from
    execution."""
    ctx = ctx or ExecutionContext()
    phys = lower(plan, ctx, _true_rows(tables))
    n = phys.n_shards
    decisions: List[Decision] = []
    seen = set()

    def visit(node: PH.PNode) -> None:
        if node in seen:         # structural dedup == executor memoization
            return
        seen.add(node)
        for c in PH.children(node):
            visit(c)
        if isinstance(node, PH.PJoin):
            probe = _strip_movement(node.probe)
            build = _strip_movement(node.build)
            if node.dist is not None:
                decisions.append(Decision(
                    "DistJoin", f"{node.probe_key}={node.build_key}, "
                    f"probe={probe.rows * n}, build={build.rows * n}, "
                    f"shards={n}", node.dist,
                    tuple(dist_join_costs(probe.rows * n, build.rows * n,
                                          n).items())))
            else:
                decisions.append(Decision(
                    "Join", f"{node.probe_key}={node.build_key}, "
                    f"probe={probe.rows}, build={build.rows}",
                    node.strategy))
        elif isinstance(node, PH.Exchange):
            # key=None marks a partial-sums routing ONLY for hash
            # exchanges; broadcast/gather move whole tables and carry no
            # routing key at all
            if node.key is not None:
                detail = f"kind={node.kind}, key={node.key}"
                # key-routing hash exchange: the layout-pass impl is a
                # planner choice, priced alongside the wire estimate
                # (moved_rows stays FIRST — consumers index costs[0])
                costs = ((("moved_rows", float(node.moved_rows)),)
                         + tuple(exchange_costs(node.child.rows).items()))
                decisions.append(Decision(
                    "Exchange", f"{detail}, rows={node.rows}",
                    f"{node.kind}/{node.impl}", costs))
                return
            if node.kind == "hash":
                detail = f"kind={node.kind}, key=<group-partials>"
            else:
                detail = f"kind={node.kind}"
            decisions.append(Decision(
                "Exchange", f"{detail}, rows={node.rows}", node.kind,
                (("moved_rows", float(node.moved_rows)),)))
        elif isinstance(node, PH.PFilter) and node.pushed:
            decisions.append(Decision(
                "FilterBelowExchange", L.expr_str(node.pred),
                "pushed"))
        elif isinstance(node, PH.PTopK) and node.dist is not None:
            G = _strip_movement(node.child).rows
            decisions.append(Decision(
                "DistTopK", f"col={node.col}, k={node.k}, groups={G}, "
                f"shards={n}", node.dist,
                tuple(topk_costs(G, node.k, n).items())))
        elif isinstance(node, PH.Compact):
            decisions.append(Decision(
                "Compact", f"capacity={node.capacity}, "
                f"from={node.child.rows}", "compact",
                (("rows_cut", float(node.child.rows - node.capacity)),)))
        elif isinstance(node, PH.PAggregate) and node.key is not None:
            N = _strip_movement(node.child).rows
            C = stacked_width(node.aggs)
            G = node.n_groups
            # cost basis = the inputs the layout was actually CHOSEN from
            # (lower's per-merge arithmetic), so the printed table can
            # justify the printed choice: owner-merge aggregates run on
            # the routed buffer over per-shard slots, gather-merge on the
            # converged rows, everything else on the record input
            if node.merge == "owner" and isinstance(node.child, PH.Exchange):
                cost_n = node.child.rows
                cost_g = (G + (-G % n)) // n + 1
            elif node.merge == "gather":
                cost_n, cost_g = N * n, G
            else:
                cost_n, cost_g = N, G
            detail = f"key={node.key}, rows={N}, groups={G}, cols={C}"
            if node.merge is not None:
                detail += f", merge={node.merge}"
            decisions.append(Decision(
                "Aggregate", detail, node.layout,
                tuple(aggregate_costs(cost_n, cost_g, C).items())))

    visit(phys.root)
    return decisions


def explain_physical(plan: L.LogicalPlan, tables,
                     ctx: Optional[ExecutionContext] = None,
                     n_shards: Optional[int] = None) -> str:
    """Render the lowered physical tree (physical.describe): Exchange
    kinds with estimated moved rows, compaction points, resolved join/
    aggregate strategies. Deterministic for fixed table shapes — the
    golden-snapshot format. ``n_shards`` lowers for a mesh width without
    materializing devices."""
    ctx = ctx or ExecutionContext()
    return PH.describe(lower(plan, ctx, _true_rows(tables),
                             n_shards=n_shards))


# explain_analyze — the executable twin of explain_physical (runs the
# plan under telemetry and annotates the tree with observed rows) —
# lives in repro.analytics.telemetry; re-exported here for symmetry.
explain_analyze = telemetry.explain_analyze
