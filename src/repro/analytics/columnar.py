"""Physical columnar operators (the W5 "database system" layer).

A Table is a struct-of-arrays with static length; selection is mask-based
(TPU-friendly: no compaction, predicates become aggregation weights), joins
are PK-FK gathers through a sorted index, and aggregations are masked
segment ops.  This module is the *physical operator library*: queries are
authored as logical plans (plan.py) and the cost-based planner (planner.py)
lowers each logical node onto one of the operators here.

Grouped aggregation has three physical layouts the planner chooses between
per Aggregate node — see planner.choose_aggregate for the cost model:

  "xla"          one XLA segment scatter per summed column — the naive plan
                 a query compiler emits without memory tuning. N passes
                 over the table for N aggregates.
  "dense"        fused-kernel sweep with positionally-chunked full-width
                 tables (no sort; exact): every (sum, avg, count) aggregate
                 over one key column rides in its own measure column, and
                 all are swept in ONE pass through the hash_aggregate Pallas
                 kernel (VMEM-resident tables — the paper's
                 partition-then-per-thread-table recipe). Valid for key
                 domains up to DENSE_GROUP_LIMIT.
  "partitioned"  fused-kernel sweep after a range-partitioning pass, so each
                 partition's table stays narrow; overflow is counted exactly
                 (never dropped silently). Pays an argsort of the keys —
                 worthwhile only when many aggregates amortize it.

Order statistics (max/min/median) are not distributive sums and stay on
exact XLA lowerings under every layout — max/min on segment ops, median on
the ``segment_median`` sort-based selection (holistic: a group's median
needs all of its values co-located, paper Section 2).  ``group_aggregate``'s string ``executor``
knob ("xla" picks the first layout, "kernel" the domain-appropriate fused
one) is kept as the untuned/tuned axis the Fig 8/9 benchmark measures.

PK-FK joins have two physical forms: ``pkfk_join`` (sorted-index
searchsorted gather; the build-side argsort is cached per Table and
propagated through filter/with_columns/join derivations — and hoisted out
of the compiled plan entirely by planner.JoinIndexPool) and
``pkfk_join_kernel`` (hash-partition both sides, probe through the
kernels/join_probe broadcast-compare kernel; capacity overflow triggers a
residual re-probe of the kernel's misses through the sorted path, so
skewed keys stay exact — or is counted and surfaced with residual=False).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from repro.analytics.hashing import pad_partitions, partition_of
from repro.analytics.plan import is_holistic, parse_quantile
from repro.kernels.hash_aggregate import hash_aggregate
from repro.kernels.hash_aggregate.kernel import (STEP_TILES, TILE,
                                                 padded_tiles)
from repro.kernels.join_probe import join_probe

# Largest key domain aggregated with full-width per-chunk tables (the
# kernel's one-hot is (n_bins, 128): 4096 x 128 fp32 = 2 MB VMEM per
# row). Beyond this the kernel path range-partitions so each partition
# table stays narrow.
DENSE_GROUP_LIMIT = 4096
# Largest (chunks x groups) table of the XLA layout's chunked sums.
SUM_CHUNK_TABLE = 1 << 22
# Rows below which an f32 sum of 0/1 weights is an exact count.
F32_EXACT_COUNT = 1 << 24
# Widest partition table of the range-partitioned layout: the partition
# count grows with the group count so the one-hot stays VMEM-sized.
MAX_PARTITION_BINS = 2048
# Kernel join bounds: the build tile each probe row is compared against
# (the partition count grows with the build side to keep it), and the rows
# per side (row positions ride through the kernel as exact f32 integers).
MAX_BUILD_TILE = 4096
KERNEL_JOIN_MAX_ROWS = 1 << 24


@dataclass
class Table:
    columns: Dict[str, jax.Array]
    mask: Optional[jax.Array] = None     # float32 selection weights (None = 1)
    # name -> (order, sorted_keys) argsort cache for join build sides.
    # Shared with derived tables whose column arrays are unchanged; entries
    # for overwritten columns are dropped at derivation time.
    index_cache: Dict[str, Tuple[jax.Array, jax.Array]] = field(
        default_factory=dict, repr=False)

    def __post_init__(self):
        lens = {c.shape[0] for c in self.columns.values()}
        if len(lens) != 1:
            raise ValueError(f"ragged table: {lens}")

    @property
    def n_rows(self) -> int:
        return next(iter(self.columns.values())).shape[0]

    def col(self, name: str) -> jax.Array:
        return self.columns[name]

    def weights(self) -> jax.Array:
        if self.mask is None:
            return jnp.ones((self.n_rows,), jnp.float32)
        return self.mask

    def key_index(self, name: str) -> Tuple[jax.Array, jax.Array]:
        """(order, sorted_keys) for ``name``, built once per column array.

        Never caches a TRACER computed from a concrete column: a Table that
        outlives the trace it was first joined in (e.g. an eager dimension
        table closed over by a jitted query) would otherwise serve a dead
        trace's tracer to every later call."""
        hit = self.index_cache.get(name)
        if hit is None:
            k = self.columns[name]
            order = jnp.argsort(k)
            hit = (order, k[order])
            if not (isinstance(order, jax.core.Tracer)
                    and not isinstance(k, jax.core.Tracer)):
                self.index_cache[name] = hit
        return hit

    def filter(self, pred: jax.Array) -> "Table":
        """AND a predicate into the selection mask (no data movement)."""
        w = self.weights() * pred.astype(jnp.float32)
        return Table(self.columns, w, self.index_cache)

    def with_columns(self, **cols: jax.Array) -> "Table":
        merged = dict(self.columns)
        merged.update(cols)
        cache = {k: v for k, v in self.index_cache.items() if k not in cols}
        return Table(merged, self.mask, cache)


def concat_slices(parts):
    """Concatenate (columns, mask) row-slice pairs, in order, into one
    (columns, mask) pair.

    The merge primitive of the serving tier's split-probe path: each part
    is one morsel's slice of a per-row pipeline's output, so concatenation
    in slice order rebuilds the unsliced table bit-for-bit (a row concat,
    never a float re-ordering). ``mask`` is None only when every part's
    mask is None (a maskless pipeline stays maskless)."""
    cols0, mask0 = parts[0]
    cols = {c: jnp.concatenate([p[0][c] for p in parts]) for c in cols0}
    mask = (None if mask0 is None
            else jnp.concatenate([p[1] for p in parts]))
    return cols, mask


def pkfk_join(fact: Table, dim: Table, fact_key: str, dim_key: str,
              take: Mapping[str, str]) -> Table:
    """Gather dim columns into the fact table through the PK (sorted index).

    ``take`` maps new-column-name -> dim-column-name. Misses zero the mask.
    The build-side sorted index comes from ``dim.key_index`` — cached on the
    Table, so joining the same dimension (or a filtered view of it) again
    re-uses the argsort instead of re-sorting per call site.
    """
    order, sk = dim.key_index(dim_key)
    pos = jnp.clip(jnp.searchsorted(sk, fact.col(fact_key)), 0, sk.shape[0] - 1)
    found = sk[pos] == fact.col(fact_key)
    dim_w = dim.weights()[order][pos]
    new_cols = {new: dim.col(src)[order][pos] for new, src in take.items()}
    out = fact.with_columns(**new_cols)
    return Table(out.columns, out.weights() * found.astype(jnp.float32) * dim_w,
                 out.index_cache)


def _capacity(rows: int, n_partitions: int, capacity_factor: float,
              unit: int) -> int:
    """Slots per partition: the capacity-factor share, rounded up to
    ``unit``."""
    share = int(rows // n_partitions * capacity_factor)
    return int(max(unit, -(-share // unit) * unit))


def join_layout(n_fact: int, n_dim: int, n_partitions: int,
                capacity_factor: float) -> Tuple[int, int, int]:
    """(partitions, build slots, probe slots) of the kernel join's dense
    layouts. Partitions: ``n_partitions``, or the power of two that keeps
    each build tile within MAX_BUILD_TILE keys. Probe slots are a multiple
    of 1024, which the kernel folds into 8 rows of 128-lane multiples."""
    tiles = -(-int(n_dim * capacity_factor) // MAX_BUILD_TILE)
    P = max(n_partitions, 1 << max(0, tiles - 1).bit_length())
    return (P, _capacity(n_dim, P, capacity_factor, 128),
            _capacity(n_fact, P, capacity_factor, 1024))


class JoinSide(NamedTuple):
    """One side of the kernel join in its dense (P, cap) partition layout.

    ``keys``: (P, cap) int32, -1 in empty slots. ``rows``: on the build
    side the (P, cap) float32 row position of each slot (the probe's
    payload); on the probe side the (n,) int32 slot of each row, P * cap
    for a row beyond its partition's capacity. ``overflow``: the side's
    rows beyond capacity."""
    keys: jax.Array
    rows: jax.Array
    overflow: jax.Array


@functools.partial(jax.jit, static_argnames=("n_partitions", "cap", "side"))
def join_side_layout(keys: jax.Array, n_partitions: int, cap: int,
                     side: str) -> JoinSide:
    """``keys`` hash-partitioned (hashing.partition_of, so matching keys
    co-partition) into the kernel join's (P, cap) layout, rows in their
    original order within a partition. It depends on the key column
    alone, so the planner's JoinIndexPool builds it once per base column
    and feeds it to every plan that joins on that column."""
    if side not in ("build", "probe"):
        raise ValueError(f"unknown join side {side!r}")
    P = n_partitions
    keys = keys.astype(jnp.int32)
    n = keys.shape[0]
    part = partition_of(keys, P)
    order = jnp.argsort(part, stable=True)
    counts = jnp.bincount(part, length=P)
    starts = jnp.cumsum(counts) - counts
    if side == "build":
        # the build side carries its own row positions as the probe payload
        pkeys, rows, overflow = pad_partitions(
            keys[order], order.astype(jnp.float32), starts, counts, P, cap)
    else:
        pkeys, _, overflow = pad_partitions(keys[order], [], starts, counts,
                                            P, cap)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True)
        within = rank - starts[part]
        rows = jnp.where(within < cap, part * cap + within, P * cap)
    return JoinSide(pkeys, rows, overflow.astype(jnp.int32))


def pkfk_join_kernel(fact: Table, dim: Table, fact_key: str, dim_key: str,
                     take: Mapping[str, str], *, n_partitions: int = 32,
                     capacity_factor: float = 2.0,
                     mode: Optional[str] = None,
                     residual: bool = True,
                     layouts: Optional[Tuple[JoinSide, JoinSide]] = None
                     ) -> Tuple[Table, jax.Array]:
    """PK-FK join probed through the kernels/join_probe blocked compare.

    Both sides are hash-partitioned into dense (P, cap) layouts
    (``join_side_layout``); the kernel matches each probe slot against its
    partition's build tile and returns the matched build ROW POSITION,
    through which the ``take`` columns (and the build-side mask) are
    gathered. Keys must be non-negative (key -1 is the padding sentinel).

    The partitions and their capacities come from ``join_layout``.
    ``layouts`` is the (probe, build) pair of ``join_side_layout`` at
    those sizes, built ahead (the planner pools them for base-column
    keys); None builds both here.

    Rows beyond a partition's capacity — on either side — are dropped from
    the dense layouts and would degrade to join misses under key skew. With
    ``residual=True`` (default) a residual pass re-probes the kernel's
    misses through the exact sorted path whenever overflow occurred, so the
    result is EXACT under any skew and the returned overflow is 0. With
    ``residual=False`` the overflow is counted and surfaced (never silent),
    and overflowed rows stay misses — the PR-2 accounting behavior.
    Returns (joined table, overflow).
    """
    fk = fact.col(fact_key).astype(jnp.int32)
    dk = dim.col(dim_key).astype(jnp.int32)
    n_fact, n_dim = fk.shape[0], dk.shape[0]
    if max(n_fact, n_dim) >= KERNEL_JOIN_MAX_ROWS:
        # row positions ride through the kernel as float32 payloads, which
        # are only exact integers below 2^24 — beyond that positions would
        # silently collide; refuse rather than corrupt the join
        raise ValueError(f"pkfk_join_kernel limited to <2^24 rows per side, "
                         f"got fact={n_fact}, dim={n_dim}")
    P, build_cap, probe_cap = join_layout(n_fact, n_dim, n_partitions,
                                          capacity_factor)
    if layouts is None:
        layouts = (join_side_layout(fk, P, probe_cap, "probe"),
                   join_side_layout(dk, P, build_cap, "build"))
    probe, build = layouts
    if (probe.keys.shape != (P, probe_cap) or probe.rows.shape != (n_fact,)
            or build.keys.shape != (P, build_cap)):
        raise ValueError("join layouts do not match join_layout's sizes")
    vals, found = join_probe(build.keys, build.rows, probe.keys, mode=mode)
    # back to row order in one gather through each row's slot: the
    # matched position, or -1 for a miss and for a row beyond capacity
    packed = jnp.where(found, vals.astype(jnp.int32), -1).reshape(-1)
    hit = packed.at[probe.rows].get(mode="fill", fill_value=-1)
    pos, found_r = jnp.maximum(hit, 0), hit >= 0
    overflow = probe.overflow + build.overflow
    if residual:
        # Residual pass: capacity overflow drops rows from the dense
        # layouts — a dropped probe row never reaches a slot, and a
        # dropped build row makes its probes compare not-found — so every
        # missed match surfaces as found_r == False. Re-probing the
        # kernel's misses through the exact sorted index restores
        # exactness under any skew; lax.cond defers that cost until
        # overflow actually happened (the argsort itself is cached on the
        # build Table / hoisted by the planner's JoinIndexPool).
        order, sk = dim.key_index(dim_key)

        def _reprobe(args):
            pos0, found0 = args
            spos = jnp.clip(jnp.searchsorted(sk, fk), 0, sk.shape[0] - 1)
            sfound = sk[spos] == fk
            return (jnp.where(found0, pos0, order[spos].astype(jnp.int32)),
                    found0 | sfound)

        pos, found_r = jax.lax.cond(overflow > 0, _reprobe, lambda a: a,
                                    (pos, found_r))
        overflow = jnp.zeros((), jnp.int32)
    pos = jnp.clip(pos, 0, n_dim - 1)
    dim_w = dim.weights()[pos]
    new_cols = {new: dim.col(src)[pos] for new, src in take.items()}
    out = fact.with_columns(**new_cols)
    joined = Table(out.columns,
                   out.weights() * found_r.astype(jnp.float32) * dim_w,
                   out.index_cache)
    return joined, overflow


# ---------------------------------------------------------------------------
# grouped aggregation: default XLA plan vs tuned fused-kernel plan
# ---------------------------------------------------------------------------
def group_aggregate(table: Table, key: str, n_groups: int,
                    aggs: Mapping[str, Tuple[str, str]], *,
                    executor: str = "xla", mode: Optional[str] = None,
                    layout: Optional[str] = None,
                    n_partitions: int = 64, capacity_factor: float = 2.0
                    ) -> Dict[str, jax.Array]:
    """aggs: out_name -> (op, column); op in {sum, count, avg, max, min}.
    Masked rows contribute nothing. Returns dict of (n_groups,) arrays plus
    ``_count`` and ``_overflow`` (records beyond partition capacity on the
    kernel path; always 0 on the XLA path and the dense kernel path).

    Both executors sum the same measure columns (``stacked_columns``):
    "xla" with segment scatters, "kernel" with the fused kernel.
    ``layout`` overrides the kernel path's dense/partitioned choice (the
    cost-based planner sets it per Aggregate node); None keeps the
    DENSE_GROUP_LIMIT domain-size rule."""
    if executor not in ("xla", "kernel"):
        raise ValueError(f"unknown executor {executor!r}")
    keys, cols, src = stacked_columns(table, key, n_groups, aggs)
    if executor == "xla":
        layout = "xla"
    elif layout is None:
        layout = "dense" if n_groups <= DENSE_GROUP_LIMIT else "partitioned"
    sums, overflow = stacked_group_sums(
        keys, cols, n_groups, layout=layout, mode=mode,
        n_partitions=n_partitions, capacity_factor=capacity_factor)
    out = finalize_stacked(
        aggs, src, sums,
        lambda op, col: segment_order_stat(table, keys, n_groups, op, col))
    out["_overflow"] = overflow.astype(jnp.int32)
    return out


def stacked_columns(table: Table, key: str, n_groups: int,
                    aggs: Mapping[str, Tuple[str, str]]
                    ) -> Tuple[jax.Array, List[jax.Array], list]:
    """(keys, measure columns, distinct sum/avg source columns).

    Column 0 carries the selection weights (COUNT), column 1 + i the
    weighted ``src[i]``; masked rows have weight 0 so they vanish from
    every fused sum. The columns stay separate (N,) arrays: every layout
    reads them one at a time, and an (N, C) stack would cost a relayout
    pass of the whole table on the TPU."""
    keys = jnp.clip(table.col(key), 0, n_groups - 1).astype(jnp.int32)
    w = table.weights()
    src: list = []                       # distinct sum/avg source columns
    for name, (op, col) in aggs.items():
        if op in ("sum", "avg") and col not in src:
            src.append(col)
        elif (op not in ("sum", "avg", "count", "max", "min")
              and not is_holistic(op)):
            raise ValueError(f"unknown agg op {op!r}")
    cols = [w] + [table.col(c).astype(jnp.float32) * w for c in src]
    return keys, cols, src


def stacked_group_sums(keys: jax.Array, cols: Sequence[jax.Array],
                       n_groups: int, *, layout: str,
                       mode: Optional[str] = None, n_partitions: int = 64,
                       capacity_factor: float = 2.0
                       ) -> Tuple[jax.Array, jax.Array]:
    """Per-group sums of C (N,) measure columns under one layout.

    The single physical primitive every grouped-sum lowering shares: the
    local executor, the distributed per-shard partials (planner.py) and
    aggregate.count_partitioned all funnel through here. Returns
    ((n_groups, C) sums, overflow).

    Column 0 (the 0/1 selection weights) comes back as the exact count,
    rounded once to f32: each layout adds it in int32 wherever a partial
    could pass 2^24, the last integer f32 accumulation keeps."""
    if layout == "xla":
        return _segment_sums_xla(keys, cols, n_groups), \
            jnp.zeros((), jnp.int32)
    if layout == "dense":
        return _fused_dense(keys, cols, n_groups, mode=mode), \
            jnp.zeros((), jnp.int32)
    if layout == "partitioned":
        sums, overflow = _fused_partitioned(
            keys, cols, n_groups, mode=mode, n_partitions=n_partitions,
            capacity_factor=capacity_factor)
        return sums, overflow.astype(jnp.int32)
    raise ValueError(f"unknown layout {layout!r}")


def _segment_sums_xla(keys: jax.Array, cols: Sequence[jax.Array],
                      n_groups: int) -> jax.Array:
    """(n_groups, C) sums of the measure columns by segment scatters.

    A scatter-add accumulates in f32 in row order, so one big group drifts
    (a count stops at 2^24). Rows are cut into about sqrt(N) position
    chunks, as many as keep the chunk tables within SUM_CHUNK_TABLE
    entries; each chunk sums into its own table and the tables are added,
    the count column in int32. One scatter per column: a scatter of an
    (N, C) matrix makes the TPU compiler put C on the 128-lane axis."""
    N = keys.shape[0]
    k = max(1, min(math.isqrt(N), SUM_CHUNK_TABLE // n_groups))
    seg = (jnp.arange(N, dtype=jnp.int32) // -(-N // k)) * n_groups + keys

    def chunk_sums(v):
        return jax.ops.segment_sum(v, seg, num_segments=k * n_groups
                                   ).reshape(k, n_groups).sum(axis=0)

    counts = chunk_sums(cols[0].astype(jnp.int32)).astype(jnp.float32)
    return jnp.stack([counts] + [chunk_sums(c) for c in cols[1:]], axis=1)


def _segment_selection(keys: jax.Array, vals: jax.Array, n_groups: int):
    """Shared sort pass of the order-statistic primitives: per-group
    value-sorted runs plus each run's (count, start). Keys < 0 are
    EXCLUDED (the routed-buffer padding / masked-row sentinel); keys >=
    n_groups clip into the last group (the stacked_columns convention —
    the selection math needs the key order and the count clipping to
    agree, so the clip is enforced here, not left to callers). Returns
    (sorted_vals, counts i32, starts i32 shifted past the excluded run,
    sorted_keys i-dtype in the same order as sorted_vals). Counts and starts
    are integers: an f32 count stops at 2^24 rows."""
    keys = jnp.where(keys < 0, -1, jnp.minimum(keys, n_groups - 1))
    order_v = jnp.argsort(vals, stable=True)
    k1, v1 = keys[order_v], vals[order_v]
    order_k = jnp.argsort(k1, stable=True)
    sv = v1[order_k]
    sk = k1[order_k]
    counts = jnp.bincount(jnp.where(keys < 0, n_groups, keys),
                          length=n_groups + 1)
    # excluded records (key < 0) are counted in the extra last bin; they
    # sort first, so the starts shift past them
    starts = jnp.cumsum(counts[:-1]) - counts[:-1] + counts[-1]
    return sv, counts[:-1], starts, sk


def segment_median(keys: jax.Array, vals: jax.Array, n_groups: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Exact per-group median by local sort + selection.

    The holistic (order-statistic) primitive: a group's median cannot be
    merged from partials (paper Section 2), so every median lowering —
    single-device, full-replication, or routed distributed selection —
    funnels through this one sort-based selection (shared with
    ``segment_quantile``, the arbitrary-rank generalization). The median
    is the mean of the run's two middle elements (NaN for empty groups).
    Returns (medians, counts), both (n_groups,)."""
    sv, counts, starts, _sk = _segment_selection(keys, vals, n_groups)
    c, s = counts.astype(jnp.int32), starts.astype(jnp.int32)
    lo = jnp.clip(s + jnp.maximum((c - 1) // 2, 0), 0, sv.shape[0] - 1)
    hi = jnp.clip(s + jnp.maximum(c // 2, 0), 0, sv.shape[0] - 1)
    med = (sv[lo] + sv[hi]) * 0.5
    return jnp.where(c > 0, med, jnp.nan), counts.astype(jnp.float32)


def segment_quantile(keys: jax.Array, vals: jax.Array, n_groups: int,
                     rank: float) -> Tuple[jax.Array, jax.Array]:
    """Exact per-group ``rank`` quantile (linear interpolation, the
    numpy default): median generalized to an arbitrary selection index.

    Rides the same sort pass as ``segment_median`` — the selection
    position within a group's value-sorted run is rank * (count - 1); a
    fractional position interpolates between the two neighboring order
    statistics. Keys < 0 are excluded, empty groups yield NaN. ``rank``
    must lie in the OPEN interval (0, 1) — the endpoints are min/max,
    which have exact distributive lowerings. Returns (quantiles, counts),
    both (n_groups,)."""
    if not 0.0 < float(rank) < 1.0:
        raise ValueError(f"quantile rank must be in (0, 1), got {rank}")
    sv, counts, starts, _sk = _segment_selection(keys, vals, n_groups)
    c, s = counts.astype(jnp.int32), starts.astype(jnp.int32)
    pos = jnp.float32(rank) * jnp.maximum(c - 1, 0).astype(jnp.float32)
    base = jnp.floor(pos).astype(jnp.int32)
    frac = pos - base.astype(jnp.float32)
    lo = jnp.clip(s + base, 0, sv.shape[0] - 1)
    hi = jnp.clip(s + jnp.minimum(base + 1, jnp.maximum(c - 1, 0)),
                  0, sv.shape[0] - 1)
    q = sv[lo] + (sv[hi] - sv[lo]) * frac
    return jnp.where(c > 0, q, jnp.nan), counts.astype(jnp.float32)


def segment_distinct(keys: jax.Array, vals: jax.Array, n_groups: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Exact per-group distinct-value count via the shared selection sort.

    Within a group's value-sorted run, a value is counted when it differs
    from its predecessor (the run's first element always counts): the
    distinct count is the per-group sum of those boundaries. Holistic
    like median — partials from disjoint shards cannot be merged (the
    same value may appear on two shards) — but when one shard holds ALL
    of a group's records (routed or placed lowerings) the local count is
    exact. Keys < 0 are excluded; empty groups yield 0, not NaN (a count,
    not an order statistic). Returns (distinct f32, counts f32)."""
    sv, counts, _starts, sk = _segment_selection(keys, vals, n_groups)
    prev_k = jnp.concatenate([sk[:1] - 1, sk[:-1]])
    prev_v = jnp.concatenate([sv[:1], sv[:-1]])
    new = (sk >= 0) & ((sk != prev_k) | (sv != prev_v))
    distinct = jax.ops.segment_sum(
        jnp.where(new, 1.0, 0.0), jnp.clip(sk, 0, n_groups - 1),
        num_segments=n_groups)
    return distinct, counts.astype(jnp.float32)


def segment_order_stat(table: Table, keys: jax.Array, n_groups: int,
                       op: str, col: str) -> jax.Array:
    """Masked per-group max/min/median/quantile/distinct via exact XLA
    lowerings (none of these are distributive sums, so they never ride
    the fused sweep)."""
    v = table.col(col).astype(jnp.float32)
    w = table.weights()
    if op == "median":
        return segment_median(jnp.where(w > 0, keys, -1), v, n_groups)[0]
    if op == "distinct":
        return segment_distinct(jnp.where(w > 0, keys, -1), v, n_groups)[0]
    rank = parse_quantile(op)
    if rank is not None:
        return segment_quantile(jnp.where(w > 0, keys, -1), v, n_groups,
                                rank)[0]
    if op == "max":
        big = jnp.where(w > 0, v, -jnp.inf)
        return jax.ops.segment_max(big, keys, num_segments=n_groups)
    small = jnp.where(w > 0, v, jnp.inf)
    return jax.ops.segment_min(small, keys, num_segments=n_groups)


def finalize_stacked(aggs: Mapping[str, Tuple[str, str]], src: list,
                     sums: jax.Array, order_stat) -> Dict[str, jax.Array]:
    """Named outputs from a merged (n_groups, C) stacked-sums table.

    Shared by the local kernel path and the distributed per-policy path so
    the two can never drift. ``order_stat(op, col)`` supplies max/min (the
    distributed executor composes a cross-shard reduction on top of the
    segment ops)."""
    cnt = sums[:, 0]
    out: Dict[str, jax.Array] = {}
    for name, (op, col) in aggs.items():
        if op == "count":
            out[name] = cnt
        elif op == "sum":
            out[name] = sums[:, 1 + src.index(col)]
        elif op == "avg":
            out[name] = sums[:, 1 + src.index(col)] / jnp.maximum(cnt, 1.0)
        else:
            out[name] = order_stat(op, col)
    out["_count"] = cnt
    return out


def _tile_fold(col: jax.Array, pad: int) -> jax.Array:
    """The kernel's (R, 8, 128) operand: ``col`` padded with ``pad`` zeros
    to a whole number of 1024-record tiles. A 1-D column's TPU layout is
    1024-element tiles, each one (8, 128) tile, so the reshape is a
    bitcast: the pad is the only pass the fold costs."""
    return jnp.pad(col, (0, pad)).reshape(-1, 8, 128)


def dense_layout(n_rows: int) -> Tuple[int, int]:
    """(chunks, tiles per chunk) of the dense aggregate: at least 8 chunks
    of equal grid steps (``padded_tiles``, which adds fewer than
    STEP_TILES tiles), each under F32_EXACT_COUNT rows; one chunk below 8
    tiles of rows."""
    n_chunks = max(8, -(-n_rows // (F32_EXACT_COUNT - STEP_TILES * TILE))) \
        if n_rows >= 8 * TILE else 1
    return n_chunks, padded_tiles(-(-n_rows // (n_chunks * TILE)))


def _fused_dense(keys: jax.Array, cols: Sequence[jax.Array], n_groups: int,
                 *, mode: Optional[str]) -> jax.Array:
    """Small key domain: positional chunking, full-width tables, no sort.

    Rows are split into chunks by position (``dense_layout``); each
    chunk's (C, n_bins) table covers every group, so the result is the
    exact sum of chunk tables — no partitioning pass, no overflow
    possible. Padding rows carry zero values, so their bin placement is
    irrelevant. A chunk holds fewer than F32_EXACT_COUNT rows, so its
    counts are exact and add up in int32."""
    N = keys.shape[0]
    bins = max(128, -(-n_groups // 128) * 128)
    n_chunks, chunk_tiles = dense_layout(N)
    pad = n_chunks * chunk_tiles * TILE - N
    table = hash_aggregate(_tile_fold(keys, pad),
                           [_tile_fold(c, pad) for c in cols],
                           n_parts=n_chunks, n_bins=bins, mode=mode)
    counts = table[:, 0].astype(jnp.int32).sum(axis=0).astype(jnp.float32)
    return table.sum(axis=0).at[0].set(counts).T[:n_groups]


def partition_layout(n_rows: int, n_groups: int, n_partitions: int,
                     capacity_factor: float, block: int
                     ) -> Tuple[int, int, int, int]:
    """(partitions, groups per partition, table bins, slots per partition)
    of the range-partitioned aggregate. Partitions: ``n_partitions``, or as
    many as keep each partition table within MAX_PARTITION_BINS slots and
    each partition's slots below F32_EXACT_COUNT (a group's count stays
    exact in the kernel's f32 table)."""
    P = max(n_partitions, -(-n_groups // MAX_PARTITION_BINS),
            -(-int(n_rows * capacity_factor) // (F32_EXACT_COUNT // 2)))
    range_size = -(-n_groups // P)
    bins = max(128, -(-range_size // 128) * 128)
    return P, range_size, bins, _capacity(n_rows, P, capacity_factor, block)


def _fused_partitioned(keys: jax.Array, cols: Sequence[jax.Array],
                       n_groups: int, *, mode: Optional[str],
                       n_partitions: int, capacity_factor: float
                       ) -> Tuple[jax.Array, jax.Array]:
    """Large key domain: range partition, then fused per-partition tables.

    Range partitioning on the (clipped, dense) group ids makes the
    partition-local slot (key % range_size) collision-free, so the kernel
    result is EXACT whenever no partition overflows its capacity; overflow
    is counted and returned, as in aggregate.count_partitioned. The
    layout's sizes come from ``partition_layout``; a partition's slots are
    whole 1024-record tiles, so each row of the (P, slots) layout is a run
    of the kernel's (R, 8, 128) fold. The kernel sweeps the largest number
    of tiles per grid step that divides a partition's (``step_tiles``), so
    the layout and its overflow do not depend on the step."""
    C = len(cols)
    n_partitions, range_size, bins, pad_t = partition_layout(
        keys.shape[0], n_groups, n_partitions, capacity_factor, TILE)
    part = jnp.clip(keys // range_size, 0, n_partitions - 1)
    order = jnp.argsort(part, stable=True)
    counts_p = jnp.bincount(part, length=n_partitions)
    starts = jnp.cumsum(counts_p) - counts_p
    # measure columns travel one by one through the same gather as the keys
    pk, pv, overflow = pad_partitions(
        keys[order], [c[order] for c in cols], starts, counts_p,
        n_partitions, pad_t)
    local = jnp.where(pk < 0, 0, pk % range_size)   # padded vals are zero
    table = hash_aggregate(local.reshape(-1, 8, 128),
                           [v.reshape(-1, 8, 128) for v in pv],
                           n_parts=n_partitions, n_bins=bins, mode=mode)
    flat = table[:, :, :range_size].transpose(0, 2, 1)
    return flat.reshape(n_partitions * range_size, C)[:n_groups], overflow
