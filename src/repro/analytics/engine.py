"""Distributed analytics operators under the paper's placement policies.

This is the reproduction's centerpiece: the SAME logical query (W1/W2/W3)
executes under each memory placement policy (paper Section 3.3), and the
policies change only the *placement/communication plan*, never the query
code — the paper's application-agnostic thesis, realized as shard_map plans:

  FIRST_TOUCH  every shard aggregates into its own FULL-width table
               (the node that first touches a group owns a whole copy);
               merge = all-reduce over the table. Memory O(G)/shard,
               collective O(G * n) wire bytes. The OS-default analogue.
  LOCAL_ALLOC  same local tables, but the merge is a reduce-scatter: each
               shard ends up owning G/n of the result where its output
               "allocation" lives. Half the wire bytes of FIRST_TOUCH.
  INTERLEAVE   the table is bucket-interleaved across shards up front;
               records are routed to their owning shard (all-to-all of the
               DATA, O(N) wire bytes, independent of G) and aggregated once.
               Memory O(G/n)/shard. The paper's winner for shared state.
  PREFERRED    all records converge on one submesh slice (all-gather);
               models the paper's Preferred-x + its congestion.

For HOLISTIC aggregation (W1, median) partials cannot be merged, so
FIRST_TOUCH/LOCAL_ALLOC degrade to full record replication (all-gather of
data) — reproducing the paper's observation that holistic functions are the
memory system's worst case — while INTERLEAVE routes each group's records
to one owner and sorts locally.

Since PR 4 none of the workloads carries its own shard_map plan: W1/W2/W3
are logical plans lowered through the planner's distributed backend, and
this module provides the per-policy physical primitives those lowerings
(and the TPC-H plans) share — partial-table merging, record routing,
partitioned join routing, and distributed selection.

The AutoNUMA analogue (`auto_rebalance`) appends a policy-ideal resharding
of the result state after the query — pure extra collective traffic when
the plan was already local (paper Fig 5a), a rescue when the plan was
PREFERRED.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analytics.columnar import (concat_slices, segment_distinct,
                                      segment_median, segment_quantile,
                                      stacked_group_sums)
from repro.analytics.hashing import partition_of
from repro.analytics.physical import ceil128
from repro.kernels.radix_partition.ops import block_histograms
from repro.core.config import PlacementPolicy


# ---------------------------------------------------------------------------
# record routing (the all-to-all building block of INTERLEAVE)
# ---------------------------------------------------------------------------
def route_records(keys: jax.Array, vals: jax.Array, n_shards: int,
                  owner: jax.Array, capacity: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Bucket local records by owning shard into a dense (n, capacity) send
    layout. Returns (keys_out, vals_out, overflow). Padding key = -1.

    ``vals`` may carry trailing measure dims — (N,) or (N, C) — so a stacked
    multi-aggregate matrix rides through the same routing as its keys (the
    planner's INTERLEAVE Aggregate backend)."""
    if keys.shape[0] == 0:  # degenerate empty shard: all-padding send layout
        k_out = jnp.full((n_shards, capacity), -1, keys.dtype)
        v_out = jnp.zeros((n_shards, capacity) + vals.shape[1:], vals.dtype)
        return k_out, v_out, jnp.zeros((), jnp.int32)
    order = jnp.argsort(owner, stable=True)
    sk, sv, so = keys[order], vals[order], owner[order]
    counts = jnp.bincount(owner, length=n_shards)
    starts = jnp.cumsum(counts) - counts
    idx = starts[:, None] + jnp.arange(capacity)[None, :]
    valid = jnp.arange(capacity)[None, :] < jnp.minimum(counts, capacity)[:, None]
    idx = jnp.clip(idx, 0, max(keys.shape[0] - 1, 0))
    k_out = jnp.where(valid, sk[idx], -1)
    vmask = valid.reshape(valid.shape + (1,) * (sv.ndim - 1))
    v_out = jnp.where(vmask, sv[idx], 0)
    overflow = jnp.maximum(counts - capacity, 0).sum()
    return k_out, v_out, overflow


def route_owner(keys: jax.Array, alive: jax.Array, n: int,
                method: str = "modulo") -> jax.Array:
    """Owner shard for routing one row set: alive rows co-locate by key;
    dead rows — scan padding, masked rows, the padding of an upstream
    routed buffer — spread round-robin instead. Dead rows contribute
    nothing wherever they land, but co-located (e.g. all key -1 -> shard
    n-1, or all clipped to key 0 -> shard 0) they would mass on ONE
    destination and eat its capacity, surfacing overflow for records that
    do not exist. One copy of this rule serves every routed lowering.

    ``method`` picks the owner function. "modulo" (key % n) is ideal for
    DENSE id domains — group ids, permuted PKs — and is what the
    interleaved republish slot math (owner g = g % n, slot g // n)
    requires. "hash" takes the TOP radix bits of the multiplicative hash
    (hashing.partition_of — the same choice the join kernels make; the
    LOW hash bits are degenerate for power-of-two strides, where
    key * KNUTH stays a multiple of the stride): the right choice for
    CLUSTERED key spaces (sequential/moving-window keys, strided ids),
    where key % n would mass whole key runs — or every key of one stride
    class — onto a few shards."""
    spread = jnp.arange(keys.shape[0], dtype=jnp.int32) % n
    if method == "hash":
        owned = partition_of(keys, n)
    elif method == "modulo":
        owned = (keys % n).astype(jnp.int32)
    else:
        raise ValueError(f"unknown routing method {method!r}")
    return jnp.where(alive, owned, spread)


def routing_capacity(n_rows: int, n_shards: int,
                     capacity_factor: float) -> int:
    """Per-destination slot budget for routing ``n_rows`` local records to
    ``n_shards`` owners: the balanced share times ``capacity_factor``,
    rounded up to a 128-row tile (one copy of the formula every routed
    lowering shares; the tile rounding itself is physical.ceil128, shared
    with the Compact occupancy budgets)."""
    return ceil128(int(capacity_factor * n_rows / n_shards))


def route_table_rows(cols, weights: jax.Array, owner: jax.Array,
                     n_shards: int, capacity: int, axis: str):
    """All-to-all route a struct-of-arrays row set to its owner shards.

    Generalizes ``route_records`` to a whole table: ONE argsort-by-owner
    layout pass shared by every column, then one all-to-all per column.
    Integer columns pad with -1 (the key sentinel: padding never matches a
    real join key and is excluded from order statistics), floats with 0;
    ``weights`` rides along so routed padding rows carry zero selection
    weight. Returns (cols, weights, overflow) — the received buffers hold
    n_shards * capacity rows per shard; rows beyond a destination's
    capacity are counted in overflow (local, caller psums)."""
    n_rows = weights.shape[0]
    if n_rows == 0:
        return _empty_routed(cols, weights, n_shards, capacity)
    order = jnp.argsort(owner, stable=True)
    counts = jnp.bincount(owner, length=n_shards)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(capacity)
    idx = jnp.clip(starts[:, None] + slot[None, :], 0, max(n_rows - 1, 0))
    valid = slot[None, :] < jnp.minimum(counts, capacity)[:, None]

    def exchange(a, fill):
        sent = jnp.where(valid, a[order][idx], fill)
        return jax.lax.all_to_all(sent, axis, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(-1)

    out = {c: exchange(a, -1 if jnp.issubdtype(a.dtype, jnp.integer) else 0)
           for c, a in cols.items()}
    w = exchange(weights, 0)
    overflow = jnp.maximum(counts - capacity, 0).sum()
    return out, w, overflow


def _empty_routed(cols, weights: jax.Array, n_shards: int, capacity: int):
    """Receive-side buffers for the degenerate empty shard (n_rows == 0).

    Under shard_map the row count is a static per-shard shape, so EVERY
    shard is empty when one is; each peer would only ever send padding, so
    the all-to-all is elided and the fully-padded receive buffers are built
    locally. Keeping this out of the main path also keeps the argsort /
    radix layout math free of ``n_rows - 1 == -1`` clip bounds."""
    size = n_shards * capacity
    out = {c: jnp.full((size,),
                       -1 if jnp.issubdtype(a.dtype, jnp.integer) else 0,
                       a.dtype)
           for c, a in cols.items()}
    w = jnp.zeros((size,), weights.dtype)
    return out, w, jnp.zeros((), jnp.int32)


def radix_route_table_rows(cols, weights: jax.Array, owner: jax.Array,
                           n_shards: int, capacity: int, axis: str, *,
                           block: int = 256, mode: Optional[str] = None):
    """All-to-all route a row set via the radix-partition histogram kernel.

    Same contract and BIT-IDENTICAL send layout as ``route_table_rows``,
    built without the argsort: per-block owner histograms come from
    ``block_histograms`` (kernel-mode resolved — the seed's Pallas MXU
    one-hot reduce on TPU, its oracle elsewhere), an exclusive prefix over
    blocks gives each block's base slot per destination, and a within-block
    running count gives each row's stable rank among its owner's rows. Rows
    then scatter straight into the (n_shards, capacity) send buffer at
    ``owner * capacity + rank`` — rank order equals position order, so the
    layout matches the stable argsort exactly and downstream reductions are
    bit-identical across the two paths. Rows ranked past ``capacity`` drop
    into the surfaced overflow count, exactly as the argsort path's
    ``valid`` mask does.

    ``owner`` is padded with zeros to a ``block`` multiple for the kernel
    (padding sits at the END, so real rows' ranks are unaffected) and the
    destination-0 count is corrected before the prefix sum. ``n_bins`` is
    the owner-domain [0, n_shards) rounded up to a power of two, as the
    digit mask requires."""
    n_rows = weights.shape[0]
    if n_rows == 0:
        return _empty_routed(cols, weights, n_shards, capacity)
    n_bins = 1 << max(1, (n_shards - 1).bit_length())
    pad = -n_rows % block
    owner = owner.astype(jnp.int32)
    owner_p = jnp.pad(owner, (0, pad)) if pad else owner
    hist = block_histograms(owner_p, n_bins=n_bins, shift=0, block=block,
                            mode=mode)                  # (n_blocks, n_bins)
    counts_all = hist.sum(axis=0)
    if pad:
        counts_all = counts_all.at[0].add(-pad)
    counts = counts_all[:n_shards]
    # Stable rank of each row among its destination's rows, without a sort:
    # exclusive block prefix (base slot of each block per bin) + exclusive
    # within-block running count of the row's own bin.
    block_base = jnp.cumsum(hist, axis=0) - hist        # (n_blocks, n_bins)
    ob = owner_p.reshape(-1, block)                     # (n_blocks, block)
    oh = (ob[:, :, None] ==
          jnp.arange(n_bins, dtype=jnp.int32)[None, None, :]).astype(jnp.int32)
    within = jnp.cumsum(oh, axis=1) - 1                 # (blocks, block, bins)
    rank_in_block = jnp.take_along_axis(within, ob[:, :, None], axis=2)[..., 0]
    base = jnp.take_along_axis(block_base, ob, axis=1)
    rank = (base + rank_in_block).reshape(-1)[:n_rows]
    pos = jnp.where(rank < capacity, owner * capacity + rank,
                    n_shards * capacity)                # OOB -> dropped

    def exchange(a, fill):
        sent = jnp.full((n_shards * capacity,), fill, a.dtype)
        sent = sent.at[pos].set(a, mode="drop").reshape(n_shards, capacity)
        return jax.lax.all_to_all(sent, axis, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(-1)

    out = {c: exchange(a, -1 if jnp.issubdtype(a.dtype, jnp.integer) else 0)
           for c, a in cols.items()}
    w = exchange(weights, 0)
    overflow = jnp.maximum(counts - capacity, 0).sum()
    return out, w, overflow


def compact_routed_rows(cols, weights: jax.Array, capacity: int):
    """Occupancy-aware re-compaction of a routed buffer (the physical
    planner's ``Compact`` operator).

    A routed buffer holds n_shards * capacity slots but only ~its share of
    the ALIVE rows; feeding it to another routing pass sizes the next
    capacity from the padded length, so chained partitioned joins grow
    their buffers by a capacity_factor per hop. Compacting between hops
    stable-partitions the alive rows (weight > 0) to the front — original
    relative order preserved, so downstream float reductions stay
    deterministic — and cuts the buffer back to ``capacity`` rows. Alive
    rows beyond capacity are COUNTED into the returned overflow (the
    caller folds it into the plan's ``_overflow``), never dropped
    silently. Returns (cols, weights, overflow int32)."""
    alive = weights > 0
    order = jnp.argsort(jnp.where(alive, 0, 1).astype(jnp.int32),
                        stable=True)
    idx = order[:capacity]
    kept = {c: jnp.asarray(a)[idx] for c, a in cols.items()}
    w = weights[idx]
    n_alive = alive.sum()
    overflow = jnp.maximum(n_alive - capacity, 0).astype(jnp.int32)
    return kept, w, overflow


def pushdown_group_sums(partial: jax.Array, n_groups: int, axis: str,
                        n: int, *, capacity_factor: float = 2.0,
                        capacity: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Aggregate push-down merge: exchange per-shard PARTIAL sums instead
    of records.

    ``partial`` is the local (n_groups, C) stacked-sums table. Each group
    row g routes to its modulo owner (g % n) — deterministic and balanced
    by construction, since every shard ships the same group ids — the
    owner adds its received contributions, and the merged rows republish
    in natural group order (the same slot math as interleave_group_sums).
    Per-shard wire volume is O(n_groups) rows where routing the records
    costs O(n_rows): the win the physical planner's push-down rewrite
    prices. ``capacity`` overrides the slot budget (the planner passes
    its Exchange node's capacity, as in interleave_group_sums). Returns
    ((n_groups, C) replicated, overflow) — overflow is 0 by construction
    for capacity_factor >= 1 (each destination receives exactly its owned
    groups from each source)."""
    G = n_groups
    g = jnp.arange(G, dtype=jnp.int32)
    owner = g % n
    cap = (capacity if capacity is not None
           else routing_capacity(G, n, capacity_factor))
    k_out, v_out, route_ovf = route_records(g, partial, n, owner, cap)
    k_in = jax.lax.all_to_all(k_out, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    v_in = jax.lax.all_to_all(v_out, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    n_slots = (G + (-G % n)) // n
    slot = jnp.where(k_in >= 0, k_in // n, n_slots)      # OOB drop slot
    local = jax.ops.segment_sum(v_in.reshape((-1,) + v_in.shape[2:]),
                                slot.reshape(-1), num_segments=n_slots + 1)
    gathered = jax.lax.all_gather(local[:n_slots], axis, tiled=True)
    full = gathered[(g % n) * n_slots + g // n]
    overflow = jax.lax.psum(route_ovf, axis)
    return full, overflow


# ---------------------------------------------------------------------------
# morsel-sliced distributive aggregation (the serving scheduler's unit)
# ---------------------------------------------------------------------------
# A morsel is a contiguous row range of a scan — the intra-node work-split
# analog of the paper's kernel load balancing: the serving scheduler
# (analytics/service/scheduler.py) dispatches morsels to socket-pinned
# worker pools and merges the per-morsel partial tables in MORSEL ORDER, so
# the merged result is deterministic for a fixed morsel size regardless of
# which pool executed which morsel (or in what order work stealing
# completed them).

def morsel_slices(n_rows: int, morsel_rows: Optional[int]
                  ) -> List[Tuple[int, int]]:
    """[lo, hi) row ranges covering n_rows; the last morsel takes the
    remainder when n_rows is not divisible by morsel_rows. None = one
    morsel (whole scan)."""
    if morsel_rows is not None and morsel_rows < 1:
        raise ValueError("morsel_rows must be >= 1")
    if morsel_rows is None or morsel_rows >= n_rows:
        return [(0, n_rows)]
    return [(lo, min(lo + morsel_rows, n_rows))
            for lo in range(0, n_rows, morsel_rows)]


def morsel_slice_columns(cols, lo, length: int):
    """Slice every column of a scan to one morsel's rows [lo, lo+length).

    ``length`` is static (jit specializes per morsel width — with a fixed
    morsel size only the tail morsel adds a second compilation) while
    ``lo`` stays a traced scalar, so one executable serves every aligned
    morsel of a scan."""
    return {c: jax.lax.dynamic_slice_in_dim(jnp.asarray(a), lo, length)
            for c, a in cols.items()}


def morsel_group_sums(keys: jax.Array, vals: jax.Array, n_groups: int, *,
                      layout: str = "xla", mode: Optional[str] = None,
                      n_partitions: int = 64, capacity_factor: float = 2.0
                      ) -> Tuple[jax.Array, jax.Array]:
    """Partial (n_groups, C) sums over ONE morsel's (already-sliced) rows.

    A named delegation to the shared stacked-group-sums recipe: the morsel
    path exercises the SAME physical layouts the planner chooses between,
    and the (sums, int32 overflow) pair is exactly what
    merge_morsel_partials folds."""
    return stacked_group_sums(
        keys, vals, n_groups, layout=layout, mode=mode,
        n_partitions=n_partitions, capacity_factor=capacity_factor)


def merge_morsel_partials(partials: Sequence[Tuple[Any, jax.Array]]
                          ) -> Tuple[Any, jax.Array]:
    """Merge per-morsel partials in morsel order.

    Two partial shapes flow through here:

    * distributive aggregates — (sums, overflow) pairs, left-folded by
      addition. The fold order is part of the result's float semantics:
      merging in sequence-number order (not completion order) keeps
      served answers deterministic under work stealing.
    * split-probe pipelines — ((columns_dict, mask), overflow): each
      morsel returns its slice of the pre-aggregate intermediate table,
      and concatenating the slices in sequence order reconstructs the
      serial table bit-for-bit (every on-path operator is per-row, so
      row lo..hi of the serial run IS morsel (lo, hi)'s output).
    """
    if not partials:
        raise ValueError("no morsel partials to merge")
    head = partials[0][0]
    if isinstance(head, tuple) and len(head) == 2 and isinstance(
            head[0], dict):
        merged = concat_slices([p[0] for p in partials])
        overflow = partials[0][1]
        for _, o in partials[1:]:
            overflow = overflow + o
        return merged, overflow
    sums, overflow = partials[0]
    for s, o in partials[1:]:
        sums = sums + s
        overflow = overflow + o
    return sums, overflow


# ---------------------------------------------------------------------------
# per-policy physical backends for the logical-plan Aggregate (planner.py)
# ---------------------------------------------------------------------------
# These run INSIDE an open shard_map over ``axis``: each shard holds a row
# slice of the table and the policy decides only the placement/communication
# plan of the shared group table — never the query semantics. FIRST_TOUCH /
# LOCAL_ALLOC merge per-shard partial tables (all-reduce vs reduce-scatter +
# all-gather); INTERLEAVE routes the records to bucket-interleaved owners
# before aggregating; PREFERRED converges all records on every shard (models
# the paper's Preferred-x congestion). All four return the same full-width
# replicated table, so one downstream plan serves every policy.

def merge_partial_table(table: jax.Array, policy: PlacementPolicy,
                        axis: str, n: int) -> jax.Array:
    """Merge per-shard partial (G, C) group tables into the full table.

    FIRST_TOUCH owns whole replicas -> all-reduce; LOCAL_ALLOC owns the
    output slice where it was allocated -> reduce-scatter, then an
    all-gather republishes the slices (G is padded to a multiple of n for
    the tiled collectives)."""
    if policy == PlacementPolicy.FIRST_TOUCH:
        return jax.lax.psum(table, axis)
    if policy == PlacementPolicy.LOCAL_ALLOC:
        G = table.shape[0]
        pad = -G % n
        padded = jnp.pad(table, ((0, pad),) + ((0, 0),) * (table.ndim - 1))
        shard = jax.lax.psum_scatter(padded, axis, scatter_dimension=0,
                                     tiled=True)
        return jax.lax.all_gather(shard, axis, tiled=True)[:G]
    raise ValueError(f"merge_partial_table does not implement {policy}")


def interleave_group_sums(keys: jax.Array, vals: jax.Array, n_groups: int,
                          axis: str, n: int, aggregate_fn, *,
                          capacity_factor: float = 2.0,
                          capacity: Optional[int] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """INTERLEAVE backend: route records to bucket-interleaved owners
    (all-to-all of the DATA, O(N) wire bytes), aggregate once on the owner,
    then republish. ``aggregate_fn(slot_ids, vals, n_slots) -> (sums, ovf)``
    is the shard-local aggregation (the planner passes the cost-chosen
    lowering, so the fused kernel path composes with this placement plan).
    NOTE: the routed (n, cap) buffer parks every padding slot on one extra
    drop slot with zero values, so ``aggregate_fn`` must use a layout whose
    result does not depend on row OCCUPANCY — xla segment ops or the dense
    chunked kernel, not the range-partitioned layout, whose per-partition
    capacity the massed padding rows would consume (dropping real records
    and reporting phantom overflow). ``capacity`` overrides the
    per-destination slot budget — the physical planner passes its
    Exchange node's capacity so the executed routing can never drift from
    the rendered plan. Returns ((n_groups, C) replicated, overflow)."""
    G_pad = n_groups + (-n_groups % n)
    if vals.ndim > 1:
        # column 0 of a stacked matrix carries the selection weights
        owner = route_owner(keys, vals[:, 0] > 0, n)
    else:
        owner = keys % n
    cap = (capacity if capacity is not None
           else routing_capacity(keys.shape[0], n, capacity_factor))
    k_out, v_out, route_ovf = route_records(keys, vals, n, owner, cap)
    k_in = jax.lax.all_to_all(k_out, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    v_in = jax.lax.all_to_all(v_out, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    # owned group g lives in local slot g // n (keys % n == my shard index)
    n_slots = G_pad // n
    slot = jnp.where(k_in >= 0, k_in // n, n_slots)      # OOB drop slot
    local, agg_ovf = aggregate_fn(slot.reshape(-1),
                                  v_in.reshape((-1,) + v_in.shape[2:]),
                                  n_slots + 1)
    gathered = jax.lax.all_gather(local[:n_slots], axis, tiled=True)
    g = jnp.arange(n_groups)
    full = gathered[(g % n) * n_slots + g // n]
    overflow = jax.lax.psum(route_ovf + agg_ovf, axis)
    return full, overflow


def gather_rows(arrs, axis: str):
    """PREFERRED backend building block: converge every shard's rows
    (all-gather of the data, the paper's congestion worst case)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.all_gather(a, axis, tiled=True), arrs)


# ---------------------------------------------------------------------------
# W2: distributive COUNT under each policy
# ---------------------------------------------------------------------------
def dist_count(mesh: Mesh, policy: PlacementPolicy, cardinality: int, *,
               axis: str = "data", capacity_factor: float = 2.0,
               auto_rebalance: bool = False) -> Callable:
    """Build the policy's distributed COUNT plan.

    Returns fn(keys (N,) sharded over ``axis``) -> (G,) counts, replicated
    in natural group order under every policy.

    W2 no longer carries its own shard_map plan: the count is expressed as
    a logical ``Aggregate`` and lowered through the planner's distributed
    backend — the same per-policy collectives (merge_partial_table /
    interleave_group_sums / gather_rows) that serve the TPC-H plans, so
    there is exactly one copy of each placement strategy in the repo. This
    thin wrapper exists for the fig5 benchmark and callers that want the
    bare-operator signature. The AutoNUMA analogue is composed as a
    post-pass: a policy-ideal resharding of the already-merged table (pure
    extra collective traffic when the plan was already local, paper Fig
    5a)."""
    # planner imports engine's merge primitives; import lazily to avoid the
    # module cycle
    from repro.analytics import plan as L
    from repro.analytics import planner

    n = mesh.shape[axis]
    lplan = L.LogicalPlan(
        L.scan("keys").aggregate("k", cardinality, count=("count", "k")),
        ("count",))
    ctx = planner.ExecutionContext(executor="xla", mesh=mesh, policy=policy,
                                   axis=axis, capacity_factor=capacity_factor)
    rebalance = jax.shard_map(
        lambda t: _rebalance_to_interleave(t, n, axis), mesh=mesh,
        in_specs=P(), out_specs=P(), check_vma=False)

    def fn(keys):
        counts = planner.execute_plan(lplan, {"keys": {"k": keys}},
                                      ctx)["count"]
        if auto_rebalance:  # AutoNUMA: reshard toward interleave post hoc
            counts = rebalance(counts)
        return counts

    return fn


def _rebalance_to_interleave(table: jax.Array, n: int, axis: str) -> jax.Array:
    """AutoNUMA analogue: migrate a replicated table toward interleaved
    ownership — pure extra collective traffic on an already-merged result.

    The input is the REPLICATED merged table (one identical copy per
    shard), so the reduce-scatter sums n copies; dividing AFTER the
    scatter keeps the migration value-preserving ((n*x)/n is exact for
    exactly-representable x, e.g. integer counts, where float32(x/n)
    summed n times is not — n=6 turns a count of 7 into 6.9999995). The
    leading dim is padded to a multiple of n for the tiled collectives
    (as in merge_partial_table) and sliced back after the gather."""
    G = table.shape[0]
    pad = -G % n
    padded = jnp.pad(table, ((0, pad),) + ((0, 0),) * (table.ndim - 1))
    shard = jax.lax.psum_scatter(padded, axis, scatter_dimension=0,
                                 tiled=True) / n
    return jax.lax.all_gather(shard, axis, tiled=True)[:G]


# ---------------------------------------------------------------------------
# holistic MEDIAN backends (per-policy lowerings of the Aggregate op)
# ---------------------------------------------------------------------------
# These run INSIDE an open shard_map, like the distributive backends above.
# A median cannot be merged from partials (paper Section 2), so the
# replication-based policies degrade to full record gathering — the paper's
# "holistic functions are the memory system's worst case" — while
# INTERLEAVE routes each group's records to one owner and selects locally
# (distributed selection). Both return natural-group-order replicated
# results so one downstream plan serves every policy.

def _select(k, v, n_groups, rank):
    """One sort-based selection: the median when ``rank`` is None, the
    exact distinct count when ``rank`` is the string "distinct", the
    interpolated ``rank`` quantile otherwise (all exclude keys < 0).
    ``rank`` is what plan.holistic_selector returns for the agg op."""
    if rank is None:
        return segment_median(k, v, n_groups)
    if rank == "distinct":
        return segment_distinct(k, v, n_groups)
    return segment_quantile(k, v, n_groups, rank)


def replicated_group_median(keys: jax.Array, cols, w: jax.Array,
                            n_groups: int, axis: str, ranks=None):
    """FIRST_TOUCH / LOCAL_ALLOC / PREFERRED holistic lowering: gather
    every shard's records (all-gather of the DATA) and run one local
    sort-based selection per value column. ``cols``: {name: (N,) values} —
    the keys/weights are gathered ONCE for all of them. ``ranks`` maps a
    column name to a quantile rank in (0, 1); absent/None means the
    median (the selection machinery is the same — a quantile is just a
    different selection index). Returns ({name: (n_groups,) order
    statistics}, counts), replicated."""
    ranks = ranks or {}
    ak = jax.lax.all_gather(keys, axis, tiled=True)
    aw = jax.lax.all_gather(w, axis, tiled=True)
    k_eff = jnp.where(aw > 0, ak, -1)
    meds, counts = {}, None
    for name, v in cols.items():
        av = jax.lax.all_gather(v, axis, tiled=True)
        meds[name], counts = _select(k_eff, av, n_groups, ranks.get(name))
    return meds, counts


def interleave_group_median(keys: jax.Array, cols, w: jax.Array,
                            n_groups: int, axis: str, n: int, *,
                            capacity_factor: float = 2.0, ranks=None):
    """INTERLEAVE holistic lowering: route each group's records to its
    bucket-interleaved owner (all-to-all, O(N) wire bytes), select the
    order statistic locally on the owner, then republish in natural group
    order. ``cols``: {name: (N,) values}; every value column rides ONE
    routing pass (one argsort-by-owner layout, keys/weights exchanged
    once). ``ranks`` as in replicated_group_median (None entry = median).
    Returns ({name: (n_groups,) order stats}, counts, overflow),
    replicated."""
    ranks = ranks or {}
    k_eff = jnp.where(w > 0, keys, -1).astype(jnp.int32)
    owner = route_owner(k_eff, k_eff >= 0, n)
    cap = routing_capacity(keys.shape[0], n, capacity_factor)
    # positional names: aggregate output names could collide with "k"
    send = {"k": k_eff}
    send.update({f"v{i}": v for i, v in enumerate(cols.values())})
    routed, w_in, ovf = route_table_rows(send, w, owner, n, cap, axis)
    n_slots = -(-n_groups // n)
    local_ids = jnp.where((routed["k"] >= 0) & (w_in > 0),
                          routed["k"] // n, -1)
    g = jnp.arange(n_groups)                       # owner of g is g % n
    pos = (g % n) * n_slots + g // n
    meds, counts = {}, None
    for i, name in enumerate(cols):
        med, cnt = _select(local_ids, routed[f"v{i}"], n_slots,
                           ranks.get(name))
        meds[name] = jax.lax.all_gather(med, axis, tiled=True)[pos]
        counts = jax.lax.all_gather(cnt, axis, tiled=True)[pos]
    return meds, counts, jax.lax.psum(ovf, axis)


def placed_group_median(keys: jax.Array, cols, w: jax.Array,
                        n_groups: int, axis: str, ranks=None):
    """Route-once holistic lowering: the child is ALREADY placed by the
    group key (e.g. a partitioned join routed every group's alive records
    to one owner shard), so each order statistic selects locally on
    whichever shard holds the group — no fresh Exchange. Exact because
    placement means exactly ONE shard holds ALL of a group's alive rows:
    its local selection over the full value set equals the global one,
    and every other shard sees an empty group (zero count) and is masked
    out of the merge. The merge is a psum of owner-only values — cheaper
    than re-routing O(N) records by a wide margin (O(G) wire rows).
    ``cols``/``ranks`` as in replicated_group_median. Returns
    ({name: (n_groups,) order stats}, counts), replicated."""
    ranks = ranks or {}
    k_eff = jnp.where(w > 0, keys, -1).astype(jnp.int32)
    meds, counts = {}, None
    for name, v in cols.items():
        sel = ranks.get(name)
        stat, cnt = _select(k_eff, v, n_groups, sel)
        cnt_all = jax.lax.psum(cnt, axis)
        if sel == "distinct":
            # a distinct count is 0 (not NaN) on non-owner shards: the
            # psum alone reconstructs the owner's exact count
            meds[name] = jax.lax.psum(stat, axis)
        else:
            stat_all = jax.lax.psum(jnp.where(cnt > 0, stat, 0.0), axis)
            meds[name] = jnp.where(cnt_all > 0, stat_all, jnp.nan)
        counts = cnt_all
    return meds, counts


# ---------------------------------------------------------------------------
# W1: holistic MEDIAN under each policy
# ---------------------------------------------------------------------------
def dist_median(mesh: Mesh, policy: PlacementPolicy, cardinality: int, *,
                axis: str = "data", capacity_factor: float = 2.0) -> Callable:
    """fn(keys, vals) -> (G,) per-group medians, replicated in natural
    group order under every policy.

    W1 no longer carries its own shard_map plan: the median is expressed
    as a logical ``Aggregate`` with an order-statistic ("median") agg and
    lowered through the planner's distributed backend onto the holistic
    primitives above — FIRST_TOUCH / LOCAL_ALLOC / PREFERRED degrade to
    full record replication, INTERLEAVE runs the routed distributed
    selection. One copy of each placement strategy serves W1 and every
    TPC-H median plan alike; this thin wrapper keeps the bare-operator
    signature for the fig5 benchmark."""
    from repro.analytics import plan as L
    from repro.analytics import planner

    lplan = L.LogicalPlan(
        L.scan("t").aggregate("k", cardinality, med=("median", "v")),
        ("med",))
    ctx = planner.ExecutionContext(executor="xla", mesh=mesh, policy=policy,
                                   axis=axis, capacity_factor=capacity_factor)

    def fn(keys, vals):
        return planner.execute_plan(lplan, {"t": {"k": keys, "v": vals}},
                                    ctx)["med"]

    return fn


# ---------------------------------------------------------------------------
# W3: hash join under each policy
# ---------------------------------------------------------------------------
def dist_hash_join(mesh: Mesh, policy: PlacementPolicy, *,
                   axis: str = "data", capacity_factor: float = 2.0) -> Callable:
    """fn(build_keys, build_vals, probe_keys) -> (count, checksum).

    W3 no longer carries its own shard_map plan: the join is a logical
    ``Join`` + global ``Aggregate`` lowered through the planner's
    distributed backend. The placement policy fixes the physical join
    strategy the cost model would otherwise choose: INTERLEAVE routes both
    sides by join-key hash (partitioned join, the paper's winner for large
    build sides); the replication-based policies broadcast the build side
    (all-gather, as a first-touching shard would fault it in). PREFERRED's
    record convergence lives in its Aggregate lowering."""
    from repro.analytics import plan as L
    from repro.analytics import planner

    probe = L.scan("probe").join(L.scan("build"), "pk", "bk", {"_v": "bv"})
    lplan = L.LogicalPlan(
        probe.aggregate(None, 1, count=("count", "_v"),
                        checksum=("sum", "_v")),
        ("count", "checksum"))
    dist_join = ("partitioned" if policy == PlacementPolicy.INTERLEAVE
                 else "broadcast")
    # dist_route="modulo": the retired W3 shard_map plan routed by key % n,
    # and the pinned fixture (tests/fixtures/w1w3_retired_plans.npz) checks
    # the float checksums BIT-exactly — identical data movement, identical
    # per-shard reduction order. New plans default to hash-based routing.
    ctx = planner.ExecutionContext(executor="xla", mesh=mesh, policy=policy,
                                   axis=axis, capacity_factor=capacity_factor,
                                   dist_join=dist_join, dist_route="modulo")

    def fn(bk, bv, pk):
        out = planner.execute_plan(
            lplan, {"probe": {"pk": pk}, "build": {"bk": bk, "bv": bv}}, ctx)
        return out["count"][0], out["checksum"][0]

    return fn
