"""Mixture-of-Experts FFN: top-k token-choice routing, sort-based dispatch.

TPU-native adaptation: instead of per-token pointer chasing (GPU style
scatter into expert queues), tokens are argsorted by expert id and packed
into a dense (E, capacity, d) buffer — all gathers/scatters are large,
contiguous, MXU-feedable ops, and expert FFNs run as one grouped einsum.

Sharding: the expert dimension of the stacked weights carries the logical
axis "expert", mapped to "model" (phi: 16 experts / 16-way TP = 1 expert per
TP rank) or ("data","model") for deepseek-scale EP (256 experts / 256 chips).
Dispatch then lowers to all-to-alls under SPMD; the explicit shard_map
variant is a §Perf hillclimb (see EXPERIMENTS.md).

Aux losses follow the standard load-balancing formulation
(mean_prob_per_expert x token_fraction_per_expert x E).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ArchConfig, MoEConfig
from repro.core.params import pdef
from repro.models.layers import activation


def moe_schema(arch: ArchConfig, expert_axis: str = "expert") -> Dict[str, Any]:
    m = arch.moe
    d, de = arch.d_model, m.d_expert
    E = m.n_experts
    s = {
        "router": pdef((d, E), ("embed", None), "scaled"),
        "w_gate": pdef((E, d, de), (expert_axis, "embed", "expert_ff"), "scaled"),
        "w_up": pdef((E, d, de), (expert_axis, "embed", "expert_ff"), "scaled"),
        "w_down": pdef((E, de, d), (expert_axis, "expert_ff", "embed"), "scaled"),
    }
    if m.n_shared_experts:
        dsh = de * m.n_shared_experts
        s["shared_gate"] = pdef((d, dsh), ("embed", "ff"), "scaled")
        s["shared_up"] = pdef((d, dsh), ("embed", "ff"), "scaled")
        s["shared_down"] = pdef((dsh, d), ("ff", "embed"), "scaled")
    return s


def _capacity(n_tokens: int, moe: MoEConfig) -> int:
    per_expert = n_tokens * moe.top_k / moe.n_experts
    cap = int(per_expert * moe.capacity_factor)
    return max(8, (cap + 7) // 8 * 8)


def shared_expert_forward(p: Dict[str, Any], x: jax.Array,
                          arch: ArchConfig) -> jax.Array:
    """Always-on (deepseek) shared experts — a plain TP FFN, computed
    outside the routed dispatch."""
    f = activation(arch.act)
    sh = f(x @ p["shared_gate"]) * (x @ p["shared_up"])
    return sh @ p["shared_down"]


def moe_forward(p: Dict[str, Any], x: jax.Array, arch: ArchConfig, *,
                capacity: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    m = arch.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    C = capacity or _capacity(T, m)
    xt = x.reshape(T, d)

    # --- routing (fp32) ----------------------------------------------------
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gates, ids = jax.lax.top_k(probs, K)                          # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- aux load-balancing loss -------------------------------------------
    me = probs.mean(axis=0)                                       # (E,)
    one_hot_topk = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1)  # (T, E)
    ce = one_hot_topk.mean(axis=0) / K
    aux = (me * ce).sum() * E * m.router_aux_weight

    # --- sort-based dispatch -------------------------------------------------
    flat_e = ids.reshape(T * K)                                   # expert ids
    flat_t = jnp.repeat(jnp.arange(T), K)                         # token ids
    flat_g = gates.reshape(T * K)
    order = jnp.argsort(flat_e)  # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(flat_e, length=E)                       # (E,)
    starts = jnp.cumsum(counts) - counts                          # exclusive
    pos_in_e = jnp.arange(T * K) - starts[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)              # overflow slot

    buf = jnp.zeros((E * C + 1, d), xt.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xt[st], 0))
    hidden = buf[:-1].reshape(E, C, d)

    # --- grouped expert FFN --------------------------------------------------
    f = activation(arch.act)
    h = f(jnp.einsum("ecd,edf->ecf", hidden, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", hidden, p["w_up"])
    y_exp = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, d)
    y_exp = jnp.concatenate([y_exp, jnp.zeros((1, d), y_exp.dtype)], axis=0)

    # --- combine --------------------------------------------------------------
    contrib = y_exp[slot] * (sg * keep).astype(y_exp.dtype)[:, None]
    out = jnp.zeros((T, d), xt.dtype).at[st].add(contrib)

    # --- shared (always-on) experts ------------------------------------------
    if m.n_shared_experts:
        out = out + shared_expert_forward(p, xt, arch)

    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism: explicit per-device dispatch + all-to-all
# ---------------------------------------------------------------------------
def moe_forward_sharded(p: Dict[str, Any], x: jax.Array, arch: ArchConfig, *,
                        mesh, expert_axes: Tuple[str, ...],
                        token_spec) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE with explicit communication.

    The jit-SPMD (gather) path above defeats the XLA partitioner: the
    data-dependent scatter/gather dispatch gets replicated per device
    (measured: 255 GB/device temp for deepseek-v3 train — see EXPERIMENTS
    §Perf "before"). This version makes the paper's INTERLEAVE policy
    explicit: every device owns E/n experts, routes its resident tokens
    with a dense (n_shards, capacity) all-to-all, runs its expert FFN on
    what arrives, and routes results back. Per-device memory is
    O(T_local * top_k * capacity_factor * d); wire bytes are 2 passes of
    the routed activations — independent of E.

    Requires the residual stream to be fully sharded over ``mesh`` (batch
    over data, sequence over model — the SP layout), so each token lives on
    exactly one device. ``token_spec`` is that PartitionSpec.
    """
    from jax.sharding import PartitionSpec as P

    m = arch.moe
    E, K = m.n_experts, m.top_k
    n_shards = 1
    for a in expert_axes:
        n_shards *= mesh.shape[a]
    if E % n_shards:
        raise ValueError(f"{E} experts not divisible by {n_shards} shards")
    e_local = E // n_shards
    axis = expert_axes if len(expert_axes) > 1 else expert_axes[0]
    f = activation(arch.act)

    def local_fn(router, wg, wu, wd, xb):
        # xb: (B_loc, S_loc, d) — this device's resident tokens
        Bl, Sl, d = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, K)                  # (T, K)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        # aux loss (global): local partials averaged over the WHOLE mesh
        # (tokens are sharded over every axis under the SP layout)
        all_axes = tuple(mesh.axis_names)
        me = jax.lax.pmean(probs.mean(0), all_axes)
        ce = jax.lax.pmean(
            jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1).mean(0) / K,
            all_axes)
        aux = (me * ce).sum() * E * m.router_aux_weight

        # ---- route to owning shard -----------------------------------
        flat_e = ids.reshape(T * K)
        flat_t = jnp.repeat(jnp.arange(T), K)
        flat_g = gates.reshape(T * K)
        owner = flat_e // e_local                              # (T*K,)
        cap = max(8, -(-int(T * K / n_shards * m.capacity_factor) // 8) * 8)
        order = jnp.argsort(owner, stable=True)
        so, se, st, sg = (owner[order], flat_e[order], flat_t[order],
                          flat_g[order])
        counts = jnp.bincount(owner, length=n_shards)
        starts = jnp.cumsum(counts) - counts
        slot_idx = starts[:, None] + jnp.arange(cap)[None, :]  # (n, cap)
        valid = jnp.arange(cap)[None, :] < jnp.minimum(counts, cap)[:, None]
        slot_idx = jnp.clip(slot_idx, 0, T * K - 1)
        send_x = jnp.where(valid[..., None], xt[st[slot_idx]], 0)
        send_e = jnp.where(valid, se[slot_idx] % e_local, -1)  # local id
        # token origin slot for the return trip is positional (same layout)

        recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, axis, 0, 0, tiled=True)
        # recv_*: (n_shards * cap, ...) after tiled concat? tiled all_to_all
        # keeps leading dim = n_shards * cap / n_shards ... reshape to flat:
        rx = recv_x.reshape(-1, d)
        re = recv_e.reshape(-1)

        # ---- local expert FFN (e_local experts on this device) --------
        y = jnp.zeros((rx.shape[0], d), rx.dtype)
        for le in range(e_local):
            sel = (re == le)[:, None].astype(rx.dtype)
            xin = rx * sel
            h = f(xin @ wg[le]) * (xin @ wu[le])
            y = y + (h @ wd[le]) * sel
        y = y.reshape(recv_x.shape)

        # ---- route back + combine -------------------------------------
        back = jax.lax.all_to_all(y, axis, 0, 0, tiled=True)   # (n, cap, d)
        contrib = jnp.where(valid[..., None], back, 0)
        gsel = (sg[slot_idx] * valid).astype(xt.dtype)
        out = jnp.zeros((T, d), xt.dtype).at[
            st[slot_idx].reshape(-1)
        ].add((contrib * gsel[..., None]).reshape(-1, d))
        return out.reshape(Bl, Sl, d), aux

    espec = P(axis)
    wrapped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), espec, espec, espec, token_spec),
        out_specs=(token_spec, P()),
        check_vma=False)
    return wrapped(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
