"""Pallas TPU kernel for the RWKV6 WKV recurrence.

TPU adaptation: the (N x N) per-head state lives in VMEM scratch across the
sequential time-block grid axis (N = 64 -> 16 KB fp32, trivially resident).
Each step is one (1,N)x(N,N) matvec (MXU) plus rank-1 state update (VPU);
the time loop is an in-kernel fori_loop over the current (block_s, N) tile.
A chunked matmul formulation (flash-linear-attention style) is the recorded
hillclimb follow-up; this kernel is the faithful, bandwidth-efficient
baseline: r/k/v/w stream through VMEM once, state never leaves VMEM.

Grid: (batch * heads, seq_blocks); time is innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sout_ref, s_scr, *,
                block_s: int, n_s_blocks: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    r = r_ref[0].astype(jnp.float32)   # (bs, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)   # (1, N)

    def body(i, S):
        ri = jax.lax.dynamic_slice_in_dim(r, i, 1, 0)   # (1, N)
        ki = jax.lax.dynamic_slice_in_dim(k, i, 1, 0)
        vi = jax.lax.dynamic_slice_in_dim(v, i, 1, 0)
        wi = jax.lax.dynamic_slice_in_dim(w, i, 1, 0)
        bonus = jnp.sum(ri * u * ki)                    # scalar
        y = jax.lax.dot_general(ri, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y = y + bonus * vi                              # (1, N)
        # index the leading block dim with a size-1 slice, not a bare int:
        # int indices crash the interpreter's store discharge
        o_ref[pl.ds(0, 1), pl.ds(i, 1), :] = y[None].astype(o_ref.dtype)
        S = wi.reshape(-1, 1) * S + ki.reshape(-1, 1) * vi
        return S

    S = jax.lax.fori_loop(0, block_s, body, s_scr[...])
    s_scr[...] = S

    @pl.when(si == n_s_blocks - 1)
    def _emit_state():
        sout_ref[...] = S[None].astype(sout_ref.dtype)


def wkv6_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, *, block_s: int = 256,
                interpret: bool = False):
    """r,k,v,w: (B, S, H, N); u: (H, N). Returns (y (B,S,H,N) fp32, S_out)."""
    B, S, H, N = r.shape
    bs = max(1, min(block_s, S))
    while S % bs:
        bs //= 2
    ns = S // bs

    def hm(x):  # (B,S,H,N) -> (B*H, S, N) heads-major
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, N)

    rh, kh, vh, wh = hm(r), hm(k), hm(v), hm(w)
    kernel = functools.partial(_wkv_kernel, block_s=bs, n_s_blocks=ns)
    grid = (B * H, ns)
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, N), lambda g, si: (g, si, 0)),
            pl.BlockSpec((1, bs, N), lambda g, si: (g, si, 0)),
            pl.BlockSpec((1, bs, N), lambda g, si: (g, si, 0)),
            pl.BlockSpec((1, bs, N), lambda g, si: (g, si, 0)),
            pl.BlockSpec((1, N), lambda g, si, H=H: (g % H, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, N), lambda g, si: (g, si, 0)),
            pl.BlockSpec((1, N, N), lambda g, si: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rh, kh, vh, wh, u)
    y = jnp.moveaxis(y.reshape(B, H, S, N), 1, 2)
    return y, s_out.reshape(B, H, N, N)
