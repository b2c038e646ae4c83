"""Explicit data-parallel train step with compressed gradient all-reduce.

Under plain jit+shardings, XLA inserts the gradient all-reduce implicitly
and there is no seam to compress it. This step builds the seam: the
forward/backward runs inside shard_map (model replicated, batch sharded
over the data axes), gradients are synchronized EXPLICITLY — either a
plain pmean or the int8 block-quantized scheme with error feedback
(optim.compression) — and the optimizer update runs replicated on the
synced grads. 4× fewer gradient wire bytes than bf16 at ~1e-2 relative
gradient error (bounded by block max/127, test-checked), unbiased over
steps via the error-feedback carry.

This is the small-model/large-fleet regime's step (model fits per device);
the FSDP/TP steps in launch/dryrun cover the sharded-model regime.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.config import RunConfig
from repro.models.lm import LMModel
from repro.optim import adamw, schedules
from repro.optim.compression import compressed_psum


def make_dp_train_step(model: LMModel, cfg: RunConfig, mesh: Mesh, *,
                       axis: str = "data",
                       total_steps: int = 10_000) -> Callable:
    """Returns step(params, opt_state, errors, batch, step) ->
    (params, opt_state, errors, metrics). ``errors`` is the error-feedback
    pytree (zeros_like params fp32; ignored when compression is off)."""
    tcfg = cfg.train
    compress = cfg.sharding.gradient_compression

    def local_grads(params, batch):
        def loss_fn(p):
            loss, _ = model.loss_fn(p, batch, z_loss=tcfg.z_loss)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    def sharded_part(params, errors, batch):
        # per-device: local microbatch forward/backward
        loss, grads = local_grads(params, batch)
        loss = jax.lax.pmean(loss, axis)
        if compress:
            grads, errors = compressed_psum(grads, axis, errors)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
        return loss, grads, errors

    batch_spec = jax.tree.map(lambda _: P(axis), {"tokens": 0, "labels": 0})

    def step(params, opt_state, errors, batch, step_idx):
        wrapped = jax.shard_map(
            sharded_part, mesh=mesh,
            in_specs=(P(), P(), {k: P(axis) for k in batch}),
            out_specs=(P(), P(), P()),
            check_vma=False)
        loss, grads, new_errors = wrapped(params, errors, batch)
        if not compress:
            # pmean already averaged; compression path averages internally
            pass
        lr = schedules.warmup_cosine(step_idx, peak_lr=tcfg.learning_rate,
                                     warmup_steps=tcfg.warmup_steps,
                                     total_steps=total_steps)
        new_params, new_opt, opt_metrics = adamw.update(
            grads, opt_state, params, lr, tcfg)
        metrics = {"loss": loss, "lr": lr, **opt_metrics}
        return new_params, new_opt, new_errors, metrics

    return step


def init_error_feedback(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
