"""Training step: loss -> grads (with sharding-local microbatch accumulation)
-> AdamW update.

Tier-2 ROCKET movement modes are applied *around* this function by the
launcher via sharding specs (sync = all-reduce baseline; pipelined = ZeRO-1
moment sharding -> reduce-scatter + all-gather; compression = bf16 grad sync
via ``AdamWConfig.grad_sync_dtype``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.registry import ModelAPI
from repro.optim import adamw
from repro.sharding import api as shard_api


@dataclass(frozen=True)
class TrainConfig:
    opt: adamw.AdamWConfig = field(default_factory=adamw.AdamWConfig)
    microbatches: int = 1
    accum_dtype: str = "float32"
    # manual data parallelism over these mesh axes (shard_map): gradients are
    # accumulated *locally* and reduced ONCE per step — the explicit analogue
    # of deferring completion checks to batch granularity (paper's pipelined
    # mode), instead of GSPMD's per-layer in-loop all-reduces.  Requires
    # replicated parameters over these axes (layout dp_only for model axis).
    manual_dp_axes: tuple = ()


def _split_microbatches(batch, m: int):
    """(B, ...) -> (M, B//M, ...) preserving per-device row locality:
    reshape (B,...)->(B//M, M, ...) keeps each device's rows in place, then
    the scan axis is moved to the front (a transpose over a replicated dim).
    """
    def fn(x):
        b = x.shape[0]
        assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
        return jnp.swapaxes(x.reshape(b // m, m, *x.shape[1:]), 0, 1)
    return jax.tree.map(fn, batch)


def make_train_step(model: ModelAPI, tcfg: TrainConfig):
    def loss_fn(params, mb):
        return model.loss(params, mb)

    def grads_of(params, batch):
        """loss/grads with local microbatch accumulation."""
        m = tcfg.microbatches
        if m > 1:
            mbs = _split_microbatches(batch, m)
            acc_dt = jnp.dtype(tcfg.accum_dtype)

            def mb_step(gacc, mb):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(acc_dt), gacc, grads)
                return gacc, (loss, metrics)

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
            gacc, (losses, metricss) = jax.lax.scan(mb_step, g0, mbs)
            grads = jax.tree.map(lambda g: g / m, gacc)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(lambda x: jnp.mean(x.astype(jnp.float32)),
                                   metricss)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if tcfg.manual_dp_axes:
            loss, metrics, grads = _manual_dp_grads(
                model, tcfg, grads_of, params, batch)
        else:
            loss, metrics, grads = grads_of(params, batch)
        params, opt_state, om = adamw.update(tcfg.opt, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step


def _manual_dp_grads(model, tcfg, grads_of, params, batch):
    """shard_map manual data parallelism: per-shard backward with *local*
    gradient accumulation, one ``pmean`` per step (batch-granularity
    completion, ROCKET pipelined mode at tier 2)."""
    from jax.sharding import PartitionSpec as P
    mesh = shard_api.get_mesh()
    axes = tuple(a for a in tcfg.manual_dp_axes if a in mesh.axis_names)

    def shard_fn(params, batch):
        loss, metrics, grads = grads_of(params, batch)
        sync_dt = tcfg.opt.grad_sync_dtype
        if sync_dt:
            grads = jax.tree.map(
                lambda g: g.astype(jnp.dtype(sync_dt)), grads)
        grads = jax.lax.pmean(grads, axes)
        loss = jax.lax.pmean(loss, axes)
        metrics = jax.tree.map(lambda x: jax.lax.pmean(
            x.astype(jnp.float32), axes), metrics)
        return loss, metrics, grads

    batch_specs = jax.tree.map(
        lambda x: P(axes, *([None] * (x.ndim - 1))), batch)
    param_specs = jax.tree.map(lambda _: P(), params)
    out_specs = (P(), jax.tree.map(lambda _: P(), {"ce": 0, "aux": 0,
                                                   "tokens": 0}),
                 jax.tree.map(lambda _: P(), params))
    with shard_api.manual_mode():
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(param_specs, batch_specs),
            out_specs=out_specs, check_vma=False)(params, batch)


def init_train_state(model: ModelAPI, rng):
    params = model.init(rng)
    return params, adamw.init(params)
