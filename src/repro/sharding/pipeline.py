"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis
via shard_map + collective_permute.

The stage axis holds one layer-group per shard; activations flow stage→stage
with `ppermute` while each stage processes a different microbatch — the
ROCKET *pipelined* execution mode applied to the layer dimension (submission
= microbatch injection at stage 0, completion = drain at the last stage,
depth = number of in-flight microbatches).

Schedule: GPipe forward with `n_micro + n_stages - 1` ticks. Stages idle in
the fill/drain bubbles (bubble fraction = (S-1)/(M+S-1), reported by
:func:`bubble_fraction` so the planner can size microbatch counts).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import api as shard_api


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, stage_params, x, *, axis: str,
                   n_micro: int):
    """Run ``y = stage_fn(params_s, ...) for s in stages`` as a pipeline.

    stage_params: pytree with leading dim = n_stages (sharded over ``axis``);
    x: (batch, ...) microbatched along dim 0 into ``n_micro`` slices.
    Returns y with the same shape as x (activations after the last stage).
    """
    mesh = shard_api.get_mesh()
    assert mesh is not None, "pipeline_apply requires an active mesh"
    n_stages = int(mesh.shape[axis])
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params_local, x_local):
        # params_local: (1, ...) this stage's parameters
        # x_local: full input (replicated); only stage 0 consumes it
        stage = jax.lax.axis_index(axis)
        p_mine = jax.tree.map(lambda t: t[0], params_local)
        xs = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        outs0 = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry              # buf: (mb, ...) in-flight act
            inject = xs[jnp.minimum(t, n_micro - 1)]
            buf = jnp.where((stage == 0) & (t < n_micro), inject, buf)
            y = stage_fn(p_mine, buf)
            # last stage banks microbatch (t - (n_stages-1)) when valid
            out_idx = t - (n_stages - 1)
            bank = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                bank,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm_fwd)
            return (buf, outs), None

        buf0 = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_micro + n_stages - 1))
        # every stage holds outs; only the last stage's is real — psum after
        # masking so the result is replicated
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs.reshape(b, *x_local.shape[1:])

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    with shard_api.manual_mode():
        out = jax.shard_map(
            per_stage, mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(), check_vma=False,
        )(stage_params, x)
    return out


def sequential_apply(stage_fn: Callable, stage_params, x):
    """Reference: apply the stages sequentially (no pipelining)."""
    def body(h, p):
        return stage_fn(p, h), None
    out, _ = jax.lax.scan(body, x, stage_params)
    return out
