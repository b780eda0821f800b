"""Mixture-of-Experts layer: top-k token-choice routing with capacity.

GShard-style grouped one-hot dispatch (einsum form) so GSPMD can shard the
expert dimension over the ``model`` axis (expert parallelism) and insert the
dispatch collectives.  Tokens are processed in groups of ``GROUP_SIZE`` to
bound the dispatch-tensor working set (the same size-threshold discipline
the paper applies to offloaded transfers).  A ragged tail is padded into
a last group whose pad slots are masked out of routing: they take no expert
capacity and get no output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, pdtype
from repro.sharding import api as shard_api

GROUP_SIZE = 512


def moe_init(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = pdtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, e), dt),
        "we_g": dense_init(k2, (e, d, f), dt),
        "we_u": dense_init(k3, (e, d, f), dt),
        "we_d": dense_init(k4, (e, f, d), dt),
    }


def moe_param_count(cfg: ModelConfig) -> int:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return d * e + 3 * e * d * f


def expert_capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens_per_group * cfg.num_experts_per_token
                  / cfg.num_experts * cfg.moe_capacity_factor)
    return max(c, 1)


def moe_apply(params, x, cfg: ModelConfig):
    """x: (B, S, D) -> (y, aux_loss). Top-k routing with capacity dropping."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    n = b * s
    tg = min(GROUP_SIZE, n)
    ng = -(-n // tg)
    xt = jnp.pad(x.reshape(n, d), ((0, ng * tg - n), (0, 0))).reshape(ng, tg, d)
    valid = (jnp.arange(ng * tg) < n).reshape(ng, tg)
    cap = expert_capacity(tg, cfg)

    # --- routing (fp32) -----------------------------------------------------
    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (g, t, e)
    gate_vals, idx = jax.lax.top_k(probs, k)                 # (g, t, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)    # renormalize

    # --- position-in-expert with capacity, k priority order ------------------
    dispatch = jnp.zeros((ng, tg, e, cap), x.dtype)
    combine = jnp.zeros((ng, tg, e, cap), jnp.float32)
    counts = jnp.zeros((ng, e), jnp.int32)
    for kk in range(k):
        m = jax.nn.one_hot(idx[..., kk], e, dtype=jnp.int32) \
            * valid[..., None]                                         # (g,t,e)
        pos = jnp.cumsum(m, axis=1) - m + counts[:, None, :]          # (g,t,e)
        keep = (pos < cap) & (m > 0)
        poh = jax.nn.one_hot(pos, cap, dtype=x.dtype) * keep[..., None].astype(x.dtype)
        dispatch = dispatch + poh
        combine = combine + poh.astype(jnp.float32) * gate_vals[..., kk][..., None, None]
        counts = counts + jnp.sum(m, axis=1)

    # --- expert computation (sharded over the expert axis) -------------------
    ein = jnp.einsum("gtec,gtd->egcd", dispatch, xt)
    ein = shard_api.constrain(ein, "expert", "batch", None, None)
    wg = params["we_g"].astype(ein.dtype)
    wu = params["we_u"].astype(ein.dtype)
    wd = params["we_d"].astype(ein.dtype)
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", ein, wg)) \
        * jnp.einsum("egcd,edf->egcf", ein, wu)
    eout = jnp.einsum("egcf,efd->egcd", h, wd)
    eout = shard_api.constrain(eout, "expert", "batch", None, None)
    y = jnp.einsum("egcd,gtec->gtd", eout, combine.astype(eout.dtype))

    # --- load-balancing aux loss (switch-style) -------------------------------
    # fraction of tokens whose top-1 choice is expert e  ×  mean router prob
    w = valid[..., None].astype(jnp.float32) / n
    top1 = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
    frac = jnp.sum(top1 * w, axis=(0, 1))
    mean_p = jnp.sum(probs * w, axis=(0, 1))
    aux = e * jnp.sum(frac * mean_p)

    return y.reshape(ng * tg, d)[:n].reshape(b, s, d), aux
