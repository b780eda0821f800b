"""GQA flash attention (forward) with explicit VMEM tiling.

The attention hot-spot under the same pipelined-DMA discipline as
``offload_copy``: BlockSpec-driven HBM→VMEM streaming of K/V tiles with a
running (m, l, acc) online-softmax state in VMEM scratch — the bounded
working set that makes 32k-token prefill feasible.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
``arbitrary`` (sequential) so scratch carries across kv tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  bq: int, bk: int, nk: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def tile():
        q = q_ref[...]                                  # (bq, hd)
        k = k_ref[...]                                  # (bk, hd)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_s[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, hd)
        acc[...] = acc[...] * corr + pv

    if causal:
        # skip tiles fully above the diagonal
        pl.when((ki * bk) <= (qi * bq + bq - 1))(tile)
    else:
        tile()

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False):
    """q: (B,S,H,hd); k/v: (B,T,K,hd) -> (B,S,H,hd).

    The kernel runs head-major, (B,H,S,hd): each block is a (seq, hd)
    tile, so its last two dims meet the TPU's (8,128) tiling rule for any
    head count (batch and head dims are squeezed out of the block)."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    nq, nk = s // bq, t // bk
    scale = 1.0 / float(hd) ** 0.5

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               causal=causal, scale=scale)
    sq = pl.squeezed
    q_spec = pl.BlockSpec((sq, sq, bq, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((sq, sq, bk, hd),
                           lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))
    o = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return o.transpose(0, 2, 1, 3)
