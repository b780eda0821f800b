"""Mamba2 SSD chunk-scan kernel: carried (N,P) state in VMEM scratch.

Grid (batch, heads, chunks); the chunk dimension is sequential so the
recurrent state lives in VMEM across chunk tiles — inter-chunk state passing
without HBM round-trips (the VMEM-residency/"injection" discipline applied
to the scan carry).  All contractions are 2D MXU matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, dsk_ref,
                y_ref, hout_ref, state, *, q: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = x_ref[...].astype(jnp.float32)               # (q, P)
    bm = b_ref[...].astype(jnp.float32)              # (q, N)
    cm = c_ref[...].astype(jnp.float32)              # (q, N)
    dt = dt_ref[...].astype(jnp.float32)             # (q, 1)
    da = da_ref[...].astype(jnp.float32)             # (q, 1)
    dsk = dsk_ref[pl.program_id(1)]

    # inclusive cumsum of da as a row and as a column, from masked
    # reductions (no 1D vectors, no scan primitive)
    ji = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    sgm_row = jnp.sum(jnp.where(ji <= ii, jnp.broadcast_to(da, (q, q)), 0.0),
                      axis=0, keepdims=True)                        # (1,q)
    sgm = jnp.sum(jnp.where(ji == ii, jnp.broadcast_to(sgm_row, (q, q)),
                            0.0), axis=1, keepdims=True)            # (q,1)
    s_last = jnp.sum(da, axis=0, keepdims=True)                     # (1,1)
    dtx = dt * x                                                    # (q, P)

    # intra-chunk: M[j,i] = exp(s_j - s_i) (C_j . B_i), i <= j
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (q,q)
    causal = ii <= ji
    m = jnp.where(causal, cb * jnp.exp(jnp.where(causal, sgm - sgm_row,
                                                 0.0)), 0.0)
    y = jax.lax.dot_general(m, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (q,P)

    # inter-chunk: y_j += exp(s_j) C_j . h_prev
    y += jax.lax.dot_general(cm, state[...], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(sgm)

    # state update: h = exp(s_last) h + B^T (decay_to_end * dtx)
    decay = jnp.exp(s_last - sgm)                                   # (q,1)
    upd = jax.lax.dot_general(bm, decay * dtx, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)   # (N,P)
    state[...] = state[...] * jnp.exp(s_last) + upd

    y_ref[...] = (y + dsk * x).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _():
        hout_ref[...] = state[...].astype(hout_ref.dtype)


def ssd_scan_pallas(xh, bm, cm, dt, da, d_skip, *, chunk: int = 256,
                    interpret: bool = False):
    """xh (B,S,H,P); bm/cm (B,S,G,N); dt/da (B,S,H); d_skip (H,).

    Returns (y (B,S,H,P) fp32, h_final (B,H,N,P) fp32).

    The kernel runs head-major: each block is a (chunk, width) tile with
    batch and head squeezed out, dt/da are (chunk, 1) columns and the skip
    weights sit whole in SMEM, so every block meets the TPU's (8,128)
    tiling rule for any head count.
    """
    b, s, nh, p = xh.shape
    g, n = bm.shape[2], bm.shape[3]
    hg = nh // g
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    kernel = functools.partial(_ssd_kernel, q=q, nc=nc)
    heads = lambda a: a.transpose(0, 2, 1, 3)        # (B,S,H,·) <-> (B,H,S,·)
    col = lambda a: heads(a[..., None])              # (B,S,H) -> (B,H,S,1)
    sq = pl.squeezed

    def tile(width, group=1):
        return pl.BlockSpec((sq, sq, q, width),
                            lambda bi, hi, ci: (bi, hi // group, ci, 0))

    y, hf = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[tile(p), tile(n, hg), tile(n, hg), tile(1), tile(1),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[
            tile(p),
            pl.BlockSpec((sq, sq, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(heads(xh), heads(bm), heads(cm), col(dt), col(da),
      d_skip.astype(jnp.float32))
    return heads(y), hf
