"""The DSA-engine analogue on TPU: an explicit-DMA streaming copy/transform
kernel with ROCKET's three execution modes and the VMEM-injection knob.

Structure (paper §II-B mapped to TPU):
- *descriptor submission* = ``pltpu.make_async_copy(...).start()``;
- *completion flag*       = the DMA semaphore, ``.wait()``;
- *sync mode*             = depth-1: copy-in → wait → transform → copy-out → wait;
- *async/pipelined*       = depth-k rotation: block i+depth's copy-in is
  submitted while block i is transformed (compute hides the DMA, the same
  overlap the paper gets from its async engine);
- *cache injection*       = ``inject=True`` fuses the consumer (a global
  reduction over the destination — the paper's Fig.-5 microbenchmark) into
  the kernel while the data is VMEM-resident, instead of a second HBM pass.

The transform is a fused scale+cast (a copy engine with a twist, as used by
the data pipeline for dtype conversion on the fly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # TPU lane width; last dim of blocks
# Bytes of VMEM scratch the in/out rotation may take: half of v5e's 16 MiB
# default scoped-VMEM limit, leaving the rest to the compiler.
VMEM_BUDGET = 8 << 20


def _copy_kernel(x_hbm, y_hbm, sum_out, vmem_in, vmem_out, sem_in, sem_out,
                 acc, *, block_rows: int, depth: int, n_blocks: int,
                 scale: float, out_dtype, inject: bool):
    i = pl.program_id(0)
    slot = jax.lax.rem(i, depth)

    def in_copy(b, s):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(b * block_rows, block_rows)],
            vmem_in.at[s], sem_in.at[s])

    def out_copy(b, s):
        return pltpu.make_async_copy(
            vmem_out.at[s],
            y_hbm.at[pl.ds(b * block_rows, block_rows)], sem_out.at[s])

    # --- warm-up: submit the first `depth` descriptors ------------------------
    @pl.when(i == 0)
    def _():
        if inject:
            acc[0, 0] = 0.0
        for d in range(depth):

            @pl.when(d < n_blocks)
            def _():
                in_copy(d, d).start()

    # --- completion check for this block's copy-in ----------------------------
    in_copy(i, slot).wait()

    # --- the previous occupant of the out-slot must have drained ---------------
    @pl.when(i >= depth)
    def _():
        out_copy(i - depth, slot).wait()

    # --- transform while VMEM-resident ----------------------------------------
    data = vmem_in[slot]
    vmem_out[slot] = (data.astype(jnp.float32) * scale).astype(out_dtype)
    if inject:   # fused consumer: reduce the destination while it's in VMEM
        acc[0, 0] += jnp.sum(data.astype(jnp.float32) * scale)

    # --- submit copy-out + prefetch block i+depth ------------------------------
    out_copy(i, slot).start()

    @pl.when(i + depth < n_blocks)
    def _():
        in_copy(i + depth, slot).start()

    # --- drain on the last block ------------------------------------------------
    @pl.when(i == n_blocks - 1)
    def _():
        for d in range(depth):
            b = i - d

            @pl.when((b >= 0) & (b + depth >= n_blocks))
            def _():
                out_copy(b, jax.lax.rem(b, depth)).wait()
        if inject:
            sum_out[0, 0] = acc[0, 0]


def offload_copy_pallas(x, *, scale: float = 1.0, out_dtype=None,
                        depth: int = 2, block_rows: int = 256,
                        inject: bool = False, interpret: bool = False):
    """x: (R, LANE·k) — streams row-blocks through VMEM. Returns (y, sum|None).

    ``block_rows`` is halved until the ``depth``-deep in/out buffers fit
    :data:`VMEM_BUDGET`, so a wide slab never asks for more VMEM than the
    chip gives a kernel."""
    assert x.ndim == 2, "offload_copy operates on 2D row-major slabs"
    rows, cols = x.shape
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    row_bytes = depth * cols * (x.dtype.itemsize + out_dtype.itemsize)
    while block_rows * row_bytes > VMEM_BUDGET and block_rows % 16 == 0:
        block_rows //= 2
    n_blocks = rows // block_rows
    depth = max(1, min(depth, n_blocks))

    kernel = functools.partial(
        _copy_kernel, block_rows=block_rows, depth=depth, n_blocks=n_blocks,
        scale=scale, out_dtype=out_dtype, inject=inject)

    out_shapes = [
        jax.ShapeDtypeStruct((rows, cols), out_dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
    ]
    y, total = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((depth, block_rows, cols), x.dtype),
            pltpu.VMEM((depth, block_rows, cols), out_dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
    return (y, total[0, 0]) if inject else (y, None)
