"""jit'd public wrappers for the Pallas kernels, with ROCKET offload control:
below the size threshold (or via policy device=inline) the inline XLA path is
used instead of the kernel — the paper's cpu/dsa knob at tier 3.

``interpret=True`` runs a kernel in the Pallas interpreter (how the tests
check them on the CPU); only a caller chooses it, never the backend.
"""
from __future__ import annotations

import functools

import jax

from repro.core.policy import OffloadPolicy
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.offload_copy import offload_copy_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=(
    "scale", "out_dtype", "depth", "block_rows", "inject", "policy",
    "interpret"))
def offload_copy(x, *, scale: float = 1.0, out_dtype=None, depth: int = 2,
                 block_rows: int = 256, inject: bool = False,
                 policy: OffloadPolicy | None = None, interpret: bool = False):
    """Streaming copy/transform; inline XLA path below the size threshold."""
    pol = policy or OffloadPolicy()
    if not pol.should_offload(x.size * x.dtype.itemsize):
        return ref.offload_copy(x, scale=scale, out_dtype=out_dtype,
                                inject=inject or pol.injection_enabled())
    mode_depth = {"sync": 1, "async": 2, "pipelined": max(depth, 2)}[
        pol.mode.value]
    return offload_copy_pallas(
        x, scale=scale, out_dtype=out_dtype, depth=mode_depth,
        block_rows=block_rows, inject=inject or pol.injection_enabled(),
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, bm, cm, dt, da, d_skip, *, chunk: int = 256,
             interpret: bool = False):
    return ssd_scan_pallas(xh, bm, cm, dt, da, d_skip, chunk=chunk,
                           interpret=interpret)
