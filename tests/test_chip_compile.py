"""Compile-only checks for a described (not attached) TPU v5e chip.

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the (8,128) tiling, kernels that ask for more VMEM than the chip
gives, programs that do not fit HBM.  These tests compile each Pallas kernel
and the served granite-moe-1b-a400m prefill and decode at real widths.
Nothing runs; the topology is described inside a fixture (never at import),
and the persistent compilation cache is off around the compiles.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.offload_copy import offload_copy_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(s, h, kh, hd):
    return (functools.partial(flash_attention_pallas, causal=True),
            [(1, s, h, hd), (1, s, kh, hd), (1, s, kh, hd)], jnp.bfloat16)


KERNELS = {
    # granite-moe-1b-a400m: 16 heads (kv 8) x 64
    "flash_granite_moe": _flash(2048, 16, 8, 64),
    # zamba2-2.7b shared attention: 32 heads (kv 32) x 80
    "flash_zamba2": _flash(2048, 32, 32, 80),
    # zamba2-2.7b SSD: 80 heads x 64, one group, state 64, chunk 256
    "ssd_scan_zamba2": (
        functools.partial(ssd_scan_pallas, chunk=256),
        [(1, 2048, 80, 64), (1, 2048, 1, 64), (1, 2048, 1, 64),
         ((1, 2048, 80), jnp.float32), ((1, 2048, 80), jnp.float32),
         ((80,), jnp.float32)], jnp.bfloat16),
    # a 64 MiB f32 slab: the VMEM budget must shrink the 256-row blocks
    "offload_copy_f32_slab": (
        functools.partial(offload_copy_pallas, block_rows=256, depth=2),
        [(4096, 4096)], jnp.float32),
    "offload_copy_bf16_inject": (
        functools.partial(offload_copy_pallas, block_rows=256, depth=3,
                          out_dtype=jnp.float32, inject=True),
        [(4096, 1024)], jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes, dtype = KERNELS[name]
    args = [_spec(one_chip, *s) if isinstance(s[0], tuple)
            else _spec(one_chip, s, dtype) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def granite_server(one_chip):
    """The served granite-moe-1b-a400m at published widths and full depth,
    with parameter *shapes* on the described chip."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import BatchedServer, ServeConfig
    cfg = get_config("granite-moe-1b-a400m")
    model = build_model(cfg)
    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          jax.eval_shape(model.init, jax.random.key(0)))
    server = BatchedServer(model, params, ServeConfig(max_len=1024))
    yield cfg, server
    server.close()


def test_granite_moe_prefill_and_decode_compile_for_v5e(one_chip,
                                                        granite_server):
    cfg, server = granite_server
    b, s = 8, 512
    batch = {"tokens": _spec(one_chip, (b, s), jnp.int32)}
    prefill = server._prefill.lower(server.params, batch).compile()
    logits, cache = jax.eval_shape(server._prefill, server.params, batch)
    assert logits.shape[0] == b and logits.shape[-1] == cfg.vocab_size
    cache = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), cache)
    tok = _spec(one_chip, (b, 1), jnp.int32)
    decode = server._decode.lower(server.params, cache, tok).compile()
    param_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree.leaves(server.params))
    assert param_bytes > 2 << 30                       # ~1.3 B bf16 params
    for compiled in (prefill, decode):
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes >= param_bytes
        assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < V5E_HBM_BYTES
