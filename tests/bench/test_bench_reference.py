"""The benchmark's seeded weights and plain reference against the
program at a tiny size on the CPU (float32 on both sides)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path  # noqa: F401
from bench.models import transformer as tf
from repro.configs import get_config
from repro.models import build_model

SEED = 2 ** 32 + 77
TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=96, vocab_size=256, dtype="float32",
            param_dtype="float32", remat=False, fsdp=False)


def _pair(kind: str):
    """(program model, reference arch) of one tiny configuration."""
    if kind == "moe":
        cfg = dataclasses.replace(get_config("granite-moe-1b-a400m"), **TINY,
                                  num_experts=4, num_experts_per_token=2,
                                  moe_capacity_factor=1.0)
        extra = dict(num_experts=4, experts_per_token=2, capacity_factor=1.0,
                     moe_group=512)
    else:
        cfg = dataclasses.replace(get_config("granite-8b"), **TINY)
        extra = {}
    arch = tf.Arch(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   head_dim=16, d_ff=96, vocab_size=256,
                   rope_theta=cfg.rope_theta,
                   tie_embeddings=cfg.tie_embeddings, **extra)
    return build_model(cfg), arch


@pytest.mark.parametrize("kind", ["moe", "dense"])
def test_layout_matches_program_and_layers_regenerate(kind):
    model, arch = _pair(kind)
    want = jax.eval_shape(model.init, jax.random.key(0))
    params = tf.init_weights(tf.seed_words(SEED), arch, jnp.float32)
    assert jax.tree.structure(want) == jax.tree.structure(params)
    for w, p in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        assert w.shape == p.shape and w.dtype == p.dtype
    # one layer made alone equals its slice of the stacked tree
    root = tf._root_key(tf.seed_words(SEED))
    one = tf._layer_weights(root, arch, 1, jnp.float32)
    np.testing.assert_array_equal(one["attn/wq"],
                                  params["blocks"]["attn"]["wq"][1])
    other = tf.init_weights(tf.seed_words(SEED + 1), arch, jnp.float32)
    assert not np.array_equal(other["embed"]["embedding"],
                              params["embed"]["embedding"])


@pytest.mark.parametrize("rows", [1, 3, 4])
def test_moe_prefill_matches_program_with_capacity_drops(rows):
    model, arch = _pair("moe")
    params = tf.init_weights(tf.seed_words(SEED), arch, jnp.float32)
    rng = np.random.default_rng(rows)
    toks = rng.integers(0, arch.vocab_size, (rows, 16), dtype=np.int32)
    got, _ = jax.jit(lambda p, b: model.prefill(p, b, max_len=32))(
        params, {"tokens": toks})
    pad = np.zeros((4, 16), np.int32)
    pad[:rows] = toks
    ref = tf.logits(arch, SEED, pad, 1, rows=rows, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got)[:, 0], ref[:rows, 0],
                               atol=2e-4)


def test_dense_decode_through_cache_matches_full_forward():
    model, arch = _pair("dense")
    params = tf.init_weights(tf.seed_words(SEED), arch, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 256, (2, 12),
                                             dtype=np.int32)
    logits, cache = model.prefill(params, {"tokens": toks}, max_len=20)
    steps = [np.asarray(logits)[:, -1]]
    served = [steps[-1].argmax(-1)]
    for _ in range(5):
        tok = jnp.asarray(served[-1], jnp.int32)[:, None]
        logits, cache = model.decode_step(params, cache, tok)
        steps.append(np.asarray(logits)[:, -1])
        served.append(steps[-1].argmax(-1))
    full = np.concatenate([toks, np.stack(served[:-1], 1)], 1)
    ref = tf.logits(arch, SEED, full, 6, dtype=jnp.float32)
    np.testing.assert_allclose(np.stack(steps, 1), ref, atol=2e-4)
