"""Tier-2 movement modes preserve semantics: manual-DP shard_map training
equals the GSPMD-default step; decode movement variants equal baseline
decode (exact or within quantization error)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticLMSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models import attention as attn
from repro.models.transformer import lm_decode_step_inplace
from repro.optim import adamw
from repro.sharding import api as shard_api
from repro.train import TrainConfig, init_train_state, make_train_step


@pytest.fixture()
def unit_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    with shard_api.use_mesh(mesh):
        yield mesh


def test_manual_dp_equals_default_step(rng_key, unit_mesh):
    """shard_map manual-DP (one psum/step) is numerically the same step."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params, opt_state = init_train_state(model, rng_key)
    batch = jax.tree.map(jnp.asarray,
                         next(SyntheticLMSource(cfg, ShapeConfig("t", "train", 16, 4))))
    opt = adamw.AdamWConfig(warmup_steps=1, total_steps=10)
    p_ref, _, m_ref = make_train_step(model, TrainConfig(opt=opt))(
        params, opt_state, batch)
    p_man, _, m_man = make_train_step(
        model, TrainConfig(opt=opt, manual_dp_axes=("data", "model")))(
        params, opt_state, batch)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_man["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_man)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_manual_dp_with_microbatches(rng_key, unit_mesh):
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params, opt_state = init_train_state(model, rng_key)
    batch = jax.tree.map(jnp.asarray,
                         next(SyntheticLMSource(cfg, ShapeConfig("t", "train", 16, 4))))
    opt = adamw.AdamWConfig(warmup_steps=1, total_steps=10)
    p_ref, _, m_ref = make_train_step(
        model, TrainConfig(opt=opt, microbatches=2))(params, opt_state, batch)
    p_man, _, m_man = make_train_step(
        model, TrainConfig(opt=opt, microbatches=2,
                           manual_dp_axes=("data", "model")))(
        params, opt_state, batch)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_man["loss"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_man)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_sp_decode_attention_unit_axis(rng_key, unit_mesh):
    """Split-KV shard_map decode == merged decode on a size-1 model axis."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params = model.init(rng_key)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks[:, :8]}, max_len=16)
    la, _ = lm_decode_step_inplace(params, cache, toks[:, 8:9], cfg)
    lb, _ = lm_decode_step_inplace(params, cache, toks[:, 8:9], cfg,
                                   sp_axis="model")
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=1e-5, atol=1e-5)


def test_q8_cache_roundtrip_error_bounded(rng_key):
    """int8 KV quantization: per-vector relative error < 2%."""
    x = jax.random.normal(rng_key, (2, 16, 4, 32))
    q, s = attn.quantize_kv(x)
    y = attn.dequantize_kv(q, s, x.dtype)
    rel = float(jnp.max(jnp.abs(y - x)) / jnp.max(jnp.abs(x)))
    assert q.dtype == jnp.int8
    assert rel < 0.02, rel


def test_q8_decode_close_to_exact(rng_key):
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params = model.init(rng_key)
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks[:, :8]}, max_len=16)
    la, _ = model.decode_step(params, cache, toks[:, 8:9])
    kq, ks = attn.quantize_kv(cache["k"])
    vq, vs = attn.quantize_kv(cache["v"])
    qcache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs,
              "index": cache["index"]}
    lb, qc2 = lm_decode_step_inplace(params, qcache, toks[:, 8:9], cfg)
    assert qc2["k"].dtype == jnp.int8
    err = float(jnp.max(jnp.abs(la - lb)))
    assert err < 0.05, f"quantized decode too far from exact: {err}"
