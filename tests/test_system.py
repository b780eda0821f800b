"""End-to-end behaviour: training learns, checkpoint-resume is exact,
serving round-trips through the dispatcher, dry-run machinery works."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import ExecutionMode, OffloadPolicy
from repro.data import InputPipeline, SyntheticLMSource
from repro.models import build_model
from repro.optim import adamw
from repro.serve import BatchedServer, ServeConfig
from repro.train import TrainConfig, init_train_state, make_train_step


def test_training_reduces_loss(rng_key):
    """~40 steps on the synthetic induction task must clearly reduce loss."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params, opt_state = init_train_state(model, rng_key)
    tcfg = TrainConfig(opt=adamw.AdamWConfig(lr=1e-3, warmup_steps=5,
                                             total_steps=60))
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
    shape = ShapeConfig("t", "train", 32, 8)
    pipe = InputPipeline(SyntheticLMSource(cfg, shape, seed=0),
                         OffloadPolicy(mode=ExecutionMode.PIPELINED,
                                       offload_threshold_bytes=1))
    losses = []
    for _, batch in zip(range(40), pipe):
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
    pipe.close()
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.1, f"no learning: {first:.3f} -> {last:.3f}"


def test_microbatched_grads_match_full_batch(rng_key):
    """Gradient accumulation must be numerically equivalent to the full batch."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params, opt_state = init_train_state(model, rng_key)
    shape = ShapeConfig("t", "train", 16, 8)
    batch = next(SyntheticLMSource(cfg, shape, seed=1))
    batch = jax.tree.map(jnp.asarray, batch)
    opt = adamw.AdamWConfig(warmup_steps=1, total_steps=10)
    p1, _, m1 = make_train_step(model, TrainConfig(opt=opt, microbatches=1))(
        params, opt_state, batch)
    p4, _, m4 = make_train_step(model, TrainConfig(opt=opt, microbatches=4))(
        params, opt_state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_checkpoint_resume_bitexact(tmp_path, rng_key):
    """Train 6 steps straight == train 3, checkpoint, restore, train 3."""
    cfg = get_smoke_config("granite-moe-1b-a400m")
    model = build_model(cfg)
    shape = ShapeConfig("t", "train", 16, 4)
    tcfg = TrainConfig(opt=adamw.AdamWConfig(warmup_steps=2, total_steps=10))
    step_fn = jax.jit(make_train_step(model, tcfg))

    def run(n_start, n_end, params, opt_state):
        src = SyntheticLMSource(cfg, shape, seed=9)
        src.step = n_start
        for i in range(n_start, n_end):
            params, opt_state, m = step_fn(params, opt_state,
                                           jax.tree.map(jnp.asarray, next(src)))
        return params, opt_state

    params, opt_state = init_train_state(model, rng_key)
    pa, oa = run(0, 6, params, opt_state)

    pb, ob = run(0, 3, *init_train_state(model, rng_key))
    cm = CheckpointManager(str(tmp_path))
    cm.save(3, {"params": pb, "opt": ob})
    restored, _ = cm.restore(3, {"params": pb, "opt": ob})
    pc, oc = run(3, 6, restored["params"], restored["opt"])

    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serving_end_to_end(rng_key):
    cfg = get_smoke_config("qwen3-32b")
    model = build_model(cfg)
    params = model.init(rng_key)
    srv = BatchedServer(model, params,
                        ServeConfig(max_len=32, max_new_tokens=4),
                        OffloadPolicy(max_batch=4))
    with srv.make_dispatcher() as d:
        prompts = [np.arange(1, 6, dtype=np.int32) * (i + 1) % cfg.vocab_size
                   for i in range(5)]
        jids = [d.request("generate", p, mode="pipelined") for p in prompts]
        outs = [d.query(j) for j in jids]
    assert all(o.shape == (4,) for o in outs)
    assert srv.stats["requests"] == 5
    # determinism: same prompt -> same tokens
    a = srv.generate_batch(srv._pack([prompts[0]]))
    b = srv.generate_batch(srv._pack([prompts[0]]))
    np.testing.assert_array_equal(a, b)
    srv.close()


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """The real dry-run machinery on the production mesh (512 host devices),
    via subprocess so the main test process keeps 1 device."""
    env = dict(os.environ)
    # the 512 devices are host-platform devices: never probe for a TPU
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "granite-moe-1b-a400m", "--shape", "decode_32k", "--force"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "[ok" in out.stdout


@pytest.mark.parametrize("argv,ok", [
    (["--platform", "cpu", "--smoke"], True),   # the CPU rehearsal
    ([], False),                                # no TPU here: must fail
])
def test_chip_smoke_script(tmp_path, argv, ok):
    """``chip_smoke.py`` drives the served path end to end (3 client
    processes over the fabric, sync replies byte-identical to direct
    generation, cross-client batching); without a chip it fails and prints
    no result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run([sys.executable, "chip_smoke.py", *argv],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=root)
    lines = out.stdout.strip().splitlines()
    if not ok:
        assert out.returncode != 0
        assert not any(l.startswith('{"ok"') for l in lines)
        return
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert any(l.startswith("phase A: 6 sync replies byte-identical")
               for l in lines)
