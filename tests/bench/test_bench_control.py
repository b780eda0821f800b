"""The control of ``moe-classify-open``'s correctness limit, through the
harness's own comparison, at a size a test run can hold: granite-moe-1b-
a400m at its published widths and vocabulary, 2 of its 24 layers, one
served batch of 16 prompts of the cell's 256 tokens.  With the reference
computed with float8 operands in the program's place, the comparison
must come out not correct; with the bf16 program's tokens, correct."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path
from bench import gen
from bench.harness import Run, check_outputs
from bench.models import transformer as tf
from repro.configs import get_config
from repro.models import build_model

ROWS = 16


def _served(seed, tokens_of):
    """A run whose window served one batch of ``ROWS`` prompts of client
    0, answered with ``tokens_of(prompts)``; returns the configuration,
    the run and the recorded batch."""
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           "granite-moe-1b-a400m.json")) as f:
        conf = json.load(f)
    conf["arch"]["num_layers"] = 2
    arch = tf.Arch.from_dict(conf["arch"])
    mix = dict(gen.load_mix("classify-open"), check_requests=ROWS)
    run = Run("moe-classify-open", seed, 1.0, 1, arch, tf, mix)
    run.t0, run.t_end = 0.0, 1.0
    toks = np.stack([gen.prompt(seed, 0, k, mix["prompt_len"],
                                arch.vocab_size) for k in range(ROWS)])
    out = np.asarray(tokens_of(conf, arch, seed, toks),
                     np.int32).reshape(ROWS, 1)
    run.requests = [{"client": 0, "k": k, "due": 0.5, "sent": 0.5,
                     "done": 0.6, "tokens": out[k]} for k in range(ROWS)]
    return conf, run, [(0, 1, toks, out)]


def _reference_argmax(conf, arch, seed, toks):
    return tf.logits(arch, seed, toks, 1).argmax(-1)


def _program(conf, arch, seed, toks):
    cfg = dataclasses.replace(get_config(conf["repo_config"]),
                              num_layers=2, remat=False, fsdp=False)
    model = build_model(cfg)
    params = tf.init_weights(tf.seed_words(seed), arch, jnp.bfloat16)
    logits, _ = jax.jit(lambda p, b: model.prefill(
        p, b, max_len=toks.shape[1] + 1))(params, {"tokens": toks})
    return jnp.argmax(logits[:, -1], -1)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(seed):
    conf, run, recorded = _served(seed, _reference_argmax)
    checks, _ = check_outputs(run, conf, recorded, control=True)
    assert checks["tokens_short"]["value"] == 0
    assert not all(c["value"] <= c["limit"] for c in checks.values()), checks


def test_bf16_program_passes_the_limit():
    conf, run, recorded = _served(4, _program)
    checks, _ = check_outputs(run, conf, recorded, control=False)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
