"""The reference-module contract (``bench/models/__init__.py``): the
program check by the facts a module names, the comparison chosen by
whether a module couples rows, and a family the harness has no code for
run through it end to end on the CPU."""
import dataclasses
import json
import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path
import _hybrid_stand_in as stand_in
from bench import gen
from bench.harness import Run, check_outputs, program_config, run_cell
from bench.models import transformer as tf
from repro.configs import get_config, reduce_for_smoke
from repro.models import build_model
from repro.models import moe as moe_layer
from repro.serve import BatchedServer, ServeConfig

DENSE, MOE = "granite-8b-18of36", "granite-moe-1b-a400m"


def _conf(name):
    with open(os.path.join(_bench_path.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [DENSE, MOE])
def test_both_configurations_pass_the_program_check(name):
    conf = _conf(name)
    cfg = program_config(conf, tf, tf.Arch.from_dict(conf["arch"]))
    assert cfg.num_layers == conf["arch"]["num_layers"]


@pytest.mark.parametrize("name,fact,value", [
    (DENSE, "family", "ssm"),
    (DENSE, "num_layers", 17),
    (DENSE, "d_model", 2048),
    (DENSE, "num_heads", 16),
    (DENSE, "num_kv_heads", 4),
    (DENSE, "head_dim", 64),
    (DENSE, "d_ff", 8192),
    (DENSE, "vocab_size", 49153),
    (DENSE, "rope_theta", 1.0e6),
    (DENSE, "tie_embeddings", True),
    (DENSE, "num_experts", 8),
    (DENSE, "mlp_type", "gelu"),
    (DENSE, "norm_type", "layernorm"),
    (DENSE, "qk_norm", True),
    (DENSE, "attn_logit_softcap", 30.0),
    (DENSE, "dtype", "float32"),
    (DENSE, "param_dtype", "float32"),
    (MOE, "family", "dense"),          # an MoE file over a dense program
    (MOE, "num_experts", 16),
    (MOE, "num_experts_per_token", 4),
    (MOE, "moe_capacity_factor", 1.0),
    (MOE, "moe_group", 256),
])
def test_transformer_module_refuses_each_departure(name, fact, value,
                                                   monkeypatch):
    conf = _conf(name)
    if fact == "moe_group":
        monkeypatch.setattr(moe_layer, "GROUP_SIZE", value)
    else:
        conf["changes"] = dict(conf["changes"], **{fact: value})
    with pytest.raises(SystemExit, match=f"'{fact}'"):
        program_config(conf, tf, tf.Arch.from_dict(conf["arch"]))


def test_a_fact_the_program_lacks_departs():
    conf = _conf(DENSE)
    module = types.SimpleNamespace(expected_program=lambda a, dtype: {
        **tf.expected_program(a, dtype), "no_such_fact": 1})
    with pytest.raises(SystemExit, match="'no_such_fact': \\('missing'"):
        program_config(conf, module, tf.Arch.from_dict(conf["arch"]))


# ---------------------------------------------------------------------------
# the comparison each module's answer selects
# ---------------------------------------------------------------------------

V, PROMPT, NEW, ROWS = 32, 8, 3, 4


def _next(tok):
    return (tok * 3 + 1) % V


def _fake_module(coupled: bool, calls: list):
    """A module whose reference puts ``_next(t)`` first after token t."""
    def logits(a, seed, tokens, last, rows=None, control=False):
        calls.append((tokens.shape, last, rows))
        best = _next(tokens[:, tokens.shape[1] - last:])
        return -np.abs(np.arange(V) - best[..., None]).astype(np.float32)
    return types.SimpleNamespace(couples_rows=lambda a: coupled,
                                 logits=logits)


def _run(module, seed, served_of):
    """A run whose window answered ``ROWS`` requests of client 0 with
    ``served_of(prompt)``."""
    mix = {"prompt_len": PROMPT, "new_tokens": NEW, "check_requests": ROWS}
    run = Run("dense-chat-open", seed, 1.0, 1,
              types.SimpleNamespace(vocab_size=V), module, mix)
    run.t0, run.t_end = 0.0, 1.0
    run.requests = [{"client": 0, "k": k, "due": 0.5, "sent": 0.5,
                     "done": 0.6, "tokens": np.asarray(served_of(
                         gen.prompt(seed, 0, k, PROMPT, V)), np.int32)}
                    for k in range(ROWS)]
    return run


def _greedy(prompt):
    out = [_next(prompt[-1])]
    while len(out) < NEW:
        out.append(_next(out[-1]))
    return out


def test_uncoupled_rows_are_compared_per_request_over_every_token():
    conf = {"serve": {"max_batch": ROWS}, "correct": {"max_logit_gap": 0.5}}
    calls = []
    run = _run(_fake_module(False, calls), 5, _greedy)
    checks, readings = check_outputs(run, conf, [], control=False)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    assert readings["max_logit_gap"] == 0.0
    # every sampled request, prompt and served tokens but the last, at once
    assert calls == [((ROWS, PROMPT + NEW - 1), NEW, ROWS)]
    run.requests[2]["tokens"][1] += 1          # one token altered
    checks, _ = check_outputs(run, conf, [], control=False)
    assert checks["max_logit_gap"]["value"] >= 1.0
    assert checks["tokens_short"]["value"] == 0


def test_coupled_rows_at_several_tokens_out_still_raise():
    run = _run(_fake_module(True, []), 5, _greedy)
    with pytest.raises(NotImplementedError):
        check_outputs(run, {"serve": {"max_batch": ROWS},
                            "correct": {"max_logit_gap": 0.5}}, [],
                      control=False)


# ---------------------------------------------------------------------------
# a hybrid program through the harness, with the test-only stand-in
# ---------------------------------------------------------------------------

HYBRID_LIMIT = 1e-3


def _hybrid_config():
    full = dataclasses.asdict(get_config(stand_in.REPO_CONFIG))
    smoke = dataclasses.asdict(reduce_for_smoke(
        get_config(stand_in.REPO_CONFIG)))
    return {"name": "zamba2-smoke", "repo_config": stand_in.REPO_CONFIG,
            "changes": {k: v for k, v in smoke.items() if v != full[k]},
            "model": "hybrid_stand_in", "dtype": "float32",
            "arch": {f.name: smoke[f.name]
                     for f in dataclasses.fields(stand_in.Arch)},
            "serve": {"max_batch": 2, "max_len": 32},
            "correct": {"max_logit_gap": HYBRID_LIMIT}}


def test_a_hybrid_program_runs_through_the_harness(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.models.hybrid_stand_in",
                        stand_in)
    mix = dict(gen.load_mix("chat-open"), prompt_len=16, new_tokens=3,
               clients=2, rate_per_s=10, check_requests=8)
    line = run_cell("dense-chat-open", 2 ** 33 + 3, 2.0, False,
                    platform="cpu", config=_hybrid_config(), mix=mix)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 20
    assert line["checks"]["tokens_short"]["value"] == 0


def test_a_hybrid_token_altered_where_it_is_made_fails_correct():
    conf = _hybrid_config()
    arch = stand_in.Arch.from_dict(conf["arch"])
    cfg = program_config(conf, stand_in, arch)
    seed, n = 2 ** 33 + 4, 2
    params = stand_in.init_weights(stand_in.seed_words(seed), arch,
                                   jnp.float32)
    server = BatchedServer(build_model(cfg), params,
                           ServeConfig(max_len=32, max_batch=n,
                                       max_new_tokens=3))
    toks = np.stack([gen.prompt(seed, 0, k, 16, arch.vocab_size)
                     for k in range(n)])
    out = server.generate_batch({"tokens": toks})
    server.close()
    mix = {"prompt_len": 16, "new_tokens": 3, "check_requests": n}
    run = Run("dense-chat-open", seed, 1.0, 1, arch, stand_in, mix)
    run.t0, run.t_end = 0.0, 1.0
    run.requests = [{"client": 0, "k": k, "due": 0.5, "sent": 0.5,
                     "done": 0.6, "tokens": out[k].copy()} for k in range(n)]
    checks, _ = check_outputs(run, conf, [], control=False)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    run.requests[1]["tokens"][2] = (out[1, 2] + 1) % arch.vocab_size
    checks, _ = check_outputs(run, conf, [], control=False)
    assert checks["max_logit_gap"]["value"] > HYBRID_LIMIT, checks
