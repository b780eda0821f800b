"""A test-only module with the reference module's interface, for a
smoke-sized hybrid program (Mamba-2 layers with a shared attention
block, the repo's ``zamba2-2.7b`` through ``reduce_for_smoke``).

It is not a reference of Zamba2, and not a reference at all: its
weights and its logits come from the program's own model, so its
comparison can show nothing about the model.  It proves the harness's
plumbing for a family that is neither dense nor mixture-of-experts: the
program check by the facts a module names, the layout check, and the
per-request, teacher-forced comparison at several tokens out.  The
tests register it as ``bench.models.hybrid_stand_in``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import build_model

REPO_CONFIG = "zamba2-2.7b"


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes ``reduce_for_smoke`` sets, under the program's names."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    ssm_state: int
    ssm_head_dim: int
    ssm_chunk: int
    shared_attn_every: int

    @classmethod
    def from_dict(cls, d: dict) -> "Arch":
        return cls(**d)


def expected_program(a: Arch, dtype: str) -> dict:
    return {"family": "hybrid", **dataclasses.asdict(a), "dtype": dtype,
            "param_dtype": dtype}


def couples_rows(a: Arch) -> bool:
    return False


def seed_words(seed: int) -> np.ndarray:
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


@functools.lru_cache(maxsize=None)
def _model(a: Arch, dtype: str):
    cfg = dataclasses.replace(get_config(REPO_CONFIG), **dataclasses.asdict(a),
                              dtype=dtype, param_dtype=dtype, remat=False)
    return build_model(cfg)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def init_weights(words, a: Arch, dtype=jnp.float32):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return _model(a, jnp.dtype(dtype).name).init(key)


def param_count(a: Arch) -> int:
    shapes = jax.eval_shape(init_weights, seed_words(0), a)
    return sum(x.size for x in jax.tree.leaves(shapes))


def logits(a: Arch, seed: int, tokens: np.ndarray, last: int, *,
           rows: int | None = None, control: bool = False,
           dtype=jnp.float32) -> np.ndarray:
    """The program's own prefill over all but the last ``last - 1``
    tokens, then one decode step per remaining token (float32 weights,
    as the tests serve them)."""
    if control:
        raise ValueError("the stand-in has no control")
    model = _model(a, jnp.dtype(dtype).name)
    params = init_weights(seed_words(seed), a, dtype)
    tokens = jnp.asarray(tokens, jnp.int32)
    s = tokens.shape[1]
    p = s - last + 1
    prefill = jax.jit(model.prefill, static_argnames="max_len")
    out, cache = prefill(params, {"tokens": tokens[:, :p]}, max_len=s)
    steps = [out[:, -1]]
    decode = jax.jit(model.decode_step)
    for j in range(p, s):
        out, cache = decode(params, cache, tokens[:, j:j + 1])
        steps.append(out[:, -1])
    return np.asarray(jnp.stack(steps, 1), np.float32)
