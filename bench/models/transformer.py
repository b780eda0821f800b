"""Decoder-only transformer, dense or mixture-of-experts: the benchmark's
seeded weights, its plain float32 reference and the lower-precision
control.

Nothing here imports the program under test (``bench/models/__init__.py``
states what a reference module exports).  The weight layout is the
served model's parameter tree (paths such as ``blocks/attn/wq``, stacked
over layers); the harness checks it against the program's own abstract
init before serving, so a change of layout fails loudly instead of
serving other weights than the reference computes with.

Semantics the reference follows (the model as the configuration states
it, not as the program implements it):

- RMSNorm (eps 1e-6, learned scale), grouped-query attention with RoPE
  (half-split rotation, ``theta`` from the configuration), causal mask,
  softmax in float32; SwiGLU feed-forward;
- MoE: softmax router over all experts, top-k by probability (lower
  index first on ties), gates renormalized over the k chosen; tokens are
  taken in row-major order in groups of ``moe_group`` (a single group of
  all tokens when there are fewer); each expert holds
  ``ceil(group * k / experts * capacity_factor)`` slots per group, filled
  in priority order (all first choices in token order, then all second
  choices, ...); a choice that finds its expert full contributes nothing;
- tied or untied output head; logits in float32.
"""
from __future__ import annotations

import functools
import math
import zlib
from dataclasses import dataclass, fields

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
UNIFORM_SPAN = INIT_STD * math.sqrt(12.0)      # width of U(-a, a), std 0.02
NORM_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


@dataclass(frozen=True)
class Arch:
    """The sizes a configuration file states under ``arch``."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    tie_embeddings: bool
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 0.0
    moe_group: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "Arch":
        names = {f.name for f in fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(f"unknown arch keys {sorted(unknown)}")
        return cls(**d)

    @property
    def moe(self) -> bool:
        return self.num_experts > 0


def expected_program(a: Arch, dtype: str) -> dict:
    """The served program's facts this reference stands for: the llama-
    style block this module computes, at the file's widths, in ``dtype``
    throughout."""
    want = {"family": "moe" if a.moe else "dense",
            "num_layers": a.num_layers, "d_model": a.d_model,
            "num_heads": a.num_heads, "num_kv_heads": a.num_kv_heads,
            "head_dim": a.head_dim, "d_ff": a.d_ff,
            "vocab_size": a.vocab_size, "rope_theta": a.rope_theta,
            "tie_embeddings": a.tie_embeddings,
            "num_experts": a.num_experts,
            "num_experts_per_token": a.experts_per_token,
            "mlp_type": "swiglu", "norm_type": "rmsnorm", "qk_norm": False,
            "attn_logit_softcap": 0.0, "dtype": dtype, "param_dtype": dtype}
    if a.moe:
        want.update(moe_capacity_factor=a.capacity_factor,
                    moe_group=a.moe_group)
    return want


def couples_rows(a: Arch) -> bool:
    """Expert capacity is shared by the tokens of a group, which spans
    the rows of a batch: a row's output depends on its batchmates."""
    return a.moe


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def leaf_shapes(a: Arch) -> dict:
    """path -> (shape without the layer axis, stacked over layers?, init)."""
    d, h, k, hd = a.d_model, a.num_heads, a.num_kv_heads, a.head_dim
    out = {"embed/embedding": ((a.vocab_size, d), False, "normal")}
    if not a.tie_embeddings:
        out["embed/lm_head"] = ((d, a.vocab_size), False, "normal")
    out.update({
        "blocks/ln1/scale": ((d,), True, "ones"),
        "blocks/attn/wq": ((d, h, hd), True, "normal"),
        "blocks/attn/wk": ((d, k, hd), True, "normal"),
        "blocks/attn/wv": ((d, k, hd), True, "normal"),
        "blocks/attn/wo": ((h, hd, d), True, "normal"),
        "blocks/ln2/scale": ((d,), True, "ones"),
    })
    if a.moe:
        e, f = a.num_experts, a.d_ff
        out.update({
            "blocks/moe/router": ((d, e), True, "normal"),
            "blocks/moe/we_g": ((e, d, f), True, "normal"),
            "blocks/moe/we_u": ((e, d, f), True, "normal"),
            "blocks/moe/we_d": ((e, f, d), True, "normal"),
        })
    else:
        f = a.d_ff
        out.update({
            "blocks/mlp/wg": ((d, f), True, "normal"),
            "blocks/mlp/wu": ((d, f), True, "normal"),
            "blocks/mlp/wd": ((f, d), True, "normal"),
        })
    out["final_norm/scale"] = ((d,), False, "ones")
    return out


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed up to 64 bits as two uint32 words."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} out of range")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _root_key(words):
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def _leaf(root, path: str, layer, shape, init: str, dtype):
    """One layer's value of one leaf, in the served dtype ``dtype``.

    Uniform with standard deviation :data:`INIT_STD`, made from the
    random bits by exact operations and one rounded multiply, so that the
    value is the same bit for bit whether the leaf is made alone (the
    reference, one layer at a time) or batched over layers (the served
    tree): a transcendental such as the normal's inverse erf may round
    differently in the two programs."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(root, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)   # [1, 2)
    return ((one_two - 1.5) * UNIFORM_SPAN).astype(dtype)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def init_weights(words, a: Arch, dtype=jnp.bfloat16):
    """The whole parameter tree on the device, from the seed, in one call."""
    root = _root_key(words)
    tree: dict = {}
    for path, (shape, stacked, init) in leaf_shapes(a).items():
        if stacked:
            val = jax.vmap(lambda l, p=path, s=shape, i=init:
                           _leaf(root, p, l, s, i, dtype))(
                jnp.arange(a.num_layers))
        else:
            val = _leaf(root, path, 0, shape, init, dtype)
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = val
    return tree


def _layer_weights(root, a: Arch, layer, dtype):
    """One layer's leaves, as served (``dtype``) and then widened to f32."""
    w = {}
    for path, (shape, stacked, init) in leaf_shapes(a).items():
        if stacked:
            w[path.split("/", 1)[1]] = _leaf(root, path, layer, shape, init,
                                              dtype).astype(jnp.float32)
    return w


# ---------------------------------------------------------------------------
# plain reference (float32, HIGHEST) and its control (fp8 operands)
# ---------------------------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 with one scale per tensor (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(eq, x, w, control: bool):
    if control:
        x, w = _q8(x), _q8(w)
    return jnp.einsum(eq, x, w, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def _rope(x, theta: float):
    """x (N, S, heads, hd); positions 0..S-1; rotate first/second halves."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(w, x, a: Arch, control: bool):
    n, s, _ = x.shape
    g = a.num_heads // a.num_kv_heads
    q = _rope(_mm("nsd,dhe->nshe", x, w["attn/wq"], control), a.rope_theta)
    k = _rope(_mm("nsd,dke->nske", x, w["attn/wk"], control), a.rope_theta)
    v = _mm("nsd,dke->nske", x, w["attn/wv"], control)
    k = jnp.repeat(k, g, axis=2)          # query head h reads kv head h // g
    v = jnp.repeat(v, g, axis=2)
    sc = _mm("nshe,nthe->nhst", q, k, control) / math.sqrt(a.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = _mm("nhst,nthe->nshe", p, v, control)
    return _mm("nshe,hed->nsd", o, w["attn/wo"], control)


def _swiglu(x, wg, wu, wd, eq_in, eq_out, control):
    h = jax.nn.silu(_mm(eq_in, x, wg, control)) * _mm(eq_in, x, wu, control)
    return _mm(eq_out, h, wd, control)


def _moe(w, x, valid, a: Arch, group: int, control: bool):
    """Capacity-limited top-k experts over row-major token groups."""
    n, s, d = x.shape
    e, k = a.num_experts, a.experts_per_token
    t = n * s
    ng = -(-t // group)
    cap = max(math.ceil(group * k / e * a.capacity_factor), 1)
    xt = jnp.pad(x.reshape(t, d), ((0, ng * group - t), (0, 0)))
    xt = xt.reshape(ng, group, d)
    ok = jnp.pad(valid.reshape(t), (0, ng * group - t)).reshape(ng, group)
    probs = jax.nn.softmax(_mm("gtd,de->gte", xt, w["moe/router"], control),
                           -1)
    gate, idx = jax.lax.top_k(probs, k)                   # (g, t, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((ng, e), jnp.int32)
    pos, keep = [], []
    for kk in range(k):                                    # priority order
        m = jax.nn.one_hot(idx[..., kk], e, dtype=jnp.int32) * ok[..., None]
        p = jnp.sum((jnp.cumsum(m, 1) - m + counts[:, None, :]) * m, -1)
        pos.append(p)
        keep.append(ok & (p < cap))
        counts = counts + m.sum(1)
    pos, keep = jnp.stack(pos, -1), jnp.stack(keep, -1)   # (g, t, k)
    # slot table: which token of the group sits in expert e's slot c
    gi = jnp.broadcast_to(jnp.arange(ng)[:, None, None], idx.shape)
    ti = jnp.broadcast_to(jnp.arange(group)[None, :, None], idx.shape)
    slot_e = jnp.where(keep, idx, e)                       # e: dropped
    table = jnp.full((e + 1, ng, cap + 1), group, jnp.int32)
    table = table.at[slot_e, gi, jnp.where(keep, pos, cap)].set(ti)
    table = table[:e, :, :cap]                             # (e, g, cap)
    xpad = jnp.concatenate([xt, jnp.zeros((ng, 1, d), xt.dtype)], 1)
    xin = xpad[jnp.arange(ng)[None, :, None], table]       # (e, g, cap, d)
    out = _swiglu(xin, w["moe/we_g"], w["moe/we_u"], w["moe/we_d"],
                  "egcd,edf->egcf", "egcf,efd->egcd", control)
    picked = out[idx, gi, jnp.minimum(pos, cap - 1)]       # (g, t, k, d)
    y = jnp.sum(jnp.where(keep[..., None], gate[..., None] * picked, 0.0), 2)
    return y.reshape(ng * group, d)[:t].reshape(n, s, d)


@functools.partial(jax.jit, static_argnames=("a", "control", "group",
                                             "dtype"))
def _layer(words, layer, h, valid, a: Arch, control: bool, group: int,
           dtype):
    w = _layer_weights(_root_key(words), a, layer, dtype)
    h = h + _attention(w, _rms(h, w["ln1/scale"]), a, control)
    x = _rms(h, w["ln2/scale"])
    if a.moe:
        return h + _moe(w, x, valid, a, group, control)
    return h + _swiglu(x, w["mlp/wg"], w["mlp/wu"], w["mlp/wd"],
                       "nsd,df->nsf", "nsf,fd->nsd", control)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def _embed(words, tokens, a: Arch, dtype):
    root = _root_key(words)
    emb = _leaf(root, "embed/embedding", 0, (a.vocab_size, a.d_model),
                "normal", dtype).astype(jnp.float32)
    return emb[tokens]


@functools.partial(jax.jit, static_argnames=("a", "control", "dtype"))
def _head(words, h, a: Arch, control: bool, dtype):
    root = _root_key(words)
    h = _rms(h, _leaf(root, "final_norm/scale", 0, (a.d_model,), "ones",
                      jnp.float32))
    if a.tie_embeddings:
        emb = _leaf(root, "embed/embedding", 0, (a.vocab_size, a.d_model),
                    "normal", dtype).astype(jnp.float32)
        return _mm("nsd,vd->nsv", h, emb, control)
    head = _leaf(root, "embed/lm_head", 0, (a.d_model, a.vocab_size),
                 "normal", dtype).astype(jnp.float32)
    return _mm("nsd,dv->nsv", h, head, control)


def logits(a: Arch, seed: int, tokens: np.ndarray, last: int, *,
           rows: int | None = None, control: bool = False,
           dtype=jnp.bfloat16) -> np.ndarray:
    """Float32 logits ``(N, last, V)`` at the last ``last`` positions of
    each row of ``tokens`` (N, S), one layer at a time.

    ``rows``: only the first ``rows`` rows are real; the rest pad the
    array to a fixed shape and take no expert capacity.  For MoE the rows
    are one served batch, in its row order, since capacity makes a row's
    output depend on its batchmates.  ``control`` rounds every matmul
    operand to float8 e4m3 (one scale per tensor).
    """
    tokens = np.asarray(tokens, np.int32)
    n, s = tokens.shape
    rows = n if rows is None else rows
    words = seed_words(seed)
    valid = np.zeros((n, s), bool)
    valid[:rows] = True
    group = min(a.moe_group, rows * s) if a.moe else 0
    h = _embed(words, tokens, a, dtype)
    for layer in range(a.num_layers):
        h = _layer(words, np.int32(layer), h, valid, a, control, group,
                   dtype)
    return np.asarray(_head(words, h[:, s - last:], a, control, dtype))


def param_count(a: Arch) -> int:
    """Every parameter of the tree (embedding, head, all experts)."""
    n = 0
    for shape, stacked, _ in leaf_shapes(a).values():
        n += math.prod(shape) * (a.num_layers if stacked else 1)
    return n
