"""Grouped-query attention with RoPE, optional qk-norm, KV caching.

Execution paths (the paper's *offload control* knob, §IV-B, applied to the
attention hot-spot):

- ``direct``    — reference einsum path; scores materialize (small shapes);
- ``blockwise`` — memory-efficient streaming attention (double scan over
  query/key blocks with running log-sum-exp), the pure-XLA analogue of the
  pipelined DMA kernel: bounded working set, automatically selected above a
  size threshold (``BLOCKWISE_THRESHOLD`` score elements);
- ``flash``     — Pallas kernel (``repro.kernels.flash_attention``) with
  explicit VMEM DMA tiling, validated against ``direct`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    apply_rope,
    dense_init,
    ones_init,
    pdtype,
    rms_normalize,
)

NEG_INF = -1e30
# size-threshold (paper Table III "Data Size"): switch to the streaming path
# once the score tensor would exceed this many elements per device.
BLOCKWISE_THRESHOLD = 2 ** 22
Q_BLOCK = 1024
K_BLOCK = 1024


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, d_model: int | None = None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim()
    h, k = cfg.num_heads, cfg.num_kv_heads
    dt = pdtype(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d, h, hd), dt),
        "wk": dense_init(k2, (d, k, hd), dt),
        "wv": dense_init(k3, (d, k, hd), dt),
        "wo": dense_init(k4, (h, hd, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), dt)
        p["k_norm"] = ones_init((hd,), dt)
    return p


def attn_param_count(cfg: ModelConfig, d_model: int | None = None) -> int:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim()
    n = 2 * d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
    if cfg.qk_norm:
        n += 2 * hd
    return n


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def project_qkv(params, x, cfg: ModelConfig, positions=None, rope: bool = True):
    """x: (B, S, D) -> q (B,S,H,hd), k (B,S,K,hd), v (B,S,K,hd)."""
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dke->bske", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dke->bske", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_normalize(q, params["q_norm"])
        k = rms_normalize(k, params["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def project_out(params, o, x_dtype):
    """o: (B, S, H, hd) -> (B, S, D)."""
    return jnp.einsum("bshe,hed->bsd", o, params["wo"].astype(x_dtype))


# ---------------------------------------------------------------------------
# direct (reference) GQA attention
# ---------------------------------------------------------------------------

def _scores(qg, k, cfg: ModelConfig):
    hd = qg.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bskge,btke->bkgst", qg, k).astype(jnp.float32) * scale
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        s = c * jnp.tanh(s / c)
    return s


def attend(q, k, v, cfg: ModelConfig, mask):
    """q: (B,S,H,hd); k/v: (B,T,K,hd); mask broadcastable to (B,K,G,S,T)."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    scores = jnp.where(mask, _scores(qg, k, cfg), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btke->bskge", w, v)
    return o.reshape(b, s, h, hd)


# ---------------------------------------------------------------------------
# blockwise streaming attention (memory-efficient; the inline analogue of
# the pipelined-DMA execution mode: bounded VMEM/registers working set)
# ---------------------------------------------------------------------------

def attend_blockwise(q, k, v, cfg: ModelConfig, *, causal: bool,
                     q_block: int = Q_BLOCK, k_block: int = K_BLOCK):
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    qb = min(q_block, s)
    kb = min(k_block, t)
    nq, nk = s // qb, t // kb
    qg = q.reshape(b, nq, qb, kh, g, hd)
    kc = k.reshape(b, nk, kb, kh, hd)
    vc = v.reshape(b, nk, kb, kh, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    def q_step(_, qi_and_block):
        qi, qblk = qi_and_block          # qblk: (b, qb, kh, g, hd)

        def kv_step(carry, kj_and_chunk):
            m, l, acc = carry
            kj, kchunk, vchunk = kj_and_chunk
            sc = jnp.einsum("bskge,btke->bkgst", qblk, kchunk)
            sc = sc.astype(jnp.float32) * scale
            if cfg.attn_logit_softcap:
                c = cfg.attn_logit_softcap
                sc = c * jnp.tanh(sc / c)
            if causal:
                qpos = qi * qb + jnp.arange(qb)
                kpos = kj * kb + jnp.arange(kb)
                msk = (kpos[None, :] <= qpos[:, None])[None, None, None]
                sc = jnp.where(msk, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgst,btke->bkgse", p.astype(qblk.dtype), vchunk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qb), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qb, hd), qblk.dtype)
        kv_body = jax.checkpoint(kv_step)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None].astype(acc.dtype))        # (b,kh,g,qb,hd)
        out = jnp.moveaxis(out, 3, 1).reshape(b, qb, kh * g, hd)
        return None, out

    _, blocks = jax.lax.scan(
        q_step, None, (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    # blocks: (nq, b, qb, h, hd) -> (b, s, h, hd)
    return jnp.moveaxis(blocks, 0, 1).reshape(b, s, h, hd)


def _use_blockwise(s: int, t: int) -> bool:
    return s > 1 and s * t > BLOCKWISE_THRESHOLD


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def causal_mask(s: int, t: int | None = None, offset: int = 0):
    t = t or s
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    return (kj <= qi)[None, None, None]


def full_mask(s: int, t: int):
    return jnp.ones((1, 1, 1, s, t), bool)


def decode_mask(index, t: int):
    """index: (B,) current position; keys j <= index valid. -> (B,1,1,1,T)."""
    kj = jnp.arange(t)[None, :]
    return (kj <= index[:, None])[:, None, None, None]


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  kv_dtype=None):
    hd = cfg.resolved_head_dim()
    kh = cfg.num_kv_heads
    dt = kv_dtype or jnp.dtype(cfg.dtype)
    shape = (n_layers, batch, max_len, kh, hd)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "index": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# int8 KV quantization (beyond-paper: halves decode cache traffic vs bf16)
# ---------------------------------------------------------------------------

def quantize_kv(x):
    """x (..., hd) -> (int8 values, fp scale per head-vector)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_kv_cache_q8(cfg: ModelConfig, batch: int, max_len: int,
                     n_layers: int):
    hd = cfg.resolved_head_dim()
    kh = cfg.num_kv_heads
    shape = (n_layers, batch, max_len, kh, hd)
    sshape = (n_layers, batch, max_len, kh, 1)
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(sshape, jnp.float32),
        "v_scale": jnp.zeros(sshape, jnp.float32),
        "index": jnp.zeros((batch,), jnp.int32),
    }


def cache_insert_prefill(layer_k, layer_v, k, v):
    lk = jax.lax.dynamic_update_slice(layer_k, k.astype(layer_k.dtype), (0, 0, 0, 0))
    lv = jax.lax.dynamic_update_slice(layer_v, v.astype(layer_v.dtype), (0, 0, 0, 0))
    return lk, lv


def cache_insert_token(layer_k, layer_v, k, v, index):
    """Insert one token's k/v (B,1,K,hd) at per-batch position ``index`` (B,)."""
    def upd(buf, new):
        def one(row_buf, row_new, idx):
            return jax.lax.dynamic_update_slice(
                row_buf, row_new.astype(row_buf.dtype), (idx, 0, 0))
        return jax.vmap(one)(buf, new, index)
    return upd(layer_k, k), upd(layer_v, v)


# ---------------------------------------------------------------------------
# block-level application
# ---------------------------------------------------------------------------

def self_attention(params, x, cfg: ModelConfig, *, positions, causal=True,
                   rope=True):
    s = x.shape[1]
    q, k, v = project_qkv(params, x, cfg, positions, rope=rope)
    if _use_blockwise(s, s):
        o = attend_blockwise(q, k, v, cfg, causal=causal)
    else:
        mask = causal_mask(s) if causal else full_mask(s, s)
        o = attend(q, k, v, cfg, mask)
    return project_out(params, o, x.dtype)


def self_attention_decode(params, x, cfg: ModelConfig, *, layer_k, layer_v,
                          index, rope=True):
    """One-token decode: x (B,1,D); cache layer (B,T,K,hd); index (B,)."""
    positions = index[:, None]                       # (B,1)
    q, k, v = project_qkv(params, x, cfg, positions, rope=rope)
    layer_k, layer_v = cache_insert_token(layer_k, layer_v, k, v, index)
    mask = decode_mask(index, layer_k.shape[1])
    o = attend(q, layer_k.astype(q.dtype), layer_v.astype(q.dtype), cfg, mask)
    return project_out(params, o, x.dtype), layer_k, layer_v


def _merge_new_token(o, l, m, q, k_new, v_new, cfg: ModelConfig):
    """Fold the current token's self-attention term into partial stats.

    o (B,K,G,1,hd) unnormalized; l,m (B,K,G,1); q (B,1,H,hd);
    k_new/v_new (B,1,K,hd).
    """
    b, _, h, hd = q.shape
    kh = k_new.shape[2]
    g = h // kh
    qg = q.reshape(b, 1, kh, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s_new = jnp.einsum("bskge,btke->bkgs", qg, k_new).astype(jnp.float32) * scale
    m2 = jnp.maximum(m, s_new)
    corr = jnp.exp(m - m2)
    w_new = jnp.exp(s_new - m2)
    o2 = o * corr[..., None] + jnp.einsum(
        "bkgs,btke->bkgse", w_new, v_new.astype(jnp.float32))
    l2 = l * corr + w_new
    return o2 / jnp.maximum(l2, 1e-30)[..., None]


def decode_attention_partial(q, layer_k, layer_v, cfg: ModelConfig, index,
                             pos_offset=0):
    """Unnormalized partial attention over a cache segment.

    Returns (o (B,K,G,1,hd) fp32 unnormalized, l (B,K,G,1), m (B,K,G,1)).
    ``pos_offset`` is the global position of the segment's first key.
    """
    b, _, h, hd = q.shape
    t, kh = layer_k.shape[1], layer_k.shape[2]
    g = h // kh
    qg = q.reshape(b, 1, kh, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    s = jnp.einsum("bskge,btke->bkgst", qg, layer_k.astype(q.dtype))
    s = s.astype(jnp.float32) * scale                       # (B,K,G,1,T)
    kpos = pos_offset + jnp.arange(t)
    valid = (kpos[None, :] < index[:, None])                # cached keys only
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgst,btke->bkgse", p,
                   layer_v.astype(jnp.float32))
    return o, l, m


def sp_decode_attention(q, layer_k, layer_v, k_new, v_new, cfg: ModelConfig,
                        index, axis: str = "model", batch_axes=None):
    """Split-KV flash-decode: the cache's sequence dim is sharded over
    ``axis``; each shard computes local partial stats and only the (B,K,G)
    statistics cross the interconnect (psum log-sum-exp merge) — instead of
    all-gathering the cache (the paper's 'move the computation, not the
    bytes' applied to decode)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import api as shard_api

    mesh = shard_api.get_mesh()
    bx = batch_axes if batch_axes else None

    def local(q, lk, lv, index):
        tl = lk.shape[1]
        shard = jax.lax.axis_index(axis)
        o, l, m = decode_attention_partial(q, lk, lv, cfg, index,
                                           pos_offset=shard * tl)
        m_all = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_all)
        l_all = jax.lax.psum(l * corr, axis)
        o_all = jax.lax.psum(o * corr[..., None], axis)
        return o_all, l_all, m_all

    with shard_api.manual_mode():
        o, l, m = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(bx), P(bx, axis, None, None),
                      P(bx, axis, None, None), P(bx)),
            out_specs=(P(bx), P(bx), P(bx)), check_vma=False,
        )(q, layer_k, layer_v, index)
    o = _merge_new_token(o, l, m, q, k_new, v_new, cfg)
    b, _, h, hd = q.shape
    return o.reshape(b, h, 1, hd).transpose(0, 2, 1, 3).astype(q.dtype)


def decode_attention_merged(q, layer_k, layer_v, k_new, v_new,
                            cfg: ModelConfig, index):
    """Single-device equivalent of sp_decode_attention (no cache rewrite:
    attends the stale cache + the new token's k/v)."""
    o, l, m = decode_attention_partial(q, layer_k, layer_v, cfg, index)
    o = _merge_new_token(o, l, m, q, k_new, v_new, cfg)
    b, _, h, hd = q.shape
    return o.reshape(b, h, 1, hd).transpose(0, 2, 1, 3).astype(q.dtype)


def cross_attention(params, x, memory_k, memory_v, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_normalize(q, params["q_norm"])
    s, t = x.shape[1], memory_k.shape[1]
    if _use_blockwise(s, t):
        o = attend_blockwise(q, memory_k, memory_v, cfg, causal=False)
    else:
        o = attend(q, memory_k, memory_v, cfg, full_mask(s, t))
    return project_out(params, o, x.dtype)


def cross_attention_memory(params, enc_out, cfg: ModelConfig):
    k = jnp.einsum("btd,dke->btke", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("btd,dke->btke", enc_out, params["wv"].astype(enc_out.dtype))
    if cfg.qk_norm:
        k = rms_normalize(k, params["k_norm"])
    return k, v
