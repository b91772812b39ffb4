"""GQA/MQA/MHA attention with qk-norm, QKV bias, sliding window, RoPE;
train/prefill (full-sequence) and decode (KV cache) paths.

Tensor-parallel over `model` (heads split), FSDP over the dp axes (weight
dims), expressed as weight/activation sharding constraints; the prefill path
can optionally call the Pallas flash kernel (on TPU) — CPU uses the einsum
reference, which is also the kernel oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import (dense_init, rms_norm, rope, constrain,
                                 dp_axes, tp_axes)


class AttnParams(NamedTuple):
    wq: jax.Array                 # [d, H*hd]
    wk: jax.Array                 # [d, KV*hd]
    wv: jax.Array                 # [d, KV*hd]
    wo: jax.Array                 # [H*hd, d]
    bq: Optional[jax.Array]       # [H*hd] or None
    bk: Optional[jax.Array]
    bv: Optional[jax.Array]
    q_norm: Optional[jax.Array]   # [hd] qk_norm scales
    k_norm: Optional[jax.Array]


class KVCache(NamedTuple):
    k: jax.Array                  # [B, S_max, KV, hd]
    v: jax.Array                  # [B, S_max, KV, hd]


def init_attn_params(key, d_model, n_heads, n_kv_heads, head_dim, *,
                     qkv_bias=False, qk_norm=False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    hq, hkv = n_heads * head_dim, n_kv_heads * head_dim
    z = lambda n: jnp.zeros((n,), dtype)
    return AttnParams(
        wq=dense_init(ks[0], (d_model, hq), dtype=dtype),
        wk=dense_init(ks[1], (d_model, hkv), dtype=dtype),
        wv=dense_init(ks[2], (d_model, hkv), dtype=dtype),
        wo=dense_init(ks[3], (hq, d_model), dtype=dtype),
        bq=z(hq) if qkv_bias else None,
        bk=z(hkv) if qkv_bias else None,
        bv=z(hkv) if qkv_bias else None,
        q_norm=jnp.ones((head_dim,), dtype) if qk_norm else None,
        k_norm=jnp.ones((head_dim,), dtype) if qk_norm else None,
    )


def _project_qkv(p: AttnParams, x, n_heads, n_kv_heads, head_dim, positions,
                 rope_theta, norm_eps):
    b, s, _ = x.shape
    q = x @ p.wq + (p.bq if p.bq is not None else 0.0)
    k = x @ p.wk + (p.bk if p.bk is not None else 0.0)
    v = x @ p.wv + (p.bv if p.bv is not None else 0.0)
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv_heads, head_dim)
    v = v.reshape(b, s, n_kv_heads, head_dim)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, norm_eps)
        k = rms_norm(k, p.k_norm, norm_eps)
    if rope_theta > 0:
        q, k = rope(q, k, positions, rope_theta)
    return q, k, v


def _sdpa(q, k, v, *, causal, window, q_offset=0):
    """Reference attention.  q: [B,Sq,H,hd], k/v: [B,Sk,KV,hd]."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    k = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    v = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / (hd ** 0.5)
    sk = k.shape[1]
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


BLOCKWISE_THRESHOLD = 2048   # S beyond which the O(S^2)-memory path is unsafe
BLOCK_Q = 1024


def _sdpa_blockwise(q, k, v, *, causal, window, block_q=BLOCK_Q):
    """Memory-bounded attention: scan over query blocks (logits peak is
    [B,H,block_q,S] instead of [B,H,S,S]); online softmax is unnecessary when
    K stays whole per block, so plain softmax per Q-block is exact.  This is
    also the oracle for the Pallas flash kernel."""
    b, s, h, hd = q.shape
    bq = min(block_q, s)
    while s % bq:
        bq -= 1
    nq = s // bq
    kv = k.shape[2]
    rep = h // kv
    kk = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vv = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    qs = q.reshape(b, nq, bq, h, hd).transpose(1, 0, 2, 3, 4)

    kpos = jnp.arange(s)

    def step(carry, inp):
        qb, i = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, kk).astype(jnp.float32)
        logits = logits / (hd ** 0.5)
        qpos = i * bq + jnp.arange(bq)
        mask = jnp.ones((bq, s), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(qb.dtype)
        ob = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        return carry, ob

    _, os_ = jax.lax.scan(step, 0, (qs, jnp.arange(nq)))
    return os_.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def attention(mesh, p: AttnParams, x, cfg, positions=None):
    """Full-sequence path (train / prefill).  x: [B, S, d]."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd, positions,
                           cfg.rope_theta, cfg.norm_eps)
    dp = dp_axes(mesh)
    tp = tp_axes(mesh)
    q = constrain(q, mesh, P(dp, None, tp, None))
    k = constrain(k, mesh, P(dp, None, tp if cfg.n_kv_heads > 1 else None, None))
    if s > BLOCKWISE_THRESHOLD:
        o = _sdpa_blockwise(q, k, v, causal=cfg.causal,
                            window=cfg.sliding_window)
    else:
        o = _sdpa(q, k, v, causal=cfg.causal, window=cfg.sliding_window)
    o = o.reshape(b, s, cfg.n_heads * hd)
    y = o @ p.wo
    return constrain(y, mesh, P(dp, None, None)), KVCache(k, v)


def decode_attention(mesh, p: AttnParams, x, cache: KVCache, pos, cfg):
    """One-token decode.  x: [B, 1, d]; pos: [B] absolute position; the cache
    holds S_max slots (ring-buffered when sliding window is on)."""
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd,
                                   pos[:, None], cfg.rope_theta, cfg.norm_eps)
    slot, _ = _decode_slot(pos, cache.k.shape[1], cfg)
    k = jax.vmap(lambda c, kn, i: jax.lax.dynamic_update_slice(
        c, kn, (i, 0, 0)))(cache.k, k_new, slot)
    v = jax.vmap(lambda c, vn, i: jax.lax.dynamic_update_slice(
        c, vn, (i, 0, 0)))(cache.v, v_new, slot)
    y = _decode_attend(q, k, v, pos, slot, cfg, x.dtype)
    return y @ p.wo, KVCache(k, v)


def decode_attention_rows(p: AttnParams, x, cache: KVCache, pos, cfg):
    """One-token decode that leaves ``cache`` as it is: the token's K/V
    take its slot by a select inside the attention, and only the new rows
    come back.  x: [B, 1, d]; pos: [B].  Returns (y [B, 1, d],
    k_new [B, KV, hd], v_new [B, KV, hd]); ``write_decode_rows`` puts the
    rows into a copy of the cache.  Same numbers as ``decode_attention``."""
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads, hd,
                                   pos[:, None], cfg.rope_theta, cfg.norm_eps)
    slot, hit = _decode_slot(pos, cache.k.shape[1], cfg)
    k = jnp.where(hit, k_new, cache.k)
    v = jnp.where(hit, v_new, cache.v)
    y = _decode_attend(q, k, v, pos, slot, cfg, x.dtype)
    return y @ p.wo, k_new[:, 0], v_new[:, 0]


def write_decode_rows(cache_k, rows, pos, cfg):
    """Write one decode step's rows into a copy of a stacked cache.
    cache_k: [..., B, S_cap, KV, hd]; rows: [..., B, KV, hd]; pos: [B]."""
    _, hit = _decode_slot(pos, cache_k.shape[-3], cfg)
    return jnp.where(hit, rows[..., None, :, :], cache_k)


def _decode_slot(pos, s_max, cfg):
    """The cache slot [B] a decode token at ``pos`` writes, and its mask
    [B, S_cap, 1, 1] over the cache's time axis."""
    slot = pos % s_max if cfg.sliding_window else jnp.minimum(pos, s_max - 1)
    return slot, (jnp.arange(s_max)[None, :] == slot[:, None])[:, :, None, None]


def _decode_attend(q, k, v, pos, slot, cfg, dtype):
    """Attention of the decode queries q [B, 1, H, hd] over the cache k/v
    [B, S_cap, KV, hd] that already holds this token; returns
    [B, 1, H*hd]."""
    b, s_max = k.shape[0], k.shape[1]
    hd = q.shape[-1]
    kv = cfg.n_kv_heads
    rep = cfg.n_heads // kv
    kk = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vv = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) / hd ** 0.5
    kpos = jnp.arange(s_max)[None, :]
    if cfg.sliding_window:
        # ring buffer: valid slots are the last min(pos+1, window) writes
        age = (slot[:, None] - kpos) % s_max
        valid = (age < jnp.minimum(pos[:, None] + 1, s_max))
    else:
        valid = kpos <= pos[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv).reshape(
        b, 1, cfg.n_heads * hd)


def init_kv_cache(cfg, batch, seq_len, dtype=jnp.bfloat16) -> KVCache:
    s = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    hd = cfg.resolved_head_dim
    shape = (batch, s, cfg.n_kv_heads, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
