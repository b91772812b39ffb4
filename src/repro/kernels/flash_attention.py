"""Flash attention Pallas kernel (causal / sliding-window / bidirectional).

Online-softmax over KV tiles: grid (B*H, Sq/bq, Skv/bk) with the KV index
innermost; running max m, denominator l and the fp32 accumulator persist in
VMEM scratch across the KV tiles of one (head, q-tile).  GQA is handled by
indexing the KV head as h // (H/KV) in the BlockSpec index maps, so no
jnp.repeat materialization.  Tiles are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, causal: bool, window: int, scale: float,
            n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                          # [bq, hd]
    k = k_ref[0]                                          # [bk, hd]
    v = v_ref[0]                                          # [bk, hd]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: [B, Sq, H, hd]; k/v: [B, Skv, KV, hd] -> [B, Sq, H, hd]."""
    if interpret is None:
        interpret = default_interpret()
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    rep = h // kvh
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bk = min(block_k, skv)
    while skv % bk:
        bk //= 2
    n_k = skv // bk
    scale = hd ** -0.5

    # [B, S, H, hd] -> [B*H, S, hd] layout via transpose
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)

    def kv_index(bh, qi, ki):
        # GQA: flat query row bh = b*H + head -> kv row b*KV + head // rep
        return ((bh // h) * kvh + (bh % h) // rep, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale, n_k=n_k),
        grid=(b * h, sq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
