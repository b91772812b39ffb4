"""RWKV6 chunked-recurrence Pallas kernel (rwkv6-1.6b's time-mix hot loop).

Grid (B*H, T/chunk) with the chunk index innermost; the [hd, hd] wkv state
persists in VMEM scratch across chunks of one head.  Within a chunk the
recurrence runs as an unrolled loop of outer-product updates on VMEM tiles
(hd = 64: every operand is a single VREG-friendly [64, 64] tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import default_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0, 0].astype(jnp.float32)               # [hd]

    def step(t, s):
        r_t = r_ref[0, t].astype(jnp.float32)         # [hd]
        k_t = k_ref[0, t].astype(jnp.float32)
        v_t = v_ref[0, t].astype(jnp.float32)
        w_t = w_ref[0, t].astype(jnp.float32)         # log-decay
        kv = k_t[:, None] * v_t[None, :]              # [hd, hd]
        y = jnp.sum((s + u[:, None] * kv) * r_t[:, None], axis=0)
        o_ref[0, t] = y.astype(o_ref.dtype)
        return jnp.exp(w_t)[:, None] * s + kv

    s = jax.lax.fori_loop(0, chunk, step, s_scr[...])
    s_scr[...] = s


def rwkv6_wkv(r, k, v, w, u, *, chunk: int = 64,
              interpret: bool | None = None):
    """r/k/v/w: [B, T, H, hd] (w = log decay); u: [H, hd] -> y [B,T,H,hd]."""
    if interpret is None:
        interpret = default_interpret()
    b, t, h, hd = r.shape
    c = min(chunk, t)
    while t % c:
        c //= 2
    # layout: [B*H, T, hd]
    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    rr, kk, vv, ww = map(to_bh, (r, k, v, w))
    # [B*H, 1, hd]: a (1, 1, hd) block keeps the last two dims at full extent
    uu = jnp.broadcast_to(u[None], (b, h, hd)).reshape(b * h, 1, hd)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=(b * h, t // c),
        in_specs=[
            pl.BlockSpec((1, c, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, c, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, hd), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, uu)
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
