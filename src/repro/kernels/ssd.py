"""Mamba2 SSD chunk-scan Pallas kernel (zamba2's backbone hot loop).

Grid (B*H, T/Q) with the chunk index innermost; the [P, N] SSM state
persists in VMEM scratch.  The intra-chunk part is the matmul form
(L-masked C·B^T decay matrix against the chunk inputs — MXU work), the
cross-chunk part applies the carried state; both write one output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import default_interpret


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref, h_scr, *,
            q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)                  # [Q, P]
    dt = jax.nn.softplus(dt_ref[0, 0].astype(jnp.float32))   # [Q]
    a = -jnp.exp(a_ref[0, 0, 0].astype(jnp.float32))  # scalar
    bmat = b_ref[0].astype(jnp.float32)               # [Q, N]
    cmat = c_ref[0].astype(jnp.float32)               # [Q, N]
    d = d_ref[0, 0, 0].astype(jnp.float32)            # scalar

    la = dt * a                                       # [Q] log-decay/step
    lcum = jnp.cumsum(la)                             # [Q]
    xd = x * dt[:, None]

    # intra-chunk: M[t,s] = (c_t.b_s) exp(Lt - Ls) for s<=t
    rel = lcum[:, None] - lcum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    m = jnp.where(tri, jnp.exp(rel), 0.0)
    cb = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32)
    y = jnp.dot(cb * m, xd, preferred_element_type=jnp.float32)

    # cross-chunk: y += exp(Lt) * (C_t . h_prev)
    h = h_scr[...]                                    # [P, N]
    y += jnp.exp(lcum)[:, None] * jnp.dot(cmat, h.T,
                                          preferred_element_type=jnp.float32)

    o_ref[0] = (y + x * d).astype(o_ref.dtype)

    # state update: h' = exp(L_Q) h + sum_s exp(L_Q - L_s) xd_s b_s^T
    dec_end = jnp.exp(lcum[-1] - lcum)                # [Q]
    s_chunk = jnp.dot((xd * dec_end[:, None]).T, bmat,
                      preferred_element_type=jnp.float32)   # [P, N]
    h_scr[...] = jnp.exp(lcum[-1]) * h + s_chunk


def ssd_scan(x, dt, a_log, b, c, d_skip, *, chunk: int = 128,
             interpret: bool | None = None):
    """x: [B,T,H,P]; dt: [B,T,H]; a_log,d_skip: [H]; b,c: [B,T,N]
    -> y [B,T,H,P] f32."""
    if interpret is None:
        interpret = default_interpret()
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    while t % q:
        q //= 2

    xh = x.transpose(0, 2, 1, 3).reshape(bsz * h, t, p)
    # per-head rows get a unit middle dim so every block's last two dims
    # are tile-aligned or at full extent
    dth = dt.transpose(0, 2, 1).reshape(bsz * h, 1, t)
    bh = jnp.broadcast_to(b[:, None], (bsz, h, t, n)).reshape(bsz * h, t, n)
    ch = jnp.broadcast_to(c[:, None], (bsz, h, t, n)).reshape(bsz * h, t, n)
    ah = jnp.broadcast_to(a_log[None], (bsz, h)).reshape(bsz * h, 1, 1)
    dh = jnp.broadcast_to(d_skip[None], (bsz, h)).reshape(bsz * h, 1, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=(bsz * h, t // q),
        in_specs=[
            pl.BlockSpec((1, q, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1, q), lambda g, ci: (g, 0, ci)),
            pl.BlockSpec((1, 1, 1), lambda g, ci: (g, 0, 0)),
            pl.BlockSpec((1, q, n), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, q, n), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda g, ci: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, p), lambda g, ci: (g, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, t, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xh, dth, ah, bh, ch, dh)
    return out.reshape(bsz, h, t, p).transpose(0, 2, 1, 3)
