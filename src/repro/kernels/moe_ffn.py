"""Grouped expert FFN Pallas kernel — the MoE compute hot spot (paper Fig. 2:
FFN follows the dispatch a2a; packing multiple experts per device makes this
a *grouped* GEMM, which XLA handles poorly as separate dots).

TPU mapping: grid (E, T/bt, F/bf).  Per step the MXU sees
[bt, D] @ [D, bf] -> act -> [bt, bf] @ [bf, D], accumulating the second
product over the F tiles into the fp32 output block (revisited across the
innermost grid dim).  Ragged T/F extents are padded up to the tile (zeros
flow through as zeros) instead of shrinking the tile below MXU alignment;
VMEM footprint = x(bt*D) + wi/wu/wo tiles (D*bf each) + out(bt*D) fp32.

``grouped_matmul`` is the same tiling discipline as a bare grouped GEMM —
the building block the custom-VJP backward (kernels/ops.py) uses to express
dgrad/wgrad, so fwd and bwd share MXU shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANE, block_and_pad, default_interpret


def _kernel(x_ref, wi_ref, wu_ref, wo_ref, o_ref, *, ffn_type: str):
    f_idx = pl.program_id(2)

    @pl.when(f_idx == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                                   # [bt, D]
    h = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)
    if ffn_type == "swiglu":
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = jax.nn.silu(h) * u
    else:
        h = jax.nn.gelu(h)
    o_ref[0] += jnp.dot(h.astype(x.dtype), wo_ref[0],
                        preferred_element_type=jnp.float32)


def grouped_ffn(x, wi, wu, wo, *, ffn_type: str = "swiglu",
                block_t: int = 256, block_f: int = 512,
                interpret: bool | None = None):
    """x: [E, T, D]; wi/wu: [E, D, F]; wo: [E, F, D] -> [E, T, D].

    ``wu`` may be None for gelu FFNs: the kernel never reads the up
    projection on that path, so ``wi`` is passed again as a zero-cost
    layout-compatible alias (no zeros tensor is materialized).
    """
    if interpret is None:
        interpret = default_interpret()
    e, t, d = x.shape
    f = wi.shape[-1]
    if wu is None:
        if ffn_type == "swiglu":
            raise ValueError("swiglu FFN requires the up projection wu")
        wu = wi
    bt, t_pad = block_and_pad(t, block_t)
    bf, f_pad = block_and_pad(f, block_f, sub=LANE)   # F is a lane dim in wi
    if t_pad != t:
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    if f_pad != f:
        # zero hidden units: h==0 there, gelu(0)=0 and silu(0)*0=0, and the
        # matching wo rows are zero — padded F contributes exactly nothing
        wi = jnp.pad(wi, ((0, 0), (0, 0), (0, f_pad - f)))
        wu = jnp.pad(wu, ((0, 0), (0, 0), (0, f_pad - f)))
        wo = jnp.pad(wo, ((0, 0), (0, f_pad - f), (0, 0)))
    grid = (e, t_pad // bt, f_pad // bf)
    out = pl.pallas_call(
        functools.partial(_kernel, ffn_type=ffn_type),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda e_, t_, f_: (e_, t_, 0)),
            pl.BlockSpec((1, d, bf), lambda e_, t_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, d, bf), lambda e_, t_, f_: (e_, 0, f_)),
            pl.BlockSpec((1, bf, d), lambda e_, t_, f_: (e_, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda e_, t_, f_: (e_, t_, 0)),
        out_shape=jax.ShapeDtypeStruct((e, t_pad, d), jnp.float32),
        name="grouped_ffn",
        interpret=interpret,
    )(x, wi, wu, wo)
    return out[:, :t].astype(x.dtype)


def _mm_kernel(a_ref, b_ref, o_ref):
    # K is the innermost grid dim: the output block is revisited across K
    # tiles, zero-initialized on the first visit and accumulated in fp32.
    # Padded K rows/cols are zeros, so they add exactly 0.0 — bitwise equal
    # to the single-pass product.
    @pl.when(pl.program_id(3) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[0] += jnp.dot(a_ref[0], b_ref[0],
                        preferred_element_type=jnp.float32)


def grouped_matmul(a, b, *, block_m: int = 256, block_n: int = 512,
                   block_k: int = 512, interpret: bool | None = None):
    """Grouped GEMM: a [E, M, K] @ b [E, K, N] -> [E, M, N] in fp32.

    The dgrad/wgrad primitive of the grouped-FFN backward: every gradient
    of ``grouped_ffn`` is one of these per expert row, tiled exactly like
    the forward.  All three GEMM dims are blocked — K streams as the
    innermost grid axis accumulating into the revisited fp32 output block,
    so paper-width contractions (e.g. wgrad's K == T) no longer pin a
    full-K operand pair in VMEM.
    """
    if interpret is None:
        interpret = default_interpret()
    e, m, k = a.shape
    n = b.shape[-1]
    bm, m_pad = block_and_pad(m, block_m)
    bn, n_pad = block_and_pad(n, block_n, sub=LANE)   # N is the lane dim
    # K is a's lane dim AND b's sublane dim -> LANE-multiple tiles serve both
    bk, k_pad = block_and_pad(k, block_k, sub=LANE)
    if m_pad != m:
        a = jnp.pad(a, ((0, 0), (0, m_pad - m), (0, 0)))
    if k_pad != k:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, k_pad - k)))
        b = jnp.pad(b, ((0, 0), (0, k_pad - k), (0, 0)))
    if n_pad != n:
        b = jnp.pad(b, ((0, 0), (0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        _mm_kernel,
        grid=(e, m_pad // bm, n_pad // bn, k_pad // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e_, m_, n_, k_: (e_, m_, k_)),
            pl.BlockSpec((1, bk, bn), lambda e_, m_, n_, k_: (e_, k_, n_)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda e_, m_, n_, k_: (e_, m_, n_)),
        out_shape=jax.ShapeDtypeStruct((e, m_pad, n_pad), jnp.float32),
        name="grouped_matmul",
        interpret=interpret,
    )(a, b)
    return out[:, :m, :n]
