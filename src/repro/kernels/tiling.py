"""Shared tiling policy for the Pallas kernels.

All kernels block their token/feature dims for the MXU; extents that do not
tile evenly are PADDED up to the chosen block rather than silently shrinking
the block below hardware alignment (a 1-wide tile turns the MXU into a
scalar unit).  Padding rows/columns are zeros, which every kernel here maps
to zeros (matmul, softmax-with-slice, masked gather), and the caller slices
the pad back off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128     # MXU/VPU lane width — ideal multiple for blocked dims
SUBLANE = 8    # f32 sublane height — minimum alignment for small extents

# ~16 MB of VMEM per TPU core (v4/v5 class) — the budget every kernel's
# static per-grid-step footprint is checked against (repro.analysis pass 1,
# and the call-time asserts in kernels/dispatch.py).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# minimum sublane height by dtype width (pallas guide: f32 (8,128),
# bf16 (16,128), int8/fp8 (32,128))
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}


def sublane_for(dtype) -> int:
    """Minimum second-to-last-dim tile height for ``dtype``."""
    return _SUBLANE_BY_ITEMSIZE.get(np.dtype(dtype).itemsize, SUBLANE)


def block_bytes(shape, dtype) -> int:
    """Bytes of one VMEM block of ``shape`` x ``dtype``."""
    n = 1
    for s in shape:
        n *= int(s)
    return n * np.dtype(dtype).itemsize


def default_interpret() -> bool:
    """Pallas kernels compile natively on a TPU backend; on any other
    backend (the CPU the tests run on) the bodies run in interpret mode."""
    return jax.default_backend() != "tpu"


def lane_column(a, c: int):
    """Column ``c`` of a narrow [bt, k] int block, as [bt, 1], inside a
    kernel: a lane select and an f32 lane reduction (exact for
    |values| < 2**24), which Mosaic lowers for any k, unlike a width-1
    lane slice."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    sel = jnp.where(lane == c, a.astype(jnp.float32), 0.0)
    return jnp.sum(sel, axis=1, keepdims=True).astype(a.dtype)


def pad_to(n: int, b: int) -> int:
    return -(-n // b) * b


def block_and_pad(n: int, block: int, align: int = LANE,
                  sub: int = SUBLANE) -> tuple[int, int]:
    """Choose a tile size for a dim of extent ``n`` under requested
    ``block``.  Returns ``(tile, padded_extent)``.

    * ``n`` divisible by a ``sub``-aligned ``min(block, n)`` -> keep the
      requested block and no padding (the fast path — production shapes
      are pre-aligned).
    * ``n <= align`` -> one ``sub``-aligned tile covering the whole
      (padded) extent.
    * otherwise -> the multiple of ``align`` (<= block, floored at
      ``align``) that minimizes the padded extent, ties to the larger
      tile.

    The tile is always a multiple of ``sub`` — ragged extents cost
    padding, never alignment.  Pass ``sub=LANE`` for a lane (last) block
    dim, where the hardware unit is 128 rather than the f32 sublane 8; an
    explicitly-requested unaligned ``block`` is bumped to the aligned
    choice rather than honored.
    """
    b = min(block, n)
    if b > 0 and n % b == 0 and b % sub == 0:
        return b, n
    if n <= align:
        b = pad_to(n, sub)
        return b, b
    best = align
    for cand in range(align, max(block, align) + 1, align):
        if pad_to(n, cand) <= pad_to(n, best):
            best = cand
    return best, pad_to(n, best)
