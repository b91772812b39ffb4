"""Fused router kernel: (optional router matmul) + softmax + top-k (k<=2) +
renormalized gate weights in one VMEM pass over token tiles (the gating
network of paper §2.1 — it sits on the critical path before every dispatch
a2a, so fusing removes two HBM round-trips of the [T, E] probability matrix
and, with the router folded in, the [T, E] logits round-trip as well).

Grid: (T/bt,).  Block: logits (or x [bt, D] + resident router [D, E])
in VMEM; outputs are the top-k ids/weights + full probs (the popularity
estimator consumes probs).  Ragged T pads up to the tile; padded rows are
sliced off by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import (LANE, SUBLANE, block_and_pad,
                                  default_interpret, lane_column, pad_to)


def _softmax_topk(logits, idx_ref, w_ref, probs_ref, k: int):
    x = logits.astype(jnp.float32)                     # [bt, E]
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    probs = ex / jnp.sum(ex, axis=-1, keepdims=True)
    probs_ref[...] = probs

    iota = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    p = probs
    ws, ids = [], []
    for _ in range(k):
        top = jnp.max(p, axis=-1)
        arg = jnp.argmax(p, axis=-1).astype(jnp.int32)
        ws.append(top)
        ids.append(arg)
        p = jnp.where(iota == arg[:, None], -1.0, p)
    w = jnp.stack(ws, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    idx_ref[...] = jnp.stack(ids, axis=-1)
    w_ref[...] = w


def _kernel(logits_ref, idx_ref, w_ref, probs_ref, *, k: int):
    _softmax_topk(logits_ref[...], idx_ref, w_ref, probs_ref, k)


def _fused_kernel(x_ref, router_ref, idx_ref, w_ref, probs_ref, *, k: int):
    x = x_ref[...]                                     # [bt, D]
    # f32 logits, unrounded, as ref.router_logits gives the XLA
    # path: both backends rank experts on the same values
    logits = jnp.dot(x, router_ref[...],
                     preferred_element_type=jnp.float32)
    _softmax_topk(logits, idx_ref, w_ref, probs_ref, k)


def topk_gating_fused(logits_or_x, k: int = 2, *, router=None,
                      block_t: int = 1024, interpret: bool | None = None):
    """Without ``router``: logits [T, E] -> (idx [T,k] i32, w [T,k] f32,
    probs [T,E] f32).  With ``router`` [D, E]: the first argument is the
    token block x [T, D] and the router matmul is folded into the kernel.
    """
    if interpret is None:
        interpret = default_interpret()
    t = logits_or_x.shape[0]
    e = router.shape[-1] if router is not None else logits_or_x.shape[-1]
    bt, t_pad = block_and_pad(t, block_t)
    x = logits_or_x
    if t_pad != t:
        x = jnp.pad(x, ((0, t_pad - t), (0, 0)))
    if router is None:
        kern = functools.partial(_kernel, k=k)
        in_specs = [pl.BlockSpec((bt, e), lambda i: (i, 0))]
        args = (x,)
    else:
        kern = functools.partial(_fused_kernel, k=k)
        d = logits_or_x.shape[-1]
        in_specs = [pl.BlockSpec((bt, d), lambda i: (i, 0)),
                    pl.BlockSpec((d, e), lambda i: (0, 0))]
        args = (x, router)
    idx, w, probs = pl.pallas_call(
        kern,
        grid=(t_pad // bt,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, e), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t_pad, k), jnp.int32),
            jax.ShapeDtypeStruct((t_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, e), jnp.float32),
        ),
        name="topk_gating_fused",
        interpret=interpret,
    )(*args)
    return idx[:t], w[:t], probs[:t]


def _pos_kernel(idx_ref, pos_ref, cnt_ref, *, k: int, e_pad: int):
    # Two sweeps over the token tiles (grid dim 0 = phase).  Phase 0 counts
    # each choice's assignments per expert into counter rows [0, k); phase 1
    # ranks every (token, choice), carrying running counts in rows [k, 2k),
    # with choice c offset by the totals of choices < c — all first choices
    # outrank all second choices (GShard priority).  The counter lives in
    # the revisited second output block (CONST index map -> persistent
    # across grid steps).  The position block's index map is (i * phase),
    # so phase 0 never moves it off block 0 and no unwritten block is
    # stored.  Padded rows carry expert id -1: an all-zero one-hot.
    ph = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((ph == 0) & (i == 0))
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    idx = idx_ref[...]                                  # [bt, k]
    bt = idx.shape[0]
    e_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, e_pad), 1)
    onehots = [(lane_column(idx, c) == e_iota).astype(jnp.float32)
               for c in range(k)]                       # k x [bt, e_pad]

    @pl.when(ph == 0)
    def _():
        for c in range(k):
            cnt_ref[c:c + 1, :] += jnp.sum(onehots[c], axis=0,
                                           keepdims=True).astype(jnp.int32)

    @pl.when(ph == 1)
    def _():
        # rank within the tile = strictly-lower-triangular 0/1 matmul on the
        # MXU (bf16 operands, f32 accumulation: exact for counts < 2**24)
        r_io = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
        c_io = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
        tri = (r_io > c_io).astype(jnp.bfloat16)
        lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
        out = jnp.zeros(idx.shape, jnp.int32)
        offset = jnp.zeros((1, e_pad), jnp.int32)
        for c in range(k):
            oh = onehots[c]
            rank = jnp.dot(tri, oh.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            base = (cnt_ref[k + c:k + c + 1, :] + offset).astype(jnp.float32)
            pos = jnp.sum((rank + base) * oh, axis=1, keepdims=True)
            out = jnp.where(lane == c, pos.astype(jnp.int32), out)
            cnt_ref[k + c:k + c + 1, :] += jnp.sum(
                oh, axis=0, keepdims=True).astype(jnp.int32)
            offset = offset + cnt_ref[c:c + 1, :]
        pos_ref[...] = out


def topk_positions(expert_idx, n_experts: int, *, block_t: int = 512,
                   interpret: bool | None = None):
    """GShard priority positions, fused: expert_idx [T, k] int32 (-1 for
    masked rows) -> position [T, k] int32, the choice-major rank of each
    (token, choice) within its expert — choice 0 of every token outranks
    choice 1 of any token, exactly the one-hot cumsum in
    ``core.gating.gating_from_topk``, without ever materializing the
    [T, k, E] one-hot in HBM.

    Grid (2, T/bt): a counting sweep, then a ranking sweep, over [bt, k]
    token tiles (the block's last dim is the full k); a [8, E] counter
    block is revisited across all grid steps.
    """
    if interpret is None:
        interpret = default_interpret()
    t, k = expert_idx.shape
    if 2 * k > SUBLANE:
        raise ValueError(f"topk_positions supports k <= {SUBLANE // 2}, "
                         f"got {k}")
    bt, t_pad = block_and_pad(t, block_t)
    e_pad = pad_to(max(int(n_experts), 1), LANE)
    if t_pad != t:
        expert_idx = jnp.pad(expert_idx, ((0, t_pad - t), (0, 0)),
                             constant_values=-1)
    pos, _ = pl.pallas_call(
        functools.partial(_pos_kernel, k=k, e_pad=e_pad),
        grid=(2, t_pad // bt),
        in_specs=[pl.BlockSpec((bt, k), lambda ph, i: (i, 0))],
        out_specs=(
            pl.BlockSpec((bt, k), lambda ph, i: (i * ph, 0)),
            pl.BlockSpec((SUBLANE, e_pad), lambda ph, i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t_pad, k), jnp.int32),
            jax.ShapeDtypeStruct((SUBLANE, e_pad), jnp.int32),
        ),
        name="topk_positions",
        interpret=interpret,
    )(expert_idx.astype(jnp.int32))
    return pos[:t]
