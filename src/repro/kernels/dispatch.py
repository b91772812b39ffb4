"""Fused dispatch/combine/route Pallas kernels: scatter tokens into
per-expert capacity buffers, gather them back gate-weighted, and map
(token, choice) pairs onto weighted replica rows — one pass each.

Neither side materializes the [T, E, C] one-hot dispatch mask (the einsum
oracle) nor the [T*k, d] broadcast copy of the token block (the jnp scatter
backend).  Instead the host-side caller inverts the metadata-sized
(token -> slot) map into a (slot -> token) int32 index (``invert_slots``,
one O(E*C) scatter of ids, no feature data), and:

  * ``dispatch_rows``  — grid over (output-row tile, source tile); each
    output tile is revisited across the streamed source tiles, gathering the
    rows that live in the current tile as a 0/1 one-hot matmul on the MXU
    (Mosaic has no in-kernel row gather) and accumulating (for finite
    inputs the rows outside the tile contribute exactly 0.0, so the result
    is bitwise the single-pass gather).  An optional per-row scale also
    serves the combine-backward, where the scattered rows are gate-weighted
    cotangents.
  * ``combine_rows``   — grid over (token tile, buffer tile); each token
    tile is revisited across the streamed slot-buffer tiles and reduces its
    k gate-weighted slot rows in fp32.

The one-hot gathers cost O(output rows x source rows x d) MXU work, and
they keep rows apart only for finite inputs: 0 * NaN and 0 * Inf are NaN,
so one non-finite source value at (row, feature c) turns feature c of
EVERY output row into NaN — in serving, every co-batched request's
output, where a true gather (and the XLA path) keeps the other rows
intact.  ``tests/test_kernels.py`` records this behaviour.
  * ``weighted_route`` — grid over token tiles; the per-(expert, replica)
    integer routing weights (cumsum form) and the replica->slot table stay
    VMEM-resident while each tile turns (expert, position) into a flat
    destination row via bin partition — the Lina §5/§6.2 weighted
    zero-migration replica split, fused so dispatch metadata never leaves
    VMEM.

Since this PR no kernel here keeps a T- or R-scaling block resident: the
PR-4 ``untiled-block`` / scale-1 ``vmem-over-budget`` ceilings tracked in
``ANALYSIS_BASELINE.json`` are retired, and the call-time asserts below
enforce the new (all-streamed) footprints.

Empty slots / dropped choices are index -1 and come out exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import (VMEM_BUDGET_BYTES, block_and_pad,
                                  default_interpret, lane_column)


def dispatch_vmem_bytes(block_rows: int, block_src: int, d: int,
                        itemsize: int = 4) -> int:
    """Static per-grid-step VMEM footprint of ``dispatch_rows``.

    Everything streams double-buffered: the src/scale index columns and the
    fp32 [br, d] output tile per output step, plus the [bx, d] source tile
    per source step — no block scales with the full T extent any more (the
    PR-4 ``untiled-block`` ceiling, now retired)."""
    return 2 * (block_rows * 4 + block_rows * 4
                + block_src * d * itemsize + block_rows * d * 4)


def combine_vmem_bytes(block_t: int, block_r: int, d: int, k: int,
                       itemsize: int = 4) -> int:
    """Static per-grid-step VMEM footprint of ``combine_rows`` — the slot
    buffer streams in [brf, d] tiles (no R-resident block; PR-4 ceiling
    retired), rows/weights and the fp32 output tile double-buffer."""
    return 2 * (block_t * k * 4 + block_t * k * 4
                + block_r * d * itemsize + block_t * d * 4)


def _check_vmem(name: str, footprint: int, interpret: bool,
                vmem_budget: int | None, note: str) -> None:
    """Fail loudly (with the computed footprint) instead of a silent TPU
    OOM.  Interpret mode has no VMEM, so the check only fires natively —
    or whenever the caller pins an explicit ``vmem_budget``."""
    budget = vmem_budget
    if budget is None:
        budget = None if interpret else VMEM_BUDGET_BYTES
    if budget is not None and footprint > budget:
        raise ValueError(
            f"{name}: static VMEM footprint {footprint:,} B exceeds the "
            f"per-core budget {int(budget):,} B ({note} per "
            f"grid step — checked against repro.analysis pass 1; "
            f"shrink the block or split the call)")


def invert_slots(rows, n_rows: int):
    """[T, k] flat destination row per (token, choice), -1 for dropped ->
    ([n_rows] source token id, [n_rows] source choice id), -1 for empty.

    Metadata-sized (int32, no feature dim); gating guarantees destination
    rows are unique so a plain scatter-set is exact.
    """
    t, k = rows.shape
    flat = rows.reshape(-1)
    choice = jnp.arange(t * k, dtype=jnp.int32)
    tgt = jnp.where(flat < 0, n_rows, flat)
    src = jnp.full((n_rows + 1,), -1, jnp.int32)
    src = src.at[tgt].set(choice, mode="drop")[:-1]
    return jnp.where(src >= 0, src // k, -1), jnp.where(src >= 0, src % k, -1)


def _gather_dot(onehot, tile):
    """Row gather as a 0/1 matmul on the MXU: ``onehot`` [n, b] selects rows
    of ``tile`` [b, d].  f32 accumulation, and full f32 passes for an f32
    tile, so each selected row comes out exactly — when ``tile`` is finite.
    A NaN or Inf anywhere in feature c of ``tile`` makes feature c of every
    output row NaN (0 * NaN, 0 * Inf)."""
    prec = (jax.lax.Precision.HIGHEST if tile.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.dot(onehot.astype(tile.dtype), tile, precision=prec,
                   preferred_element_type=jnp.float32)


def _dispatch_kernel(src_ref, scale_ref, x_ref, o_ref, *, block_src: int):
    # source tiles stream along grid dim 1; the output tile is revisited,
    # zero-initialized on the first source tile and accumulated in fp32.
    # Each output row's source token lives in exactly one tile; for finite
    # x the other tiles add exactly 0.0, so the sum is bitwise the one-pass
    # gather (a non-finite x value poisons its feature in every row).
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = src_ref[...]                                  # [br, 1] global token
    local = idx - j * block_src
    inside = (idx >= 0) & (local >= 0) & (local < block_src)
    col = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], block_src), 1)
    rows = _gather_dot((local == col) & inside, x_ref[...])   # [br, d] f32
    o_ref[...] += rows * scale_ref[...]


def dispatch_rows(x, src_tok, scale=None, *, block_rows: int = 1024,
                  block_src: int = 512, interpret: bool | None = None,
                  vmem_budget: int | None = None):
    """x: [T, d]; src_tok: [R] int32 source token per output row (-1 empty);
    scale: optional [R] f32 per-row weight (default 1).  -> [R, d] x.dtype.

    VMEM contract: the token block streams in [block_src, d] tiles (grid
    dim 1) — nothing scales with the full T extent, so all four paper
    shapes fit the per-core budget at scale=1.  Checked up front via
    ``dispatch_vmem_bytes`` (raises ValueError instead of a silent OOM).

    Equal to ``ref.ref_dispatch_rows`` for finite ``x``; a non-finite value
    in feature c of ``x`` makes feature c of every output row NaN.
    """
    if interpret is None:
        interpret = default_interpret()
    t, d = x.shape
    r = src_tok.shape[0]
    if scale is None:
        scale = jnp.ones((r,), jnp.float32)
    br, r_pad = block_and_pad(r, block_rows)
    bx, t_pad = block_and_pad(t, block_src)
    _check_vmem("dispatch_rows",
                dispatch_vmem_bytes(br, bx, d, x.dtype.itemsize),
                interpret, vmem_budget,
                f"streamed [bx={bx}, d={d}] source + [br={br}, d={d}] "
                f"output tiles")
    if r_pad != r:
        src_tok = jnp.pad(src_tok, (0, r_pad - r), constant_values=-1)
        scale = jnp.pad(scale, (0, r_pad - r))
    if t_pad != t:
        x = jnp.pad(x, ((0, t_pad - t), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, block_src=bx),
        grid=(r_pad // br, t_pad // bx),
        in_specs=[
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bx, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, d), jnp.float32),
        name="dispatch_rows",
        interpret=interpret,
    )(src_tok[:, None], scale.astype(jnp.float32)[:, None], x)
    return out[:r].astype(x.dtype)


def _combine_kernel(idx_ref, w_ref, buf_ref, o_ref, *, k: int,
                    block_rows: int):
    # slot-buffer tiles stream along grid dim 1; each (token, choice) hits
    # exactly one tile (for a finite buffer the others add 0.0) and fp32
    # addition is commutative, so the accumulated weighted sum equals the
    # one-pass reduction bitwise.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = idx_ref[...]                                  # [bt, k]
    w = w_ref[...]                                      # [bt, k] f32
    lane = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], block_rows), 1)
    buf = buf_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for c in range(k):
        # exact 0/1 gather of choice c's slot row, then the gate weight in
        # f32 on the VPU (as the reference applies it)
        idx_c = lane_column(idx, c)                     # [bt, 1]
        w_c = jnp.sum(jnp.where(lane == c, w, 0.0), axis=1, keepdims=True)
        local = idx_c - j * block_rows
        inside = (idx_c >= 0) & (local >= 0) & (local < block_rows)
        acc = acc + _gather_dot((local == col) & inside, buf) \
            * jnp.where(inside, w_c, 0.0)
    o_ref[...] += acc


def combine_rows(buf, rows, weights, *, block_t: int = 1024,
                 block_rows: int = 512, interpret: bool | None = None,
                 vmem_budget: int | None = None):
    """buf: [R, d] slot rows; rows: [T, k] int32 flat slot per (token,
    choice), -1 dropped; weights: [T, k] gate weights.  -> [T, d] buf.dtype.

    VMEM contract: the slot buffer streams in [block_rows, d] tiles (grid
    dim 1) — no R-resident block — checked via ``combine_vmem_bytes``.

    Equal to ``ref.ref_combine_rows`` for a finite ``buf``; a non-finite
    value in feature c of ``buf`` makes feature c of every output row NaN.
    """
    if interpret is None:
        interpret = default_interpret()
    r, d = buf.shape
    t, k = rows.shape
    bt, t_pad = block_and_pad(t, block_t)
    brf, r_pad = block_and_pad(r, block_rows)
    _check_vmem("combine_rows",
                combine_vmem_bytes(bt, brf, d, k, buf.dtype.itemsize),
                interpret, vmem_budget,
                f"streamed [brf={brf}, d={d}] buffer + [bt={bt}, d={d}] "
                f"output tiles")
    if t_pad != t:
        rows = jnp.pad(rows, ((0, t_pad - t), (0, 0)), constant_values=-1)
        weights = jnp.pad(weights, ((0, t_pad - t), (0, 0)))
    if r_pad != r:
        buf = jnp.pad(buf, ((0, r_pad - r), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, k=k, block_rows=brf),
        grid=(t_pad // bt, r_pad // brf),
        in_specs=[
            pl.BlockSpec((bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((brf, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), jnp.float32),
        name="combine_rows",
        interpret=interpret,
    )(rows, weights.astype(jnp.float32), buf)
    return out[:t].astype(buf.dtype)


def _route_kernel(idx_ref, pos_ref, cum_ref, slot_ref, o_ref, *,
                  k: int, n_experts: int, slot_cap: int):
    # bin partition: replica r owns positions [cum[r-1], cum[r]) of its
    # expert's GShard priority ranks.  Zero-weight (incl. dead/padded)
    # replicas never advance the cumsum, so they own an empty bin and are
    # skipped; pos >= total (= cum[-1]) is dropped.  Pure int32 arithmetic —
    # exactly equal to the XLA reference on both backends.  The [E, R]
    # table rows are selected by an unrolled compare-and-select over the
    # experts (metadata-sized; no in-kernel gather).
    idx_all = idx_ref[...]                              # [bt, k]
    pos_all = pos_ref[...]
    cum_tab = cum_ref[...]                              # [E, R]
    slot_tab = slot_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, idx_all.shape, 1)
    bt = idx_all.shape[0]
    rw = cum_tab.shape[-1]
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (bt, rw), 1)
    out = jnp.full(idx_all.shape, -1, jnp.int32)
    for c in range(k):
        idx_raw = lane_column(idx_all, c)               # [bt, 1]
        pos = lane_column(pos_all, c)
        cum = jnp.zeros((bt, rw), jnp.int32)
        slotvals = jnp.zeros((bt, rw), jnp.int32)
        for e in range(n_experts):
            hit = idx_raw == e
            cum = jnp.where(hit, cum_tab[e:e + 1, :], cum)
            slotvals = jnp.where(hit, slot_tab[e:e + 1, :], slotvals)
        total = jnp.sum(jnp.where(r_iota == rw - 1, cum, 0), axis=1,
                        keepdims=True)
        ge = pos >= cum                                 # [bt, R]
        which = jnp.minimum(jnp.sum(ge.astype(jnp.int32), axis=1,
                                    keepdims=True), rw - 1)
        prev = jnp.max(jnp.where(ge, cum, 0), axis=1,
                       keepdims=True)                   # cum[which-1] or 0
        slot = jnp.sum(jnp.where(r_iota == which, slotvals, 0), axis=1,
                       keepdims=True)
        rows = slot * slot_cap + (pos - prev)
        keep = (idx_raw >= 0) & (pos < total) & (slot >= 0)
        out = jnp.where(lane == c, jnp.where(keep, rows, -1), out)
    o_ref[...] = out


def weighted_route(expert_idx, position, cum_weights, slot_of,
                   slot_cap: int, *, block_t: int = 1024,
                   interpret: bool | None = None):
    """Map each kept (token, choice) onto a weighted replica row.

    expert_idx: [T, k] int32 chosen expert (-1 allowed, treated dropped);
    position:   [T, k] int32 GShard priority rank within the expert;
    cum_weights:[E, R] int32 inclusive cumsum of the per-replica integer
                routing weights (constant past the live columns);
    slot_of:    [E, R] int32 global slot id per replica (-1 on pads);
    slot_cap:   rows per slot.  -> [T, k] int32 flat destination row
    (slot * slot_cap + within-replica offset), -1 for dropped.

    The [E, R] weight/slot tables are VMEM-resident (metadata-sized);
    token tiles stream.  Positions >= the expert's total integer weight
    are dropped — with weights from ``integer_route_weights`` that is
    exactly the capacity rule, with no per-slot recount afterwards.
    """
    if interpret is None:
        interpret = default_interpret()
    t, k = expert_idx.shape
    bt, t_pad = block_and_pad(t, block_t)
    if t_pad != t:
        expert_idx = jnp.pad(expert_idx, ((0, t_pad - t), (0, 0)),
                             constant_values=-1)
        position = jnp.pad(position, ((0, t_pad - t), (0, 0)))
    e, rw = cum_weights.shape
    out = pl.pallas_call(
        functools.partial(_route_kernel, k=k, n_experts=e,
                          slot_cap=int(slot_cap)),
        grid=(t_pad // bt,),
        in_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((e, rw), lambda i: (0, 0)),
            pl.BlockSpec((e, rw), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t_pad, k), jnp.int32),
        name="weighted_route",
        interpret=interpret,
    )(expert_idx.astype(jnp.int32), position.astype(jnp.int32),
      cum_weights.astype(jnp.int32), slot_of.astype(jnp.int32))
    return out[:t]
