"""Serving-side MoE layer with Lina placement (replicated/packed experts).

Where training dispatch routes a token to *the* device owning its expert,
serving dispatch routes to one of the expert's replica slots per the
``PlacementPlan``, and each device computes every expert packed in its
sub-slots.  Weight movement is expressed as a gather of each device's
hosted experts (the SPMD analogue of §6.2's weight swap; XLA lowers it to
the minimal collective).

Replica selection (§5/§6.2) supports two modes:

  * ``"weighted"`` (default) — per-(expert, replica) integer routing
    weights are derived from the *realized* post-gating histogram and the
    plan's ``route_weight`` columns (device-load-aware fractions from
    ``placement.route_weights``), then each kept (token, choice) is mapped
    onto its replica bin by GShard priority position
    (``kernels.ops.weighted_route_op``).  Zero migration: tokens rebalance
    within the resident placement, and the per-slot capacity recount
    disappears — integer weights are capped at ``slot_cap`` by
    construction.
  * ``"round_robin"`` — the PR-1 positional round-robin (kept as the
    ablation baseline and for heterogeneous legacy plans).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import axes
from repro.core.axes import EP_AXIS
from repro.core.gating import capacity, router_top_k_gating
from repro.core.moe import MoEParams, expert_ffn
from repro.core.placement import PlacementPlan
from repro.kernels import ops as kernel_ops
from repro.kernels.dispatch import invert_slots


class PlanArrays(NamedTuple):
    """Device-resident form of a PlacementPlan (static shapes).

    A *stacked* PlanArrays carries one plan per MoE layer with a leading
    layer dim on every leaf (``slot_expert.ndim == 3``); ``decode_step``
    scans over it so each layer group dispatches under its own plan.

    ``route_weight`` holds the per-(expert, replica) routing fractions the
    weighted split starts from (rows sum to 1 over live replicas, 0 on
    pads/dead columns); per batch, ``balanced_route_fractions`` rebalances
    them against the realized histogram and ``integer_route_weights`` turns
    the result into integer targets.
    """
    slot_expert: jax.Array   # [n_dev, S] int32       (stacked: [L, n_dev, S])
    replica_of: jax.Array    # [E, R] int32 flat slot ids  (stacked: [L, E, R])
    n_replicas: jax.Array    # [E] int32                   (stacked: [L, E])
    route_weight: jax.Array = None  # [E, R] f32           (stacked: [L, E, R])
    #   None only transiently (legacy 3-field construction) — serve_moe_layer
    #   substitutes the uniform split before anything enters jit

    @classmethod
    def from_plan(cls, plan: PlacementPlan) -> "PlanArrays":
        from repro.core.placement import route_weights
        return cls(jnp.asarray(plan.slot_expert), jnp.asarray(plan.replica_of),
                   jnp.asarray(plan.n_replicas),
                   jnp.asarray(route_weights(plan)))

    @property
    def stacked(self) -> bool:
        return self.slot_expert.ndim == 3


def uniform_route_weight(replica_of, n_replicas):
    """[E, R] fractions splitting each expert evenly over its live replicas
    (the weight table callers use when no PlacementPlan is in hand)."""
    replica_of = jnp.asarray(replica_of)
    n_replicas = jnp.asarray(n_replicas)
    e, r_w = replica_of.shape
    live = jnp.arange(r_w)[None, :] < jnp.clip(n_replicas, 1, r_w)[:, None]
    live = live & (replica_of >= 0)
    n_live = jnp.maximum(jnp.sum(live, axis=1, keepdims=True), 1)
    return jnp.where(live, 1.0 / n_live.astype(jnp.float32), 0.0)


def mask_dead_route_weights(route_weight, replica_of, s_pack, dead_devices,
                            xp=jnp):
    """Zero the route-weight columns of replicas hosted on dead devices and
    renormalize each row over the survivors — the zero-migration degradation
    path: in-flight decodes re-route around a failed device with no plan
    rebuild and no slot-state loss, because the weighted split drops
    zero-weight bins entirely (``weighted_route`` keeps only positions below
    the cumulative row total).

    Rows whose every replica is dead come back all-zero; callers must
    emergency-replan those experts (``MoEServer.fail_devices`` does).
    Accepts flat [E, R] or stacked [L, E, R] tables.
    """
    dead = sorted(int(d) for d in dead_devices)
    if not dead:
        return route_weight
    dev = xp.where(replica_of >= 0, replica_of // s_pack, -1)
    doomed = xp.zeros(dev.shape, bool)
    for d in dead:
        doomed = doomed | (dev == d)
    w = xp.where(doomed, 0.0, route_weight.astype(xp.float32))
    tot = xp.sum(w, axis=-1, keepdims=True)
    return xp.where(tot > 0, w / xp.maximum(tot, 1e-9), 0.0)


def stack_plan_arrays(plans) -> PlanArrays:
    """Stack per-layer plans (PlacementPlan or PlanArrays) into one stacked
    PlanArrays with a leading layer dim.  All plans must agree on device
    count and sub-slot count; replica tables are right-padded to the widest
    plan (-1 slot ids, 0.0 route weights) so the stack is rectangular."""
    arrs = [p if isinstance(p, PlanArrays) else PlanArrays.from_plan(p)
            for p in plans]
    assert arrs, "stack_plan_arrays needs at least one plan"
    shapes = {a.slot_expert.shape for a in arrs}
    assert len(shapes) == 1, f"plans disagree on device layout: {shapes}"
    r = max(a.replica_of.shape[1] for a in arrs)

    def pad(a, fill):
        w = r - a.shape[1]
        return a if not w else jnp.pad(a, ((0, 0), (0, w)),
                                       constant_values=fill)

    def rweight(a):
        if a.route_weight is not None:
            return a.route_weight
        return uniform_route_weight(a.replica_of, a.n_replicas)

    return PlanArrays(
        jnp.stack([a.slot_expert for a in arrs]),
        jnp.stack([pad(a.replica_of, -1) for a in arrs]),
        jnp.stack([a.n_replicas for a in arrs]),
        jnp.stack([pad(rweight(a), 0.0) for a in arrs]))


def route_to_slots(expert_idx: jax.Array, position: jax.Array,
                   plan: PlanArrays) -> jax.Array:
    """[T, k] expert choices -> [T, k] flat slot ids, round-robin over the
    expert's replicas by buffer position (balances links, §5/§6.2).

    ``n_replicas`` is clamped to the live replica-table width: a stacked
    plan is right-padded with -1 slot ids, and a layer whose replica count
    disagrees with the pad width must never index a pad column.  A -1 slot
    can still surface if the plan itself is inconsistent (n_replicas >
    genuine table entries) — callers must treat ``slot < 0`` as dropped.
    """
    r_w = plan.replica_of.shape[-1]
    n_rep = jnp.clip(plan.n_replicas[expert_idx], 1, r_w)      # [T, k]
    which = position % n_rep
    return jnp.take_along_axis(plan.replica_of[expert_idx], which[..., None],
                               axis=-1)[..., 0]


def integer_route_weights(counts, route_weight, n_replicas, slot_cap,
                          xp=jnp):
    """Realized per-expert token counts -> per-(expert, replica) integer
    routing weights (the §5 weighted zero-migration split).

    counts: [E] int kept tokens per expert this batch; route_weight: [E, R]
    fractions (0 on dead/pad columns); n_replicas: [E]; slot_cap: rows per
    slot.  Returns [E, R] int32 with

      * 0 on dead/pad columns, every entry <= slot_cap,
      * row sums >= counts whenever counts <= slot_cap * live replicas
        (no token is dropped by the split itself),
      * each entry within +-1 of its fractional target counts * frac
        (largest-remainder apportionment), except where the slot_cap clamp
        forces spill into other replicas' headroom.

    Pure elementwise/int math shared by the jit path (``xp=jnp``) and the
    host telemetry mirror (``xp=numpy``) — deliberately argsort-free so
    both backends rank remainders identically.
    """
    e, r_w = route_weight.shape
    counts = counts.astype(xp.int32)
    live = xp.arange(r_w, dtype=xp.int32)[None, :] \
        < xp.clip(n_replicas, 1, r_w).astype(xp.int32)[:, None]
    frac = xp.where(live, route_weight.astype(xp.float32), 0.0)
    tot = xp.sum(frac, axis=1, keepdims=True)
    # a column whose fraction is exactly 0 was deliberately zeroed (dead
    # device) and must get no remainder/spill tokens; a fully-zeroed row
    # keeps its replicas so the uniform fallback never drops tokens here —
    # the server emergency-replans such experts off the dead devices.
    live = live & ((frac > 0.0) | (tot <= 1e-9))
    n_live = xp.maximum(xp.sum(live.astype(xp.int32), axis=1, keepdims=True),
                        1)
    uniform = xp.where(live, 1.0 / n_live.astype(xp.float32), 0.0)
    frac = xp.where(tot > 1e-9, frac / xp.maximum(tot, 1e-9), uniform)
    quota = counts[:, None].astype(xp.float32) * frac
    base = xp.floor(quota).astype(xp.int32)
    fp = xp.where(live, quota - base.astype(xp.float32), -1.0)
    # largest-remainder rank[e, r] = #{r' : fp[r'] > fp[r], ties to lower
    # index} via an [E, R, R] comparison count (argsort stability differs
    # between numpy and jax; this does not)
    idx_r = xp.arange(r_w, dtype=xp.int32)
    beats = (fp[:, None, :] > fp[:, :, None]) | \
        ((fp[:, None, :] == fp[:, :, None])
         & (idx_r[None, None, :] < idx_r[None, :, None]))
    rank = xp.sum(beats.astype(xp.int32), axis=2)               # [E, R]
    rem = xp.maximum(counts - xp.sum(base, axis=1), 0)
    base = base + ((rank < rem[:, None]) & live).astype(xp.int32)
    base = xp.minimum(base, slot_cap)
    # pour any shortfall (slot_cap clamp, fp rounding) into live headroom,
    # left to right — guarantees row sums cover counts whenever possible
    head = xp.where(live, slot_cap - base, 0)
    short = xp.maximum(counts - xp.sum(base, axis=1), 0)
    cum_prev = xp.cumsum(head, axis=1) - head
    add = xp.clip(short[:, None] - cum_prev, 0, head)
    return (base + add).astype(xp.int32)


def balanced_route_fractions(counts, route_weight, replica_of, n_replicas,
                             n_dev, s_pack, rounds=4, xp=jnp):
    """Realized per-expert token counts -> per-(expert, replica) fractions
    that balance THIS batch's per-device received tokens over the resident
    placement — §5's transfer-balance objective evaluated on the realized
    histogram rather than the plan's popularity basis.

    The plan's static ``route_weight`` (IPF on the basis popularity) seeds
    a few multiplicative rebalance rounds against ``counts``: single-replica
    experts are pinned mass the balance works around, and a stale basis
    (drift) is corrected instead of amplified — an even split is what the
    balance converges to when the placement is symmetric, so this never
    does worse than round-robin in expectation.  ``replica_of`` holds flat
    slot ids over an [n_dev, s_pack] slot grid (device = slot // s_pack).
    Pure elementwise/int-gather math shared by the jit path (``xp=jnp``)
    and the host telemetry mirror (``xp=numpy``).
    """
    e, r_w = replica_of.shape
    live = (xp.arange(r_w, dtype=xp.int32)[None, :]
            < xp.clip(n_replicas, 1, r_w).astype(xp.int32)[:, None]) \
        & (replica_of >= 0)
    dev = xp.where(live, replica_of // s_pack, 0)
    # seed: plan fractions floored away from 0 so the multiplicative update
    # can recover a column the prior starved.  A column whose weight is
    # *exactly* 0 was deliberately zeroed (dead device / pad — IPF and the
    # uniform split never emit exact zeros on live columns) and must stay 0.
    live = live & (route_weight > 0)
    w = xp.where(live, xp.maximum(route_weight.astype(xp.float32), 1e-6), 0.0)
    tot = xp.sum(w, axis=1, keepdims=True)
    w = xp.where(tot > 0, w / xp.maximum(tot, 1e-9), 0.0)
    c = counts.astype(xp.float32)[:, None]                        # [E, 1]
    target = xp.maximum(xp.sum(c) / n_dev, 1e-9)
    oh = (dev.reshape(-1)[:, None]
          == xp.arange(n_dev, dtype=xp.int32)[None, :]).astype(xp.float32)
    for _ in range(rounds):
        load = (w * c).reshape(-1) @ oh                           # [n_dev]
        fac = xp.clip(target / xp.maximum(load, 1e-9), 0.1, 10.0)
        w = xp.where(live, w * fac[dev], 0.0)
        w = w / xp.maximum(xp.sum(w, axis=1, keepdims=True), 1e-9)
    return w


def slot_capacity(cap: int, min_replicas: int) -> int:
    """Per (device, sub-slot) buffer capacity under replication.

    An expert with r replicas round-robins its <= ``cap`` tokens over r
    slots, so each slot needs only ceil(cap / r); sizing by the *minimum*
    replica count across hosted experts is safe for every slot.  Floored at
    8 to keep the scatter MXU-aligned.  Must be static (shapes depend on
    it), hence an int argument rather than a plan-array lookup.
    """
    return max(8, -(-cap // max(1, min_replicas)))


def dp_shard_count(mesh, n_tokens: int) -> int:
    """The data-parallel factor ``serve_moe_layer`` shards tokens by (1 when
    the token count does not tile the dp axes)."""
    if mesh is None:
        return 1
    sizes = axes.axis_sizes(mesh)
    dp_n = sizes.get(axes.POD, 1) * sizes.get(axes.DATA, 1)
    return dp_n if n_tokens % dp_n == 0 else 1


def _serve_body(x, router, wi, wu, wo, plan: PlanArrays, *, cfg: MoEConfig,
                ffn_type: str, ep_axis: str, top_k: int,
                min_replicas: int = 1, cap_override: int = 0,
                route_mode: str = "weighted"):
    """x: [T_local, d]; wi/wu/wo sharded expert-major over ep_axis."""
    t_local, d_model = x.shape
    e = cfg.n_experts
    ep = lax.psum(1, ep_axis)
    n_dev, s_pack = plan.slot_expert.shape
    cap = cap_override or capacity(t_local, e, top_k, cfg.capacity_factor)
    slot_cap = slot_capacity(cap, min_replicas)

    backend = kernel_ops.resolve_backend(cfg.compute_backend)
    # gating capacity stays per-expert (cap); the per-slot limit is enforced
    # by the replica split below.  The router matmul (and on the pallas
    # backend the position cumsum) is fused into the gating kernels.
    g = router_top_k_gating(x, router, top_k, cap, cfg.aux_loss_weight,
                            compute_backend=backend)

    # --- route to replica slots instead of home experts -------------------
    n_slots = n_dev * s_pack
    if route_mode == "weighted":
        # realized histogram -> integer per-replica targets -> bin routing.
        # Kept positions for expert e are exactly {0..counts_e-1} (GShard
        # priority), so position < sum(w_int) IS the capacity rule and no
        # per-slot recount is needed: every replica bin holds <= slot_cap.
        kept = (~g.dropped).astype(jnp.int32)
        counts = jnp.zeros((e,), jnp.int32).at[g.expert_idx.reshape(-1)] \
            .add(kept.reshape(-1), mode="drop")
        fracs = balanced_route_fractions(counts, plan.route_weight,
                                         plan.replica_of, plan.n_replicas,
                                         n_dev, s_pack)
        w_int = integer_route_weights(counts, fracs, plan.n_replicas,
                                      slot_cap)
        cumw = jnp.cumsum(w_int, axis=1).astype(jnp.int32)
        rows = kernel_ops.weighted_route_op(
            jnp.where(g.dropped, -1, g.expert_idx), g.position, cumw,
            plan.replica_of, slot_cap,
            use_pallas=(backend == "pallas"))                   # [T, k]
        dropped = rows < 0
    else:
        slots = route_to_slots(g.expert_idx, g.position, plan)  # [T, k]
        # position within the slot: recount capacity per slot
        oh = jax.nn.one_hot(slots, n_slots, dtype=jnp.int32)
        pos = (jnp.cumsum(oh.reshape(-1, n_slots), axis=0)
               - oh.reshape(-1, n_slots))
        pos = jnp.sum(pos.reshape(*slots.shape, n_slots) * oh, axis=-1)
        # slots < 0: inconsistent plan (n_replicas past the live table) —
        # treat as dropped rather than scattering into a negative row
        dropped = g.dropped | (pos >= slot_cap) | (slots < 0)

        # single source of truth for the slot-row map: -1 encodes dropped
        rows = jnp.where(dropped, -1, slots * slot_cap + pos)   # [T, k]
    if backend == "pallas":
        src_tok, _ = invert_slots(rows, n_slots * slot_cap)
        disp, _ = kernel_ops.dispatch_combine_op(use_pallas=True)
        buf = disp(x, src_tok, rows)
    else:
        flat_idx = jnp.where(rows < 0, n_slots * slot_cap, rows)
        buf = jnp.zeros((n_slots * slot_cap + 1, d_model), x.dtype)
        src = jnp.broadcast_to(x[:, None, :], (*rows.shape, d_model))
        buf = buf.at[flat_idx.reshape(-1)].set(src.reshape(-1, d_model),
                                               mode="drop")[:-1]
    buf = buf.reshape(n_dev, s_pack * slot_cap, d_model)

    # --- a2a to slot owners ------------------------------------------------
    # n_dev logical devices map onto ep physical ranks (group = n_dev/ep
    # logical per physical; group == 1 on the production mesh, == n_dev on a
    # single CPU device so the same code serves tests and demos)
    assert n_dev % ep == 0, "plan devices must tile the EP group"
    group = n_dev // ep
    recv = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                          tiled=True)                 # [ep*grp*S*cap, d] mine
    recv = recv.reshape(ep, group * s_pack, slot_cap, d_model)

    # --- hosted-expert weights (gather = §6.2 weight swap) -----------------
    my_dev = lax.axis_index(ep_axis)
    hosted = lax.dynamic_slice_in_dim(plan.slot_expert, my_dev * group,
                                      group, 0).reshape(group * s_pack)
    s_pack = group * s_pack
    e_local = e // ep
    # wi is the local shard [E_local, d, f]; hosted experts may live on other
    # shards -> gather the full stacks then select (XLA keeps only used rows
    # alive; the optimized delta-fetch path is a §Perf hillclimb).
    wi_full = lax.all_gather(wi, ep_axis, axis=0, tiled=True)     # [E, d, f]
    wo_full = lax.all_gather(wo, ep_axis, axis=0, tiled=True)
    wu_full = lax.all_gather(wu, ep_axis, axis=0, tiled=True) if wu is not None else None
    safe = jnp.maximum(hosted, 0)
    wi_h = wi_full[safe]
    wo_h = wo_full[safe]
    wu_h = wu_full[safe] if wu_full is not None else None

    # --- compute packed experts sequentially (§6.2) ------------------------
    # replica-packed [S, n, d] slot buffers feed the same grouped-FFN op the
    # training layer uses (the Pallas grouped GEMM on that backend)
    toks = recv.transpose(1, 0, 2, 3).reshape(s_pack, ep * slot_cap, d_model)
    out = expert_ffn(wi_h, wu_h, wo_h, toks, ffn_type, backend)   # [S, n, d]
    out = out * (hosted >= 0)[:, None, None]
    out = out.reshape(s_pack, ep, slot_cap, d_model).transpose(1, 0, 2, 3)

    # --- a2a back + combine -------------------------------------------------
    back = lax.all_to_all(out.reshape(ep, s_pack * slot_cap, d_model),
                          ep_axis, split_axis=0, concat_axis=0, tiled=True)
    flat = back.reshape(n_slots * slot_cap, d_model)
    w = jnp.where(dropped, 0.0, g.gate_weights)
    if backend == "pallas":
        _, comb = kernel_ops.dispatch_combine_op(use_pallas=True)
        y = comb(flat, rows, w).astype(x.dtype)
    else:
        vals = flat[jnp.maximum(rows, 0)]    # dropped gather row 0, w == 0
        y = jnp.sum(vals.astype(jnp.float32) * w[..., None],
                    axis=1).astype(x.dtype)
    return y, g.expert_idx, g.router_probs


def serve_moe_layer(mesh, x, params: MoEParams, cfg: MoEConfig,
                    plan: PlanArrays, *, ffn_type: str = "swiglu",
                    top_k: int | None = None, min_replicas: int = 1,
                    cap_override: int = 0, route_mode: str = "weighted"):
    """Inference MoE layer honoring a placement plan.  x: [T, d] global.

    ``min_replicas`` is the minimum live replica count across experts in
    ``plan`` (static; callers with a host-side PlacementPlan pass
    ``int(plan.n_replicas.min())``) — it shrinks per-slot buffers to
    ceil(cap / min_replicas).  ``cap_override`` (static, per-device) pins
    the per-expert gating capacity; callers serving right-padded batches
    use it to size capacity from the *valid* token count so padding rows
    cannot change real tokens' dispatch.  ``route_mode`` selects the
    replica split: ``"weighted"`` (realized-histogram integer weights,
    zero-migration §5 rebalance) or ``"round_robin"`` (positional).
    """
    if mesh is None:
        from repro.core.moe import default_mesh
        mesh = default_mesh()
    if route_mode not in ("weighted", "round_robin"):
        raise ValueError(f"unknown route_mode {route_mode!r}")
    dp = axes.dp_axes(mesh)
    dp_n = dp_shard_count(mesh, x.shape[0])
    bspec = P(dp, None) if dp_n > 1 else P(None, None)
    wspec = P(EP_AXIS, None, None)
    k = top_k if top_k is not None else max(cfg.top_k, 1)
    has_wu = params.wu is not None
    wu = params.wu if has_wu else jnp.zeros((), x.dtype)
    rweight = plan.route_weight
    if rweight is None:       # legacy plan tuples: split live replicas evenly
        rweight = uniform_route_weight(plan.replica_of, plan.n_replicas)

    def wrapped(x, router, wi, wu_, wo, se, ro, nr, rw):
        plan_arr = PlanArrays(se, ro, nr, rw)
        return _serve_body(x, router, wi, wu_ if has_wu else None, wo,
                           plan_arr, cfg=cfg, ffn_type=ffn_type,
                           ep_axis=EP_AXIS, top_k=k,
                           min_replicas=min_replicas,
                           cap_override=cap_override,
                           route_mode=route_mode)

    y, eidx, probs = shard_map(
        wrapped, mesh=mesh,
        in_specs=(bspec, P(None, None), wspec, wspec if has_wu else P(),
                  wspec, P(None, None), P(None, None), P(None),
                  P(None, None)),
        out_specs=(bspec, bspec, bspec),
        check_vma=False,
    )(x, params.router, params.wi, wu, params.wo,
      plan.slot_expert, plan.replica_of, plan.n_replicas, rweight)
    return y, eidx, probs


def _np_positions(expert_idx: np.ndarray, n_experts: int) -> np.ndarray:
    """Choice-major GShard priority rank, numpy (mirror of
    ``ref.ref_topk_positions``); -1 entries rank 0 and advance nothing."""
    t, k = expert_idx.shape
    flat = expert_idx.T.reshape(-1)
    oh = (flat[:, None] == np.arange(n_experts)[None, :]).astype(np.int64)
    pos = ((np.cumsum(oh, axis=0) - oh) * oh).sum(1)
    return pos.reshape(k, t).T


def replica_token_counts(expert_idx, plan: PlanArrays, cap: int,
                         slot_cap: int, *, valid=None, dp_shards: int = 1,
                         route_mode: str = "weighted") -> np.ndarray:
    """Host-side mirror of the device routing: realized *valid* token count
    per (device, sub-slot) under ``plan`` — the per-replica load the
    telemetry bus/controller observes (satellite of the §5 weighted split).

    expert_idx: [T, k] host ints (the server's gate output over the full
    padded batch — padding rows DO claim capacity on device and are
    mirrored here, they just aren't counted); valid: optional [T] bool;
    dp_shards: the data-parallel factor ``serve_moe_layer`` used (tokens
    route within their shard).  Returns [n_slots] int64.
    """
    idx = np.asarray(expert_idx, np.int32)
    se = np.asarray(plan.slot_expert)
    ro = np.asarray(plan.replica_of, np.int32)
    nr = np.asarray(plan.n_replicas, np.int32)
    rw_tab = plan.route_weight
    if rw_tab is None:
        rw_tab = uniform_route_weight(ro, nr)
    rw_tab = np.asarray(rw_tab, np.float32)
    e, r_w = ro.shape
    n_slots = int(se.size)
    t = idx.shape[0]
    v = np.ones(t, bool) if valid is None else np.asarray(valid, bool)
    shards = max(1, int(dp_shards))
    if t % shards:
        shards = 1
    out = np.zeros(n_slots, np.int64)
    for chunk, vc in zip(np.split(idx, shards, axis=0),
                         np.split(v, shards, axis=0)):
        pos = _np_positions(chunk, e).astype(np.int32)
        dropped = (chunk < 0) | (pos >= cap)
        counts = np.bincount(chunk[~dropped].reshape(-1),
                             minlength=e).astype(np.int32)[:e]
        if route_mode == "weighted":
            from repro.kernels import ref
            n_dev_m, s_pack_m = se.shape
            fr = balanced_route_fractions(counts, rw_tab, ro, nr, n_dev_m,
                                          s_pack_m, xp=np)
            w_int = integer_route_weights(counts, fr, nr, slot_cap, xp=np)
            cum = np.cumsum(w_int, axis=1).astype(np.int32)
            rows = ref.ref_weighted_route(np.where(dropped, -1, chunk),
                                          pos, cum, ro, slot_cap, xp=np)
            keep = (rows >= 0) & vc[:, None]
            slots = rows[keep] // slot_cap
        else:
            safe = np.maximum(chunk, 0)
            n_rep = np.clip(nr[safe], 1, r_w)
            which = pos % n_rep
            sl = np.take_along_axis(ro[safe], which[..., None],
                                    axis=-1)[..., 0]
            # the device recount one-hots ALL rows (even gating-dropped
            # ones claim recount positions) — mirror that exactly
            flat = sl.reshape(-1)                           # token-major
            soh = (flat[:, None] == np.arange(n_slots)[None, :])
            spos = ((np.cumsum(soh, axis=0) - soh) * soh).sum(1) \
                .reshape(chunk.shape)
            keep = ~dropped & (sl >= 0) & (spos < slot_cap) & vc[:, None]
            slots = sl[keep]
        out += np.bincount(slots, minlength=n_slots)[:n_slots]
    return out
