"""The expert-parallel MoE layer: gating -> dispatch -> (micro-op a2a
pipelined with expert FFN) -> combine, under ``shard_map`` on the `model`
mesh axis, with optional Lina inference placement (replication/packing).

This is the module a user drops in place of an FFN (paper Fig. 1).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import axes
from repro.core import dispatch as D
from repro.core import microop
from repro.core.axes import DP_AXES, EP_AXIS
from repro.core.gating import capacity, router_top_k_gating
from repro.kernels.ops import grouped_ffn_op, resolve_backend

_DEFAULT_MESH = None


def default_mesh():
    """1-device ('data','model') mesh so the shard_map body (and its
    collectives) also runs on a bare CPU — used by smoke tests."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = jax.make_mesh((1, 1), (axes.DATA, axes.MODEL),
                                      axis_types=(AxisType.Auto,) * 2)
    return _DEFAULT_MESH


class MoEParams(NamedTuple):
    router: jax.Array        # [d, E]
    wi: jax.Array            # [E, d, f]   (gate proj for swiglu)
    wu: jax.Array | None     # [E, d, f]   (up proj; None for gelu FFN)
    wo: jax.Array            # [E, f, d]


class MoEOutput(NamedTuple):
    y: jax.Array             # [T, d]
    aux_loss: jax.Array      # scalar
    expert_idx: jax.Array    # [T, k] — for popularity profiling/estimation
    router_probs: jax.Array  # [T, E]
    a2a_token: jax.Array     # zero scalar data-dependent on the layer's a2a
    #                          micro-ops — the ordering signal Lina's
    #                          prioritized gradient reduce yields to
    #                          (optim/reduce.py); threaded, never dropped


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    ffn_type: str = "swiglu", dtype=jnp.float32) -> MoEParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    router = (jax.random.normal(k1, (d_model, n_experts)) * s_in).astype(dtype)
    wi = (jax.random.normal(k2, (n_experts, d_model, d_ff)) * s_in).astype(dtype)
    wu = (jax.random.normal(k3, (n_experts, d_model, d_ff)) * s_in).astype(dtype) \
        if ffn_type == "swiglu" else None
    wo = (jax.random.normal(k4, (n_experts, d_ff, d_model)) * s_out).astype(dtype)
    return MoEParams(router, wi, wu, wo)


def expert_ffn(wi, wu, wo, x, ffn_type: str = "swiglu",
               compute_backend: str = "xla"):
    """x: [E_rows, n, d] with per-row expert weights [E_rows, d, f].

    ``compute_backend="pallas"`` runs the grouped-GEMM kernel
    (``kernels.ops.grouped_ffn_op``, custom-VJP so the train step's
    backward stays on tiled grouped GEMMs); ``"xla"`` keeps the einsum
    formulation the kernel is oracle-tested against.
    """
    if compute_backend == "pallas":
        return grouped_ffn_op(x, wi, wu, wo, ffn_type, use_pallas=True)
    h = jnp.einsum("end,edf->enf", x, wi)
    if ffn_type == "swiglu":
        u = jnp.einsum("end,edf->enf", x, wu)
        h = jax.nn.silu(h) * u
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("enf,efd->end", h, wo)


# ---------------------------------------------------------------------------
# The shard_map body: everything below runs per-device with explicit
# collectives — this is where Lina's schedule lives.
# ---------------------------------------------------------------------------

def _moe_shard_body(x, router, wi, wu, wo, *, cfg: MoEConfig, ffn_type: str,
                    dispatch_backend: str, ep_axis: str, dp_axes,
                    lina: bool, fsdp: bool = False, tp_axis: str | None = None,
                    top_k: int | None = None, shortcut=None):
    """x: [T_local, d].  Expert weights arrive expert-sharded over ep_axis:
    wi/wu/wo have leading dim E_local = E / ep.  With ``fsdp`` they are
    additionally sharded over the dp axes on the hidden dim and gathered
    here, per layer, so the resident footprint stays 1/(ep*dp) of the stack
    (ZeRO-3 for experts; the per-layer gather overlaps with gating).  With
    ``tp_axis`` the expert hidden dim stays sharded (expert slicing) and the
    output projection carries a psum over tp."""
    if fsdp:
        wi = lax.all_gather(wi, dp_axes, axis=2, tiled=True)
        if wu is not None:
            wu = lax.all_gather(wu, dp_axes, axis=2, tiled=True)
        wo = lax.all_gather(wo, dp_axes, axis=1, tiled=True)
    b_loc, s_loc, d_model = x.shape
    x = x.reshape(b_loc * s_loc, d_model)      # local flatten: no resharding
    t_local = x.shape[0]
    e = cfg.n_experts
    k = top_k or cfg.top_k
    cap = capacity(t_local, e, k, cfg.capacity_factor)

    backend = resolve_backend(cfg.compute_backend)
    # fused router matmul + softmax + top-k on the pallas backend
    g = router_top_k_gating(x, router, k, cap, cfg.aux_loss_weight,
                            compute_backend=backend)

    disp, comb = D.get_backend(dispatch_backend)
    buf = disp(x, g, e, cap)                                      # [E, C, d]

    ep = lax.psum(1, ep_axis)
    e_local = e // ep

    def ffn_rows(rows):                                           # [ep*E_local, c, d]
        rs = rows.reshape(ep, e_local, rows.shape[1], d_model)
        rs = rs.transpose(1, 0, 2, 3).reshape(e_local, ep * rows.shape[1], d_model)
        out = expert_ffn(wi, wu, wo, rs, ffn_type, backend)
        if tp_axis is not None:
            out = lax.psum(out, tp_axis)     # contract the tp-sharded hidden
        out = out.reshape(e_local, ep, rows.shape[1], d_model)
        return out.transpose(1, 0, 2, 3).reshape(ep * e_local, rows.shape[1], d_model)

    sc_out = None
    if shortcut is not None:
        # ScMoE shortcut branch: dense FFN on the *local* tokens with
        # replicated weights.  Ordered after the dispatch buffer so it sits
        # between dispatch and combine in program order — under the a2a
        # shadow — but carries no edge into the collective chain itself, so
        # the a2a micro-ops never wait on it.
        sw_in, sw_up, sw_out = shortcut
        xs = microop.ordered_after(x, microop._token_of(buf))
        hs = xs @ sw_in
        if ffn_type == "swiglu":
            hs = jax.nn.silu(hs) * (xs @ sw_up)
        else:
            hs = jax.nn.gelu(hs)
        sc_out = hs @ sw_out

    n_chunks = cfg.n_microops if lina else 1
    out_buf, a2a_token = microop.pipelined_expert_ffn(
        buf, ffn_rows, ep_axis, n_chunks, e, pipeline=lina and cfg.pipeline_ffn)

    y = comb(out_buf, g, e, cap)                                  # [T, d]
    if sc_out is not None:
        y = y + sc_out                     # summed into the combine (ScMoE)
    y = y.reshape(b_loc, s_loc, d_model)
    return y, g.aux_loss, g.expert_idx, g.router_probs, a2a_token


def moe_layer(mesh, x, params: MoEParams, cfg: MoEConfig, *,
              ffn_type: str = "swiglu", dispatch_backend: str = "scatter",
              lina: bool = True, fsdp: bool = False,
              top_k: int | None = None, shortcut_params=None) -> MoEOutput:
    """x: [B, S, d].  Experts sharded over `model`; tokens sharded batch-over
    dp and sequence-over-`model` — the SAME layout sequence parallelism uses
    between blocks, so entering the MoE region costs no resharding, and each
    device gates/dispatches only its T/(dp*ep) tokens (replicated over `tp`,
    whose ranks must see identical tokens for the expert-slicing psum).
    With ``fsdp``, expert hidden dims are additionally sharded over dp; a
    `tp` mesh axis tensor-slices the expert hidden dim (expert slicing)."""
    if mesh is None:
        mesh = default_mesh()
    tp = axes.TP if axes.TP in mesh.axis_names else None
    dp = axes.dp_axes(mesh)
    sizes = axes.axis_sizes(mesh)
    b_, s_, _ = x.shape
    dp_n = 1
    for a in dp:
        dp_n *= sizes.get(a, 1)
    bq = dp if b_ % dp_n == 0 else None
    sq = EP_AXIS if s_ % sizes.get(EP_AXIS, 1) == 0 else None
    bspec = P(bq, sq, None)
    hid = ((tp,) if tp else ()) + (dp if fsdp else ())  # hidden-dim shards
    if hid:
        wspec_i = P(EP_AXIS, None, hid)   # [E->ep, d, f->tp(+dp)]
        wspec_o = P(EP_AXIS, hid, None)   # [E->ep, f->tp(+dp), d]
    else:
        wspec_i = wspec_o = P(EP_AXIS, None, None)
    body = partial(_moe_shard_body, cfg=cfg, ffn_type=ffn_type,
                   dispatch_backend=dispatch_backend, ep_axis=EP_AXIS,
                   dp_axes=dp, lina=lina, fsdp=fsdp, tp_axis=tp, top_k=top_k)
    has_wu = params.wu is not None
    wu_spec = wspec_i if has_wu else P()
    wu = params.wu if has_wu else jnp.zeros((), x.dtype)

    # ScMoE shortcut weights ride along replicated (dense branch, no ep/tp
    # sharding); dummy scalars when the variant is off.
    has_sc = shortcut_params is not None
    if has_sc:
        sc_wi, sc_wu, sc_wo = shortcut_params
    else:
        sc_wi = sc_wu = sc_wo = None
    has_sc_wu = has_sc and sc_wu is not None
    dummy = jnp.zeros((), x.dtype)
    sc_in = (sc_wi if has_sc else dummy, sc_wu if has_sc_wu else dummy,
             sc_wo if has_sc else dummy)
    sc_specs = (P(None, None) if has_sc else P(),
                P(None, None) if has_sc_wu else P(),
                P(None, None) if has_sc else P())

    aux_axes = (dp if bq else ()) + ((EP_AXIS,) if sq else ())

    def wrapped(x, router, wi, wu, wo, sc_wi, sc_wu, sc_wo):
        wu_ = wu if has_wu else None
        sc = (sc_wi, sc_wu if has_sc_wu else None, sc_wo) if has_sc else None
        y, aux, eidx, probs, tok = body(x, router, wi, wu_, wo, shortcut=sc)
        # aux loss: tokens differ across every sharded axis -> mean over them
        if aux_axes:
            aux = lax.pmean(aux, aux_axes)
        return y, aux, eidx, probs, tok

    # token-flat outputs (expert ids / probs) keep the (b, s)-derived shard
    flat_axes = (tuple(bq) if bq else ()) + ((sq,) if sq else ())
    flat_spec = P(flat_axes or None, None)
    y, aux, eidx, probs, tok = shard_map(
        wrapped, mesh=mesh,
        in_specs=(bspec, P(None, None), wspec_i, wu_spec, wspec_o) + sc_specs,
        out_specs=(bspec, P(), flat_spec, flat_spec, P()),
        check_vma=False,
    )(x, params.router, params.wi, wu, params.wo, *sc_in)
    return MoEOutput(y, aux, eidx, probs, tok)
