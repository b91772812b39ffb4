"""Public jit'd kernel wrappers — the ONLY kernel entry point models use.

On a TPU backend the Pallas kernels compile natively (Mosaic).  On the CPU
test backend ``interpret=True`` executes the kernel bodies on the host for
correctness validation (the tests sweep shapes/dtypes against ref.py).
``use_pallas`` defaults to the backend: models call these ops and get the
kernel on TPU and the jnp oracle on CPU; passing ``use_pallas=True`` on CPU
forces interpret-mode kernels (the parity-test / ``compute_backend="pallas"``
path).

The MoE ops are differentiable: ``grouped_ffn_op`` carries a
``jax.custom_vjp`` whose backward expresses every dgrad/wgrad as a
``grouped_matmul`` (same tiled kernel shapes as the forward), and the fused
gating / dispatch / combine ops carry linear-map VJPs so the jitted train
step runs end-to-end on the kernel path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.dispatch import (combine_rows, dispatch_rows,
                                    invert_slots, weighted_route)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_ffn import grouped_ffn, grouped_matmul
from repro.kernels.rwkv6 import rwkv6_wkv
from repro.kernels.ssd import ssd_scan
from repro.kernels.topk_gating import topk_gating_fused, topk_positions


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def resolve_backend(name: str | None) -> str:
    """``MoEConfig.compute_backend`` -> concrete backend.

    ``"auto"`` (the default) picks the Pallas kernels on TPU and the XLA
    einsum path elsewhere; explicit ``"pallas"`` off-TPU runs the kernels in
    interpret mode (parity tests, kernel benchmarks).
    """
    if name in (None, "", "auto"):
        return "pallas" if on_tpu() else "xla"
    if name not in ("xla", "pallas"):
        raise ValueError(f"unknown compute backend {name!r}")
    return name


def _int_zero_ct(a):
    """Cotangent for an integer-dtype primal input (jax wants float0)."""
    return np.zeros(a.shape, jax.dtypes.float0)


# ---------------------------------------------------------------------------
# grouped expert FFN (fwd kernel + grouped-GEMM backward)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_ffn_pallas(x, wi, wu, wo, ffn_type):
    return grouped_ffn(x, wi, wu, wo, ffn_type=ffn_type,
                       interpret=_interpret())


def _grouped_ffn_fwd(x, wi, wu, wo, ffn_type):
    return _grouped_ffn_pallas(x, wi, wu, wo, ffn_type), (x, wi, wu, wo)


def _grouped_ffn_bwd(ffn_type, res, dy):
    x, wi, wu, wo = res
    dy = dy.astype(jnp.float32)
    xt = x.swapaxes(1, 2)                                # [E, D, T]
    h = grouped_matmul(x, wi)                            # recompute [E, T, F]
    if ffn_type == "swiglu":
        u = grouped_matmul(x, wu)
        act, act_vjp = jax.vjp(lambda a, b: jax.nn.silu(a) * b, h, u)
    else:
        act, act_vjp = jax.vjp(jax.nn.gelu, h)
    da = grouped_matmul(dy, wo.swapaxes(1, 2))           # [E, T, F]
    dwo = grouped_matmul(act.swapaxes(1, 2), dy)         # [E, F, D]
    if ffn_type == "swiglu":
        dh, du = act_vjp(da)
        dx = grouped_matmul(dh, wi.swapaxes(1, 2)) \
            + grouped_matmul(du, wu.swapaxes(1, 2))
        dwu = grouped_matmul(xt, du).astype(wu.dtype)
    else:
        (dh,) = act_vjp(da)
        dx = grouped_matmul(dh, wi.swapaxes(1, 2))
        dwu = None
    dwi = grouped_matmul(xt, dh)
    return (dx.astype(x.dtype), dwi.astype(wi.dtype), dwu,
            dwo.astype(wo.dtype))


_grouped_ffn_pallas.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def grouped_ffn_op(x, wi, wu, wo, ffn_type: str = "swiglu",
                   use_pallas: bool | None = None):
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_grouped_ffn(x, wi, wu, wo, ffn_type)
    return _grouped_ffn_pallas(x, wi, wu, wo, ffn_type)


# ---------------------------------------------------------------------------
# fused router gating (router matmul + softmax + top-k in one kernel)
# ---------------------------------------------------------------------------

def _gating_oracle(x, router, k):
    return ref.ref_topk_gating(ref.router_logits(x, router), k)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _topk_gating_pallas(x, router, k):
    # idx travels as f32 through the custom-VJP boundary: an integer output
    # of a custom_vjp carries a concrete float0 tangent that poisons any
    # downstream int arithmetic when scan/shard_map linearize (and
    # stop_gradient is a no-op on ints); the f32->i32 cast outside has a
    # symbolically-zero tangent, which is what we want
    idx, w, probs = topk_gating_fused(x, k, router=router,
                                      interpret=_interpret())
    return idx.astype(jnp.float32), w, probs


def _gating_fwd(x, router, k):
    return _topk_gating_pallas(x, router, k), (x, router)


def _gating_bwd(k, res, cts):
    # idx is integer-valued (its f32 carrier gets no real cotangent);
    # w/probs backprop through the oracle formulation — same math as the
    # XLA path, so grads match it
    x, router = res
    _, dw, dprobs = cts
    _, vjp = jax.vjp(lambda x_, r_: _gating_oracle(x_, r_, k)[1:], x, router)
    return vjp((dw, dprobs))


_topk_gating_pallas.defvjp(_gating_fwd, _gating_bwd)


def topk_gating_op(x, router, k: int, use_pallas: bool | None = None):
    """Fused gating network: logits = x @ router folded into the softmax +
    top-k kernel.  x: [T, D]; router: [D, E] ->
    (idx [T,k] i32, w [T,k] f32 renormalized, probs [T,E] f32)."""
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return _gating_oracle(x, router, k)
    idx, w, probs = _topk_gating_pallas(x, router, k)
    return idx.astype(jnp.int32), w, probs


# ---------------------------------------------------------------------------
# fused dispatch metadata (priority positions + weighted replica routing)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _positions_pallas(expert_idx, n_experts):
    # integer output -> f32 carrier across the custom-VJP boundary (same
    # float0 rationale as _topk_gating_pallas)
    return topk_positions(expert_idx, n_experts,
                          interpret=_interpret()).astype(jnp.float32)


def _positions_fwd(expert_idx, n_experts):
    return _positions_pallas(expert_idx, n_experts), (expert_idx,)


def _positions_bwd(n_experts, res, dpos):
    (expert_idx,) = res
    return (_int_zero_ct(expert_idx),)


_positions_pallas.defvjp(_positions_fwd, _positions_bwd)


def topk_positions_op(expert_idx, n_experts: int,
                      use_pallas: bool | None = None):
    """GShard priority positions: expert_idx [T, k] i32 -> [T, k] i32
    choice-major rank within each expert (the capacity cumsum that was a
    [T, k, E] one-hot in core.gating, fused on the kernel path)."""
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_topk_positions(expert_idx, n_experts)
    return _positions_pallas(expert_idx, n_experts).astype(jnp.int32)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_route_pallas(expert_idx, position, cum_weights, slot_of,
                           slot_cap):
    return weighted_route(expert_idx, position, cum_weights, slot_of,
                          slot_cap, interpret=_interpret()
                          ).astype(jnp.float32)


def _weighted_route_fwd(expert_idx, position, cum_weights, slot_of,
                        slot_cap):
    return (_weighted_route_pallas(expert_idx, position, cum_weights,
                                   slot_of, slot_cap),
            (expert_idx, position, cum_weights, slot_of))


def _weighted_route_bwd(slot_cap, res, drows):
    return tuple(_int_zero_ct(a) for a in res)


_weighted_route_pallas.defvjp(_weighted_route_fwd, _weighted_route_bwd)


def weighted_route_op(expert_idx, position, cum_weights, slot_of,
                      slot_cap: int, use_pallas: bool | None = None):
    """Weighted replica-bin routing (Lina §5/§6.2 zero-migration split):
    (expert, priority position) -> flat destination row given the
    per-(expert, replica) integer weight cumsum and replica->slot table;
    -1 = dropped.  Integer-exact on both backends."""
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_weighted_route(expert_idx, position, cum_weights,
                                      slot_of, slot_cap)
    return _weighted_route_pallas(expert_idx, position, cum_weights,
                                  slot_of, slot_cap).astype(jnp.int32)


# ---------------------------------------------------------------------------
# fused dispatch / combine (capacity-buffer scatter + weighted gather)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _dispatch_pallas(x, src_tok, tok_rows):
    return dispatch_rows(x, src_tok, interpret=_interpret())


def _dispatch_fwd(x, src_tok, tok_rows):
    return _dispatch_pallas(x, src_tok, tok_rows), (src_tok, tok_rows)


def _dispatch_bwd(res, dbuf):
    # dispatch is a (masked) permutation of token rows: the cotangent of
    # token t is the sum of its slot rows — an unweighted combine gather
    src_tok, tok_rows = res
    ones = jnp.ones(tok_rows.shape, jnp.float32)
    dx = combine_rows(dbuf, tok_rows, ones, interpret=_interpret())
    return dx, _int_zero_ct(src_tok), _int_zero_ct(tok_rows)


_dispatch_pallas.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_pallas(buf, rows, weights):
    return combine_rows(buf, rows, weights, interpret=_interpret())


def _combine_fwd(buf, rows, weights):
    return _combine_pallas(buf, rows, weights), (buf, rows, weights)


def _combine_bwd(res, dy):
    buf, rows, weights = res
    r = buf.shape[0]
    # d buf: scatter w[t,k] * dy[t] into each (token, choice)'s slot row —
    # the dispatch kernel again, with the gate weight as the per-row scale
    src_tok, src_k = invert_slots(rows, r)
    w_flat = weights.reshape(-1).astype(jnp.float32)
    t, k = rows.shape
    scale = jnp.where(src_tok >= 0,
                      w_flat[jnp.maximum(src_tok * k + src_k, 0)], 0.0)
    dbuf = dispatch_rows(dy.astype(buf.dtype), src_tok, scale,
                         interpret=_interpret())
    # d weights: row-wise dot of dy with the gathered slot rows
    vals = buf[jnp.maximum(rows, 0)].astype(jnp.float32)     # [T, k, d]
    dw = jnp.sum(vals * dy.astype(jnp.float32)[:, None, :], axis=-1)
    dw = jnp.where(rows >= 0, dw, 0.0).astype(weights.dtype)
    return dbuf, _int_zero_ct(rows), dw


_combine_pallas.defvjp(_combine_fwd, _combine_bwd)


def dispatch_combine_op(use_pallas: bool | None = None):
    """Returns the (dispatch, combine) callables with backend dispatch baked
    in — mirrors ``core.dispatch.get_backend`` so models never import kernel
    modules directly.

    dispatch(x [T,d], src_tok [R] i32, tok_rows [T,k] i32) -> [R, d]
        scatter-to-capacity-rows; ``src_tok`` is the metadata-sized inverse
        map from ``kernels.dispatch.invert_slots``; ``tok_rows`` (the
        forward map, -1 = dropped) feeds the linear-map backward.
    combine(buf [R,d], rows [T,k] i32, w [T,k]) -> [T, d]
        gate-weighted gather of each token's slot rows.
    """
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return (lambda x, src_tok, tok_rows: ref.ref_dispatch_rows(x, src_tok),
                ref.ref_combine_rows)
    return _dispatch_pallas, _combine_pallas


# ---------------------------------------------------------------------------
# the remaining (non-MoE) kernels
# ---------------------------------------------------------------------------

def flash_attention_op(q, k, v, causal: bool = True, window: int = 0,
                       use_pallas: bool | None = None):
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_attention(q, k, v, causal=causal, window=window)
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=_interpret())


def rwkv6_op(r, k, v, w, u, use_pallas: bool | None = None):
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_rwkv6(r, k, v, w, u)
    return rwkv6_wkv(r, k, v, w, u, interpret=_interpret())


def ssd_op(x, dt, a_log, b, c, d_skip, use_pallas: bool | None = None):
    use = on_tpu() if use_pallas is None else use_pallas
    if not use:
        return ref.ref_ssd(x, dt, a_log, b, c, d_skip)
    return ssd_scan(x, dt, a_log, b, c, d_skip, interpret=_interpret())
