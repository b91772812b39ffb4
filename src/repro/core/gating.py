"""Top-k gating with capacity and the Switch/GShard auxiliary load-balancing
loss (paper §2.1).

The gating network is a single trainable matrix; tokens are dispatched to the
top-k experts subject to a per-expert capacity so all shapes stay static
under SPMD (TPU requirement; matches DeepSpeed's capacity-factor dispatch that
the paper baselines against, with Random Token Dropping disabled).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import router_logits


class GatingResult(NamedTuple):
    expert_idx: jax.Array      # [T, k] int32 — chosen expert per token/slot
    gate_weights: jax.Array    # [T, k] — combine weights (softmax renormed)
    position: jax.Array        # [T, k] int32 — position within expert buffer
    dropped: jax.Array         # [T, k] bool — True if over capacity
    aux_loss: jax.Array        # scalar — load-balancing loss
    router_probs: jax.Array    # [T, E] — full softmax (popularity profiling)


def capacity(n_tokens: int, n_experts: int, top_k: int,
             capacity_factor: float) -> int:
    """Per-expert buffer capacity, MXU-aligned up to a multiple of 8."""
    c = int(n_tokens * top_k * capacity_factor / n_experts) + 1
    return max(8, -(-c // 8) * 8)


def gating_from_topk(expert_idx: jax.Array, gate_w: jax.Array,
                     probs: jax.Array, cap: int,
                     aux_loss_weight: float = 0.01,
                     position: jax.Array | None = None) -> GatingResult:
    """Shared capacity/position/aux epilogue: turn raw top-k picks
    (idx [T,k], renormalized weights [T,k], full probs [T,E]) into the
    complete dispatch metadata.  Both the XLA gating path and the fused
    Pallas kernel (``kernels.ops.topk_gating_op``) feed this, so they agree
    exactly on slots, drops and the aux loss.

    ``position`` may be precomputed (the fused ``topk_positions`` kernel on
    the pallas path); when None the [T, k, E] one-hot cumsum runs here.
    """
    n_tokens, n_experts = probs.shape
    top_k = expert_idx.shape[1]

    # Aux loss (Switch eq.4): E * sum_e f_e * p_e, f_e from top-1 assignment.
    top1 = expert_idx[:, 0]
    f_e = jnp.mean(jax.nn.one_hot(top1, n_experts, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = aux_loss_weight * n_experts * jnp.sum(f_e * p_e)

    if position is None:
        # Capacity slots: flatten the k choices in priority order (all
        # tokens' 1st choice before any 2nd choice, GShard-style) so top-1
        # wins slots.
        onehot = jax.nn.one_hot(expert_idx, n_experts,
                                dtype=jnp.int32)                 # [T,k,E]
        flat = onehot.transpose(1, 0, 2).reshape(top_k * n_tokens, n_experts)
        pos_flat = jnp.cumsum(flat, axis=0) - flat           # pos in expert
        pos = (pos_flat.reshape(top_k, n_tokens, n_experts)
               .transpose(1, 0, 2))                              # [T,k,E]
        position = jnp.sum(pos * onehot, axis=-1)                # [T, k]
    dropped = position >= cap

    gate_w = jnp.where(dropped, 0.0, gate_w)
    return GatingResult(expert_idx.astype(jnp.int32), gate_w,
                        position.astype(jnp.int32), dropped, aux, probs)


def top_k_gating(logits: jax.Array, top_k: int, cap: int,
                 aux_loss_weight: float = 0.01,
                 rng: jax.Array | None = None,
                 jitter: float = 0.0) -> GatingResult:
    """logits: [T, E].  Returns dispatch metadata with static shapes.

    Position assignment follows GShard: tokens claim capacity slots in order
    (cumsum over the one-hot dispatch mask); tokens past the capacity are
    dropped (residual connection carries them, as in DeepSpeed).
    """
    if jitter > 0.0 and rng is not None:
        logits = logits + jitter * jax.random.normal(rng, logits.shape,
                                                     logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gate_w, expert_idx = jax.lax.top_k(probs, top_k)            # [T, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    return gating_from_topk(expert_idx, gate_w, probs, cap, aux_loss_weight)


def router_top_k_gating(x: jax.Array, router: jax.Array, top_k: int,
                        cap: int, aux_loss_weight: float = 0.01, *,
                        compute_backend: str = "xla") -> GatingResult:
    """The full gating network: ``x @ router`` + softmax + top-k.

    On the pallas backend the router matmul is folded into the fused
    softmax/top-k kernel (one VMEM pass, k <= 2 on the MoE paths); the
    capacity/position/aux epilogue is shared with ``top_k_gating`` so the
    two backends produce identical GatingResults.
    """
    if compute_backend != "pallas":
        return top_k_gating(router_logits(x, router), top_k, cap,
                            aux_loss_weight)
    from repro.kernels import ops as kernel_ops
    idx, gate_w, probs = kernel_ops.topk_gating_op(x, router, top_k,
                                                   use_pallas=True)
    # the capacity/position cumsum is fused too: no [T, k, E] one-hot in HBM
    position = kernel_ops.topk_positions_op(idx, probs.shape[-1],
                                            use_pallas=True)
    return gating_from_topk(idx, gate_w, probs, cap, aux_loss_weight,
                            position=position)
