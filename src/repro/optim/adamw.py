"""AdamW with states that mirror the parameter sharding (states are created
`like` the params, so pjit shards m/v exactly as the FSDP'd weights — ZeRO
for free), plus cosine LR schedule and global-norm clipping."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    state_dtype: str = "float32"


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


def init_opt_state(params, cfg: AdamWConfig) -> OptState:
    dt = jnp.dtype(cfg.state_dtype)
    # zeros_like lays each moment out like its parameter (sharded params ->
    # moments created sharded in place)
    zeros = lambda p: jnp.zeros_like(p, dtype=dt)
    return OptState(jnp.zeros((), jnp.int32),
                    jax.tree.map(zeros, params),
                    jax.tree.map(zeros, params))


def cosine_schedule(step, cfg: AdamWConfig):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    return cfg.lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * t))


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), gn


def adamw_update(params, grads, state: OptState, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    grads, gn = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    lr = cosine_schedule(step, cfg)
    b1, b2 = cfg.betas
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * g32
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
        mh = m_new / bc1
        vh = v_new / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    # three passes (XLA CSEs the shared subexpressions); avoids tuple-leaf
    # ambiguity with NamedTuple param nodes
    new_params = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v)[0],
                              params, grads, state.m, state.v)
    new_m = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v)[1],
                         params, grads, state.m, state.v)
    new_v = jax.tree.map(lambda p, g, m, v: upd(p, g, m, v)[2],
                         params, grads, state.m, state.v)
    return new_params, OptState(step, new_m, new_v), {"grad_norm": gn, "lr": lr}
