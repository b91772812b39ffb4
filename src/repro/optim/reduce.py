"""Lina §4 gradient-reduction subsystem: the DP-axis reduce as an explicit,
schedulable collective instead of whatever XLA's partitioner happens to emit.

The paper's training-side rule is *all-to-all goes first*: the gradient
allreduce that runs concurrently with the backward a2a must yield link
bandwidth to it (Figs. 5/7), and to make yielding cheap both are tensor-
partitioned into uniform micro-ops (Fig. 8).  Under SPMD the whole step is a
static program, so "priority" becomes *program order*: every reduce micro-op
carries a compile-time dependency edge on the backward-a2a completion token
(``core.microop.ordered_after``), which XLA cannot hoist above the a2a.

Five schedules (the same names ``benchmarks/commmodel.simulate_step`` models
analytically, so measured and simulated rows line up):

  ``baseline``                      one fused psum of the whole flattened
                                    gradient vector, no ordering edge —
                                    the DDP default (Fig. 7a).
  ``priority``                      same single op, but ordered after the
                                    backward-a2a token (Fig. 7b).
  ``fixed``                         Fig. 7c: the whole-tensor reduce
                                    *deferred past the second backward a2a*
                                    of the MoE layers.  Under SPMD program
                                    order this compiles to the same single
                                    ordered op as ``priority`` — the token
                                    already pins the reduce after every
                                    backward (and forward) a2a — so its
                                    measured row is the sanity anchor for
                                    the analytic model, where the two
                                    differ only through preemption of an
                                    in-flight allreduce (which a static
                                    SPMD program cannot express).
  ``priority+partition``            uniform micro-op chunks of each
                                    device's gradient shard, sized by
                                    ``partition_bytes``, each ordered after
                                    the token and chained among themselves
                                    (Fig. 8a).
  ``priority+partition+pipeline``   chunked reduce issued *per microbatch*
                                    inside the unrolled gradient-accumulation
                                    scan, so chunk k of microbatch i can
                                    overlap microbatch i+1's compute
                                    (Fig. 8b).  The per-call behavior here is
                                    identical to ``priority+partition``; the
                                    interleaving lives in
                                    ``launch.steps.make_train_step``.

Optional compression (``optim.compression``) wraps the chunked reduce:
``bf16`` halves wire bytes with a cast (the psum payload really is bf16),
``int8_ef`` quantizes with an error-feedback residual carried across steps
(``init_reduce_state`` / ``ReduceState``).  Note the int8 path reproduces
the *numerics* (quantize → sum → dequantize, EF residual), not the wire
width: the psum payload is int32 so dp-many summands cannot overflow — a
real deployment would use an int8 ring-reduce with wider accumulators.
Both preserve the ordering edges — compression composes with, never
replaces, the schedule.

All schedules are numerically mean-psum reductions: gradients enter
replicated over dp (the jit-level autodiff already produced the global
gradient), so the explicit collective is an identity *value*-wise while the
wire traffic, chunking, and ordering are real — exactly what the measured
ablation in ``benchmarks/train_side.py`` times.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import axes, microop
from repro.optim.compression import (Int8State, compress_int8_ef,
                                     init_int8_state)

SCHEDULES = ("baseline", "priority", "fixed", "priority+partition",
             "priority+partition+pipeline")
COMPRESSIONS = (None, "bf16", "int8_ef")

# Fig. 15: 30MB micro-ops sit in the flat bottom of the partition-size sweep
DEFAULT_PARTITION_BYTES = 30e6


@dataclass(frozen=True)
class ReduceConfig:
    schedule: str = "baseline"
    partition_bytes: float = DEFAULT_PARTITION_BYTES
    compression: Optional[str] = None     # None | "bf16" | "int8_ef"

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; "
                             f"expected one of {SCHEDULES}")
        if self.compression not in COMPRESSIONS:
            raise ValueError(f"unknown compression {self.compression!r}; "
                             f"expected one of {COMPRESSIONS}")

    @property
    def ordered(self) -> bool:
        return self.schedule != "baseline"

    @property
    def partitioned(self) -> bool:
        return "partition" in self.schedule


class ReduceState(NamedTuple):
    """Cross-step reducer state (today: the int8-EF residual)."""
    int8: Optional[Int8State]


def init_reduce_state(params, cfg: ReduceConfig) -> Optional[ReduceState]:
    """Per-parameter reducer state, or None when the reducer is stateless."""
    if cfg.compression == "int8_ef":
        return ReduceState(init_int8_state(params))
    return None


def n_chunks_for_bytes(grads, partition_bytes: float) -> int:
    """Uniform micro-op count for the flattened gradient vector (§4.2: no
    gradient-boundary bucketing — pure tensor partitioning)."""
    total = sum(l.size * jnp.dtype(l.dtype).itemsize
                for l in jax.tree.leaves(grads))
    return max(1, math.ceil(total / max(float(partition_bytes), 1.0)))


def shard_n_chunks(mesh, grads, specs, partition_bytes: float) -> int:
    """Micro-op count for the shard of ``grads`` that one device reduces
    under ``specs`` (``reduce_specs``).  The chunked psum partitions each
    device's own shard, so ``partition_bytes`` is a per-device micro-op
    size: an expert gradient split over ``ep`` devices counts 1/ep of its
    bytes, and the count does not change with the expert-parallel degree."""
    local = jax.tree.map(
        lambda g, s: jax.ShapeDtypeStruct(
            NamedSharding(mesh, s).shard_shape(g.shape), g.dtype),
        grads, specs)
    return n_chunks_for_bytes(local, partition_bytes)


def reduce_axes(mesh) -> tuple:
    """The DP mesh axes the gradient reduction runs over."""
    return axes.dp_axes(mesh)


# ---------------------------------------------------------------------------
# the per-device reduction body (runs inside shard_map)
# ---------------------------------------------------------------------------

def _reduce_shard(grads, int8_state, after, *, axes, cfg: ReduceConfig,
                  n_chunks: int):
    """Reduce (mean) ``grads`` over ``axes`` under schedule ``cfg``.

    Runs per-device on each device's shard.  Returns (reduced_grads,
    new_int8_state).  The int8 path assumes gradients enter replicated over
    ``axes`` (true for this repo's train step), so the devices reducing one
    shard agree on its quantization scale and the integer psum-mean
    dequantizes exactly like a local dequantize.
    """
    tok = after if cfg.ordered else None
    if cfg.compression == "bf16":
        g16 = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
        red = microop.prioritized_chunked_reduce(g16, axes, n_chunks,
                                                 after=tok, mean=True)
        red = jax.tree.map(lambda r, g: r.astype(g.dtype), red, grads)
        return red, int8_state
    if cfg.compression == "int8_ef":
        (qs, scales), new_state = compress_int8_ef(grads, int8_state)
        # sum in int32 (dp-many values in [-127,127] cannot overflow) and
        # dequantize with the shared scale — int8-EF numerics, though the
        # psum payload itself stays 4B/element (see module docstring)
        q32 = jax.tree.map(lambda q: q.astype(jnp.int32), qs)
        summed = microop.prioritized_chunked_reduce(q32, axes, n_chunks,
                                                    after=tok, mean=False)
        denom = 1
        for a in axes:
            denom *= lax.psum(1, a)
        red = jax.tree.map(
            lambda s, sc, g: (s.astype(jnp.float32) * sc / denom
                              ).astype(g.dtype),
            summed, scales, grads)
        return red, new_state
    red = microop.prioritized_chunked_reduce(grads, axes, n_chunks,
                                             after=tok, mean=True)
    return red, int8_state


# ---------------------------------------------------------------------------
# top-level entry: global grads -> shard_map -> reduced global grads
# ---------------------------------------------------------------------------

def reduce_gradients(mesh, grads, cfg: ReduceConfig, specs, *,
                     after: Optional[jax.Array] = None,
                     state: Optional[ReduceState] = None):
    """Explicit DP-axis gradient reduction under Lina's schedule.

    mesh:   the training mesh (on the 1-device ``core.moe.default_mesh``
            the collectives are trivial but the schedule still compiles).
    grads:  the global gradient pytree out of jit-level autodiff.
    specs:  PartitionSpec per gradient leaf over the axes the reduce does
            NOT run over (``launch.sharding.reduce_specs``): each device
            reduces only its own shard, and ``cfg.partition_bytes`` sizes
            the micro-ops of that shard (``shard_n_chunks``).
    after:  backward-a2a completion token (see ``backward_a2a_token``);
            ignored by ``baseline``.
    state:  ``ReduceState`` for int8-EF, else None.

    Returns (reduced_grads, new_state).
    """
    axes = tuple(a for a in reduce_axes(mesh) if a in mesh.axis_names)
    n_chunks = (shard_n_chunks(mesh, grads, specs, cfg.partition_bytes)
                if cfg.partitioned else 1)
    if after is None:
        after = jnp.zeros((), jnp.float32)
    int8_state = state.int8 if (state is not None and
                                cfg.compression == "int8_ef") else None
    if cfg.compression == "int8_ef" and int8_state is None:
        raise ValueError("schedule with int8_ef compression needs a "
                         "ReduceState (see init_reduce_state)")

    body = partial(_reduce_shard, axes=axes, cfg=cfg, n_chunks=n_chunks)
    st_spec = None if int8_state is None else Int8State(specs)
    red, new_int8 = shard_map(
        body, mesh=mesh,
        in_specs=(specs, st_spec, P()),
        out_specs=(specs, st_spec),
        check_vma=False,
    )(grads, int8_state, after)
    new_state = ReduceState(new_int8) if new_int8 is not None else state
    return red, new_state


def backward_a2a_token(grads, fwd_marker: Optional[jax.Array] = None):
    """The backward-a2a completion marker for ``after=``.

    Under SPMD the backward all-to-all's completion is observable as a data
    dependency: every expert-weight gradient leaf is computed *from tokens
    received over the backward a2a*, so a zero-valued scalar derived from
    those leaves is available exactly when the a2a has completed.  The
    forward marker threaded out of ``core.moe`` (``MoEOutput.a2a_token`` →
    ``ModelOutput.a2a_marker``) is folded in as well, pinning the reduce
    after the forward a2a micro-ops too.

    Returns None when the gradient tree has no MoE leaves and no marker was
    given (dense model: nothing to yield to).
    """
    from repro.core.moe import MoEParams
    nodes = jax.tree.leaves(grads,
                            is_leaf=lambda x: isinstance(x, MoEParams))
    moe_leaves = [l for n in nodes if isinstance(n, MoEParams)
                  for l in jax.tree.leaves(n)]
    if not moe_leaves and fwd_marker is None:
        return None
    tok = jnp.zeros((), jnp.float32)
    for l in moe_leaves:
        tok = tok + microop._token_of(l)     # single-sourced marker idiom
    if fwd_marker is not None:
        tok = tok + microop._token_of(fwd_marker)
    return tok
