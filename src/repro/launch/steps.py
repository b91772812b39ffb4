"""Jittable train/prefill/decode steps + ShapeDtypeStruct input specs for
every (arch × shape) dry-run cell.

``input_specs(cfg, shape)`` returns weak-type-correct, shardable stand-ins —
no device allocation — exactly what ``jax.jit(...).lower()`` needs.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.moe import default_mesh
from repro.core.serving import PlanArrays
from repro.core.placement import identity_plan
from repro.launch.sharding import reduce_specs
from repro.models import lm as lm_mod
from repro.models.lm import LMCache, LMParams, FRAME_DIM
from repro.optim.adamw import AdamWConfig, OptState, adamw_update, init_opt_state
from repro.optim import reduce as reduce_mod

SERVE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def batch_struct(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    out = {}
    if cfg.frontend == "audio_stub":
        out["frames"] = _sds((b, s, FRAME_DIM), jnp.bfloat16)
        if shape.kind == "train":
            out["labels"] = _sds((b, s), jnp.int32)
        return out
    if cfg.frontend == "vision_stub":
        st = s - cfg.n_patches
        out["tokens"] = _sds((b, st), jnp.int32)
        out["patches"] = _sds((b, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            out["labels"] = _sds((b, st), jnp.int32)
        return out
    out["tokens"] = _sds((b, s), jnp.int32)
    if shape.kind == "train":
        out["labels"] = _sds((b, s), jnp.int32)
    return out


def params_struct(cfg: ModelConfig) -> LMParams:
    return jax.eval_shape(partial(lm_mod.init_params, cfg),
                          jax.random.key(0))


def cache_struct(cfg: ModelConfig, shape: ShapeConfig) -> LMCache:
    return jax.eval_shape(partial(lm_mod.init_cache, cfg, shape.global_batch,
                                  shape.seq_len, SERVE_DTYPE))


def opt_struct(cfg: ModelConfig, opt_cfg: AdamWConfig) -> OptState:
    ps = params_struct(cfg)
    return jax.eval_shape(partial(init_opt_state, cfg=opt_cfg), ps)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, opt_cfg=None) -> dict:
    """All step inputs as ShapeDtypeStructs, keyed by step argument name."""
    specs = {"params": params_struct(cfg)}
    if shape.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig(state_dtype=cfg.opt_state_dtype)
        specs["opt_state"] = opt_struct(cfg, opt_cfg)
        specs["batch"] = batch_struct(cfg, shape)
    elif shape.kind == "prefill":
        specs["batch"] = batch_struct(cfg, shape)
    else:  # decode / long_decode: one new token against a seq_len cache
        specs["cache"] = cache_struct(cfg, shape)
        specs["token"] = _sds((shape.global_batch,), jnp.int32)
    return specs


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh, opt_cfg: Optional[AdamWConfig] = None,
                    *, lina: bool = True, fsdp: bool = True,
                    dispatch_backend: str = "scatter",
                    microbatches: int = 1,
                    schedule: Optional[str] = None,
                    partition_bytes: float = reduce_mod.DEFAULT_PARTITION_BYTES,
                    grad_compression: Optional[str] = None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` scans gradient accumulation over batch slices —
    the standard activation-memory lever (and the granularity at which
    Lina's chunked DP reduction overlaps the next microbatch's compute).

    ``schedule`` selects Lina's §4 gradient-reduction schedule
    (``optim.reduce.SCHEDULES``): the DP-axis reduce becomes an explicit
    chunked psum (``core.microop.prioritized_chunked_reduce``, entered via
    ``optim.reduce.reduce_gradients``'s shard_map) ordered after the
    backward-a2a completion token that
    ``core.moe`` threads out of the shard_map body and ``models.lm``
    carries to the step as ``ModelOutput.a2a_marker``.  ``None`` keeps the
    legacy implicit reduction (whatever XLA's partitioner emits).  With
    ``priority+partition+pipeline`` and ``microbatches > 1`` the chunked
    reduce of each microbatch is interleaved with the next microbatch's
    gradient compute inside an unrolled ``lax.scan``.

    ``grad_compression`` (``"bf16"`` | ``"int8_ef"``) wraps the chunked
    reduce; int8 error feedback is stateful, which changes the signature to
    (params, opt_state, batch, reduce_state) ->
    (params, opt_state, metrics, reduce_state).
    """
    opt_cfg = opt_cfg or AdamWConfig(state_dtype=cfg.opt_state_dtype)
    if grad_compression is not None and schedule is None:
        raise ValueError("grad_compression requires an explicit schedule "
                         f"(one of {reduce_mod.SCHEDULES})")
    rcfg = None
    if schedule is not None:
        rcfg = reduce_mod.ReduceConfig(schedule=schedule,
                                       partition_bytes=partition_bytes,
                                       compression=grad_compression)
    stateful = grad_compression == "int8_ef"
    reduce_mesh = mesh if mesh is not None else default_mesh()
    pipelined = (rcfg is not None and microbatches > 1 and
                 schedule == "priority+partition+pipeline")

    def loss_fn(params, batch):
        out = lm_mod.forward_train(mesh, cfg, params, batch, lina=lina,
                                   dispatch_backend=dispatch_backend,
                                   fsdp=fsdp)
        return out.loss, out

    def explicit_reduce(grads, marker, rstate):
        # order the reduce micro-ops after the backward a2a: expert-weight
        # grad leaves are computed from tokens received over it, and the
        # forward marker pins the forward a2a micro-ops too
        after = reduce_mod.backward_a2a_token(grads, marker)
        specs = reduce_specs(cfg, reduce_mesh, grads)
        return reduce_mod.reduce_gradients(reduce_mesh, grads, rcfg, specs,
                                           after=after, state=rstate)

    def grads_of(params, batch, rstate):
        """Returns (grads, loss, aux, rstate) with grads already reduced
        when an explicit schedule is configured."""
        if microbatches <= 1:
            (loss, out), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            if rcfg is not None:
                grads, rstate = explicit_reduce(grads, out.a2a_marker, rstate)
            return grads, loss, out.aux_loss, rstate

        mb = {k: v.reshape(microbatches, v.shape[0] // microbatches,
                           *v.shape[1:]) for k, v in batch.items()}
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        z = jnp.zeros(())

        if pipelined:
            def acc_step(carry, mbatch):
                g_acc, l_acc, a_acc, rs = carry
                (l, out), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mbatch)
                # reduce THIS microbatch's chunks now; unrolled, so XLA's
                # async-collective scheduler overlaps them with the next
                # microbatch's backward compute (psum is linear: per-
                # microbatch mean-reduction sums to the full-batch one)
                g, rs = explicit_reduce(g, out.a2a_marker, rs)
                g_acc = jax.tree.map(lambda a, b_: a + b_, g_acc, g)
                return (g_acc, l_acc + l, a_acc + out.aux_loss, rs), None

            (grads, loss, aux, rstate), _ = jax.lax.scan(
                acc_step, (zeros, z, z, rstate), mb, unroll=microbatches)
        else:
            def acc_step(carry, mbatch):
                g_acc, l_acc, a_acc, m_acc = carry
                (l, out), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mbatch)
                g_acc = jax.tree.map(lambda a, b_: a + b_, g_acc, g)
                return (g_acc, l_acc + l, a_acc + out.aux_loss,
                        m_acc + out.a2a_marker), None

            (grads, loss, aux, marker), _ = jax.lax.scan(
                acc_step, (zeros, z, z, jnp.zeros((), jnp.float32)), mb)
            if rcfg is not None:
                grads, rstate = explicit_reduce(grads, marker, rstate)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        return grads, loss / microbatches, aux / microbatches, rstate

    def finish(params, opt_state, grads, loss, aux):
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, "aux_loss": aux, **om}
        return params, opt_state, metrics

    if stateful:
        def train_step(params, opt_state, batch, reduce_state):
            grads, loss, aux, reduce_state = grads_of(params, batch,
                                                      reduce_state)
            params, opt_state, metrics = finish(params, opt_state, grads,
                                                loss, aux)
            return params, opt_state, metrics, reduce_state
    else:
        def train_step(params, opt_state, batch):
            grads, loss, aux, _ = grads_of(params, batch, None)
            return finish(params, opt_state, grads, loss, aux)

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh, *, serve_plan=None,
                      serve_top_k=None, fsdp: bool = True):
    def prefill_step(params, batch):
        out = lm_mod.forward_prefill(mesh, cfg, params, batch,
                                     serve_plan=serve_plan,
                                     serve_top_k=serve_top_k, fsdp=fsdp)
        return out.logits
    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh, *, serve_plan=None,
                     serve_top_k=None, fsdp: bool = True):
    def decode_step(params, cache, token):
        return lm_mod.decode_step(mesh, cfg, params, cache, token,
                                  serve_plan=serve_plan,
                                  serve_top_k=serve_top_k, fsdp=fsdp)
    return decode_step


def make_serve_plan(cfg: ModelConfig, mesh) -> Optional[PlanArrays]:
    """Identity plan sized to the EP group (popularity plans replace it at
    runtime via the Server)."""
    if not cfg.moe.enabled:
        return None
    from repro.launch.mesh import ep_size
    ep = ep_size(mesh)
    if cfg.moe.n_experts % ep:
        return None
    pack = max(1, cfg.moe.n_experts // ep)
    return PlanArrays.from_plan(
        identity_plan(cfg.moe.n_experts, ep, max_pack=max(pack, 2)))
