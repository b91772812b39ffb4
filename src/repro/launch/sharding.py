"""Sharding rules: PartitionSpec trees for params, optimizer state, batches
and caches — FSDP over (`pod`,`data`), tensor/expert parallel over `model`.

Rules (see DESIGN.md §3):
  column-parallel weights  [..., d, f]  -> P(..., dp, "model")
  row-parallel weights     [..., f, d]  -> P(..., "model", dp)
  experts                  [E, d, f]    -> P("model", None, dp)  (EP + ZeRO-3)
  embeddings               [V, d]       -> P("model", None)      (vocab-sharded)
  SSM/RWKV stacks                       -> FSDP only (no TP; see DESIGN)
Specs are passed through ``safe_spec`` at use so non-divisible dims degrade
to replication instead of erroring (e.g. 56 heads on a 16-way model axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import axes
from repro.models.attention import AttnParams, KVCache
from repro.models.lm import (FFNParams, GroupParams, HybridParams, LMCache,
                             LMParams, RWKVStack)
from repro.models.layers import safe_spec
from repro.optim.adamw import OptState


def _dp(mesh):
    return axes.dp_axes(mesh)


def _tp(mesh):
    return axes.mp_axes(mesh)


def _attn_specs(dp, tp, lead) -> AttnParams:
    n = (None,) * lead
    return AttnParams(
        wq=P(*n, dp, tp), wk=P(*n, dp, tp), wv=P(*n, dp, tp),
        wo=P(*n, tp, dp),
        bq=P(*n, tp), bk=P(*n, tp), bv=P(*n, tp),
        q_norm=P(*n, None), k_norm=P(*n, None),
    )


def _ffn_specs(dp, tp, lead) -> FFNParams:
    n = (None,) * lead
    return FFNParams(w_in=P(*n, dp, tp), w_up=P(*n, dp, tp),
                     w_out=P(*n, tp, dp))


def param_specs(cfg: ModelConfig, mesh, params: LMParams) -> LMParams:
    """Build the PartitionSpec tree mirroring ``params``' structure.

    With ``cfg.tensor_parallel == False`` every mesh axis acts as a data/
    FSDP axis (pure ZeRO-3 — the right regime for sub-1B models where 16-way
    TP only buys collectives; §Perf hillclimb)."""
    if not cfg.tensor_parallel:
        dp = _dp(mesh) + _tp(mesh)
        tp = None
    else:
        dp = _dp(mesh)
        tp = _tp(mesh)

    if isinstance(params.stack, HybridParams):
        mamba_specs = jax.tree.map(lambda a: None, params.stack.mamba)
        mamba_specs = type(params.stack.mamba)(
            in_proj=P(None, dp, None), conv_w=P(None, None, None),
            conv_b=P(None, None), a_log=P(None, None), d_skip=P(None, None),
            dt_bias=P(None, None), norm=P(None, None),
            out_proj=P(None, dp, None))
        stack = HybridParams(
            mamba=mamba_specs, ln_m=P(None, None),
            shared_attn=_attn_specs(dp, tp, 0), shared_ffn=_ffn_specs(dp, tp, 0),
            ln_s1=P(None), ln_s2=P(None))
    elif isinstance(params.stack, RWKVStack):
        blk = type(params.stack.blocks)(
            mu=P(None, None, None), w0=P(None, None),
            w_a=P(None, dp, None), w_b=P(None, None, None),
            wk=P(None, dp, None), wv=P(None, dp, None),
            wr=P(None, dp, None), wg=P(None, dp, None),
            u=P(None, None), wo=P(None, dp, None), ln_x=P(None, None),
            mu_c=P(None, None, None), ck=P(None, dp, None),
            cv=P(None, dp, None), cr=P(None, dp, None))
        stack = RWKVStack(blocks=blk, ln1=P(None, None), ln2=P(None, None))
    else:
        gp = params.stack
        n_dense = gp.ffn is not None
        has_tp = axes.TP in mesh.axis_names
        hid = ((axes.TP,) + dp) if has_tp else dp
        stack = GroupParams(
            attn=_attn_specs(dp, tp, 2),
            ln1=P(None, None, None), ln2=P(None, None, None),
            ffn=_ffn_specs(dp, tp, 2) if n_dense else None,
            moe=type(gp.moe)(
                router=P(None, dp, None),
                wi=P(None, axes.EP_AXIS, None, hid),
                wu=P(None, axes.EP_AXIS, None, hid),
                wo=P(None, axes.EP_AXIS, hid, None),
            ) if gp.moe is not None else None,
            shared=_ffn_specs(dp, tp, 1) if gp.shared is not None else None,
        )

    return LMParams(
        embed=P(tp if tp else dp, None),
        patch_proj=P(None, None) if params.patch_proj is not None else None,
        frame_proj=P(None, None) if params.frame_proj is not None else None,
        mask_emb=P(None) if params.mask_emb is not None else None,
        stack=stack,
        final_norm=P(None),
        lm_head=P(dp, tp) if params.lm_head is not None else None,
    )


def _prune(spec_tree, param_tree):
    """Match spec tree to params (drop specs where params are None)."""
    return jax.tree.map(lambda s, p: s, spec_tree, param_tree)


def shardings_for(mesh, spec_tree, value_tree):
    """Specs -> NamedShardings, degrading non-divisible dims safely."""
    def one(spec, val):
        if val is None:        # spec present but param absent (e.g. no bias)
            return None
        if spec is None:
            spec = P()
        return NamedSharding(mesh, safe_spec(mesh, spec, val.shape))
    return jax.tree.map(one, spec_tree, value_tree,
                        is_leaf=lambda s: isinstance(s, P) or s is None)


def opt_state_specs(param_spec_tree, opt_state: OptState) -> OptState:
    return OptState(step=P(), m=param_spec_tree, v=param_spec_tree)


def _strip_axes(spec, names):
    """``spec`` with the mesh axes in ``names`` removed (those dims then
    replicate over them)."""
    if spec is None or not isinstance(spec, P):
        return spec
    out = []
    for e in spec:
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in names)
            out.append(kept if kept else None)
        else:
            out.append(None if e in names else e)
    return P(*out)


def reduce_specs(cfg: ModelConfig, mesh, grads):
    """Per-leaf PartitionSpec for the explicit data-parallel gradient
    reduce (``optim.reduce.reduce_gradients``): the training layout without
    the axes the reduce runs over.  Each device then reduces only its own
    model/tp shard; replicating every gradient instead would gather the
    expert gradients across the expert axis."""
    dp = set(axes.dp_axes(mesh))

    def one(spec, g):
        if g is None:
            return None
        return safe_spec(mesh, _strip_axes(spec or P(), dp), g.shape)
    return jax.tree.map(one, param_specs(cfg, mesh, grads), grads,
                        is_leaf=lambda s: isinstance(s, P) or s is None)


def serve_uses_fsdp(cfg: ModelConfig, mesh, budget_bytes: float = 10e9) -> bool:
    ep = 1
    for a, s in zip(mesh.axis_names, mesh.devices.shape):
        if a in axes.MP_AXES:
            ep *= s
    return 2.0 * cfg.param_count() / ep > budget_bytes


def serve_param_specs(cfg: ModelConfig, mesh, params: LMParams,
                      budget_bytes: float = 10e9) -> LMParams:
    """Serving shards weights over the model/tp axes ONLY (replicated across
    dp) when the per-device footprint fits — per-step ZeRO re-gathers are a
    training trick, not a serving one.  Falls back to the training (FSDP)
    specs for models too large for TP-only residency (llama4, qwen2-72b)."""
    specs = param_specs(cfg, mesh, params)
    ep = 1
    for a, s in zip(mesh.axis_names, mesh.devices.shape):
        if a in axes.MP_AXES:
            ep *= s
    per_dev = 2.0 * cfg.param_count() / ep  # bf16 serve weights
    if per_dev > budget_bytes:
        return specs
    dp_names = set(axes.DP_AXES)
    return jax.tree.map(lambda s: _strip_axes(s, dp_names), specs,
                        is_leaf=lambda s: isinstance(s, P) or s is None)


def batch_specs(cfg: ModelConfig, mesh, shape: ShapeConfig) -> dict:
    dp = _dp(mesh)
    from repro.launch.mesh import dp_size
    bs = dp if shape.global_batch % dp_size(mesh) == 0 else None
    out = {}
    if cfg.frontend == "audio_stub":
        out["frames"] = P(bs, None, None)
        if shape.kind == "train":
            out["labels"] = P(bs, None)
    else:
        out["tokens"] = P(bs, None)
        if shape.kind == "train":
            out["labels"] = P(bs, None)
        if cfg.frontend == "vision_stub":
            out["patches"] = P(bs, None, None)
    return out


def cache_specs(cfg: ModelConfig, mesh, cache: LMCache) -> LMCache:
    dp = _dp(mesh)
    b = cache.pos.shape[0]
    from repro.launch.mesh import dp_size
    bs = dp if b % dp_size(mesh) == 0 else None

    kv = mamba = rwkv = None
    if cache.kv is not None:
        # KV cache: batch over dp, SEQUENCE over the tp axes (kv-head counts
        # are rarely divisible by 16; a 32k x 128-batch cache at 80 layers is
        # ~1.4TB, so the seq dim must shard — decode attention then runs
        # sequence-parallel with a psum over `model`, which XLA's SPMD
        # partitioner derives from this constraint).
        lead = cache.kv.k.ndim - 4
        kv = KVCache(*(P(*(None,) * lead, bs, _tp(mesh), None, None)
                       for _ in range(2)))
    if cache.mamba is not None:
        mamba = type(cache.mamba)(
            h=P(None, bs, None, None, None), conv=P(None, bs, None, None))
    if cache.rwkv is not None:
        rwkv = type(cache.rwkv)(
            s=P(None, bs, None, None, None), x_tm=P(None, bs, None),
            x_cm=P(None, bs, None))
    return LMCache(kv, mamba, rwkv, P(bs))
