"""Shared primitives: norms, RoPE, initializers, sharding helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import axes


def dense_init(key, shape, scale_axis: int = 0, dtype=jnp.float32):
    scale = shape[scale_axis] ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def rope(q, k, positions, theta: float = 10_000.0):
    """Rotary embeddings.  q/k: [..., S, H, hd]; positions: [..., S]."""
    hd = q.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[..., :, None].astype(jnp.float32) * freqs   # [..., S, hd/2]
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def ffn_branch(x, w_in, w_up, w_out, ffn_type: str):
    """The bare dense-FFN math (no sharding hints): swiglu or gelu.

    Single source of truth for the dense branch so the shortcut-connected
    MoE variant (ScMoE — the branch fused into ``core.moe._moe_shard_body``
    under the a2a shadow) and the outer shared-expert add compute the exact
    same function; the numerical-equivalence tests rely on that.
    """
    h = x @ w_in
    if ffn_type == "swiglu":
        h = jax.nn.silu(h) * (x @ w_up)
    else:
        h = jax.nn.gelu(h)
    return h @ w_out


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(mesh.shape).get(a, 1)
    return n


def safe_spec(mesh, spec: P, shape) -> P:
    """Drop mesh axes from dims they do not divide (e.g. 56 heads on a
    16-way `model` axis) so constraints never force padded shardings."""
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is not None and dim % axis_size(mesh, axes) != 0:
            axes = None
        out.append(axes)
    return P(*out)


def constrain(x, mesh, spec: P):
    """Sharding hint; no-op off-mesh (CPU smoke tests on 1 device)."""
    if mesh is None or mesh.size == 1:
        return x
    spec = safe_spec(mesh, spec, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def dp_axes(mesh) -> tuple:
    return axes.dp_axes(mesh)


def tp_axes(mesh):
    if mesh is not None and axes.TP in mesh.axis_names:
        return axes.MP_AXES
    return axes.MODEL
