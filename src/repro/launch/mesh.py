"""Production meshes.  Functions, not module constants, so importing never
touches jax device state (the dry-run must set XLA_FLAGS first).

Every mesh here uses ``Auto`` axis types: the shard_map bodies and the
partitioner-driven jit code in this repo rely on implicit (auto) sharding
propagation, not on JAX's explicit-sharding mode."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core import axes


def mesh_context(mesh):
    """Activate ``mesh`` as the ambient mesh (``with mesh_context(mesh):``)."""
    return jax.set_mesh(mesh)


def auto_axes(n_axes: int) -> tuple:
    """``axis_types`` for an all-``Auto`` mesh of ``n_axes`` axes."""
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    names = (axes.POD, axes.DATA, axes.MODEL) if multi_pod \
        else (axes.DATA, axes.MODEL)
    return jax.make_mesh(shape, names, axis_types=auto_axes(len(names)))


def make_mesh(shape, axes, devices=None):
    """Arbitrary (test-sized) mesh with the same axis conventions."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=auto_axes(len(axes)), devices=devices)


def dp_size(mesh) -> int:
    sizes = axes.axis_sizes(mesh)
    return sizes.get(axes.POD, 1) * sizes.get(axes.DATA, 1)


def ep_size(mesh) -> int:
    return axes.axis_sizes(mesh).get(axes.MODEL, 1)


def tp_axes(mesh):
    """The tensor-parallel axes: `model` plus the expert-slicing `tp` axis
    when present (archs whose expert count < 16)."""
    return axes.mp_axes(mesh)


def arch_mesh(cfg, *, multi_pod: bool = False):
    """The production mesh, re-viewed for the arch: when n_experts does not
    divide the 16-way model axis, split it into (model=ep, tp=16/ep) so the
    MoE a2a runs over `model` and experts are tensor-sliced over `tp`
    (DeepSpeed-MoE expert slicing).  Device order is preserved — this is the
    same physical 16x16 (or 2x16x16) mesh required by the dry-run."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    e = getattr(cfg.moe, "n_experts", 0)
    if not e or 16 % e != 0 or e >= 16:
        return mesh
    ep, tp = e, 16 // e
    shape = (2, 16, ep, tp) if multi_pod else (16, ep, tp)
    names = axes.MESH_AXES if multi_pod else axes.MESH_AXES[1:]
    import jax.sharding as jsh
    return jsh.Mesh(mesh.devices.reshape(shape), names,
                    axis_types=auto_axes(len(names)))
