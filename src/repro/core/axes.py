"""Canonical mesh-axis names — the single source of truth.

Every collective, PartitionSpec and mesh constructor in this repo names its
axes through these constants; ``repro.analysis.collectives`` lints the tree
and flags raw string literals in axis positions (``axis-literal``) as well
as axis names outside this module's vocabulary (``unbound-axis``), so a
typo'd ``psum`` axis is a CI failure instead of a runtime shard_map error.

Axis roles (see DESIGN / ROADMAP):
  POD    outer data-parallel axis across pods (multi-pod meshes only)
  DATA   data-parallel / FSDP axis within a pod
  MODEL  expert-parallel axis (the MoE a2a runs here) + tensor parallel
  TP     expert-slicing tensor-parallel split of MODEL (archs whose expert
         count does not fill the 16-way model axis)
"""
from __future__ import annotations

POD = "pod"
DATA = "data"
MODEL = "model"
TP = "tp"

# the full canonical vocabulary, in mesh-major order
MESH_AXES = (POD, DATA, MODEL, TP)

# role aliases used across core/optim/launch
EP_AXIS = MODEL            # expert-parallel: dispatch/combine a2a axis
DP_AXES = (POD, DATA)      # data-parallel axes (gradient reduction)
MP_AXES = (MODEL, TP)      # model-parallel axes (weight sharding)


def axis_sizes(mesh) -> dict:
    """{axis name: size} for ``mesh`` (empty for None)."""
    if mesh is None:
        return {}
    return dict(mesh.shape)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes present on ``mesh`` (() for None)."""
    if mesh is None:
        return ()
    return DP_AXES if POD in mesh.axis_names else (DATA,)


def mp_axes(mesh) -> tuple:
    """The model/tensor-parallel axes present on ``mesh``."""
    if mesh is None:
        return (MODEL,)
    return MP_AXES if TP in mesh.axis_names else (MODEL,)
