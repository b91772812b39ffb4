"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at gpt2-moe widths in bf16.

Nothing runs on a chip: the TPU compiler shipped with jaxlib compiles each
kernel for a ``v5e:2x2`` topology that is described, not attached, and
refuses what Mosaic would refuse on the device (unaligned blocks, in-kernel
ops it cannot lower, VMEM over the limit).  The topology is described
inside a module-scoped fixture, never at import, so every pytest-xdist
worker collects the same tests and only the worker running this file
loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import GPT2_MOE
from repro.core.gating import capacity
from repro.kernels.dispatch import combine_rows, dispatch_rows, weighted_route
from repro.kernels.moe_ffn import grouped_ffn, grouped_matmul
from repro.kernels.topk_gating import topk_gating_fused, topk_positions

T = 2048                                   # tokens entering one MoE layer
D = GPT2_MOE.d_model                       # 768
F = GPT2_MOE.d_ff                          # 3072
E = GPT2_MOE.moe.n_experts                 # 16
K = GPT2_MOE.moe.top_k                     # 2
CAP = capacity(T, E, K, GPT2_MOE.moe.capacity_factor)
R = E * CAP                                # dispatch rows
RW = 4                                     # replica-table width

KERNELS = ("topk_gating_fused", "topk_positions", "weighted_route",
           "dispatch_rows", "combine_rows", "grouped_ffn", "grouped_matmul")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it (the
    # reset drops a cache this process may already have opened)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _case(name, sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    return {
        "topk_gating_fused": (
            lambda x, r: topk_gating_fused(x, K, router=r, interpret=False),
            (s((T, D), bf), s((D, E), bf))),
        "topk_positions": (
            lambda i: topk_positions(i, E, interpret=False),
            (s((T, K), i32),)),
        "weighted_route": (
            lambda i, p, c, so: weighted_route(i, p, c, so, CAP,
                                               interpret=False),
            (s((T, K), i32), s((T, K), i32), s((E, RW), i32),
             s((E, RW), i32))),
        "dispatch_rows": (
            lambda x, src, w: dispatch_rows(x, src, w, interpret=False),
            (s((T, D), bf), s((R,), i32), s((R,), f32))),
        "combine_rows": (
            lambda b, rows, w: combine_rows(b, rows, w, interpret=False),
            (s((R, D), bf), s((T, K), i32), s((T, K), f32))),
        "grouped_ffn": (
            lambda x, wi, wo: grouped_ffn(x, wi, None, wo, ffn_type="gelu",
                                          interpret=False),
            (s((E, CAP, D), bf), s((E, D, F), bf), s((E, F, D), bf))),
        "grouped_matmul": (
            lambda a, b: grouped_matmul(a, b, interpret=False),
            (s((E, CAP, D), bf), s((E, D, F), bf))),
    }[name]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_natively_for_v5e(name, one_chip):
    fn, args = _case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program as a Mosaic custom call, not a fallback,
    # and the call carries the kernel's name (what a device trace shows)
    calls = [ln.split("=")[0].strip() for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert calls and all(re.fullmatch(rf"%{name}(\.\d+)?", c)
                         for c in calls), calls
