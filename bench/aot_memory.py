#!/usr/bin/env python3
"""Compile a training cell's step for a described TPU v5e and print its
memory analysis, without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/aot_memory.py \
        transformer-xl-moe 4 8 512 1
    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/aot_memory.py \
        gpt2-moe 12 8 512 4

Arguments: configuration, layers, batch, sequence, chips.  The step is the
one ``Trainer`` builds (schedule ``priority+partition``), with the Pallas
kernels lowered through Mosaic as on the chip.  The bytes printed are per
device: arguments (parameters and AdamW moments), outputs (the new state,
which the step does not alias to its inputs) and temporaries.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv) -> int:
    name, layers, batch, seq, chips = argv[0], *map(int, argv[1:5])
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import ops, tiling
    ops._interpret = lambda: False
    tiling.default_interpret = lambda: False
    from repro.configs import get_config
    from repro.core import axes
    from repro.data import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.models import lm as lm_mod
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.runtime import Trainer, TrainerConfig

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devs = np.array(topo.devices[:chips])
    mesh = make_mesh((1, chips), (axes.DATA, axes.MODEL), devices=devs)
    cfg = get_config(name)
    cfg = dataclasses.replace(cfg, n_layers=layers,
                              moe=dataclasses.replace(cfg.moe,
                                                      compute_backend="pallas"))
    opt = AdamWConfig()
    tr = Trainer(cfg, DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                 global_batch=batch),
                 opt, TrainerConfig(steps=1, schedule="priority+partition"),
                 mesh=mesh)
    ps = jax.eval_shape(partial(lm_mod.init_params, cfg), jax.random.PRNGKey(0))
    os_ = jax.eval_shape(partial(init_opt_state, cfg=opt), ps)
    p_in = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                           sharding=sh),
                        ps, tr._param_sh)
    o_in = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                           sharding=sh),
                        os_, tr._opt_sh)
    bsh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    b_in = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=bsh)
            for k in ("tokens", "labels")}
    compiled = tr.step_fn.lower(p_in, o_in, b_in).compile()
    ma = compiled.memory_analysis()
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(ps))
    gib = 2 ** 30
    print(f"{name} layers={layers} batch={batch}x{seq} chips={chips} "
          f"params={n_params} per device: arguments "
          f"{ma.argument_size_in_bytes / gib:.2f} GiB, outputs "
          f"{ma.output_size_in_bytes / gib:.2f} GiB, aliased "
          f"{ma.alias_size_in_bytes / gib:.2f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / gib:.2f} GiB, total "
          f"{(ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / gib:.2f} GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
