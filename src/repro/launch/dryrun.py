import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
512 placeholder devices; print memory/cost analysis; extract roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k [--multi-pod] [--json results/dryrun/...json]

The two lines above MUST stay the first statements in this module (jax locks
the device count on first init).
"""
import argparse
import json
import re
import sys
import time

import jax
import numpy as np

from repro.configs import (REGISTRY, SHAPES, V5E, applicable_shapes,
                           get_config, skip_reason)
from repro.core import axes as ax
from repro.launch.mesh import (make_production_mesh, arch_mesh, dp_size,
                               ep_size, mesh_context)
from repro.launch.sharding import (batch_specs, cache_specs, opt_state_specs,
                                   param_specs, serve_param_specs,
                                   shardings_for)
from repro.launch.steps import (input_specs, make_decode_step,
                                make_prefill_step, make_serve_plan,
                                make_train_step)

from repro.launch.analytic import analytic_cost
from repro.launch.hlo_analysis import collective_summary


def roofline_terms(flops_global: float, bytes_global: float,
                   coll_bytes_per_dev: float, n_chips: int, hw=V5E) -> dict:
    """The three terms (seconds): compute/memory terms from the analytic
    model (global / chips); collective term from the trip-count-corrected
    per-device HLO wire bytes (the HLO module is the per-device SPMD
    program, so its collective bytes are already per-chip)."""
    return {
        "compute_s": flops_global / (n_chips * hw.peak_flops),
        "memory_s": bytes_global / (n_chips * hw.hbm_bw),
        "collective_s": coll_bytes_per_dev / (hw.ici_links * hw.ici_bw),
        "collective_s_single_link": coll_bytes_per_dev / hw.ici_bw,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, lina: bool = True,
             seq_parallel: bool = True, microbatches: int = 1,
             cache_batch_only: bool = False, dp_only: bool = False,
             kv_split: bool = False, tag: str = "",
             verbose: bool = True) -> dict:
    import dataclasses
    cfg = dataclasses.replace(get_config(arch), seq_parallel=seq_parallel,
                              tensor_parallel=not dp_only)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skip", "reason": reason}

    # the physical production mesh, re-viewed with an expert/tp split when
    # the arch's expert count does not divide the 16-way model axis
    mesh = arch_mesh(cfg, multi_pod=multi_pod)
    if kv_split and cfg.n_kv_heads and 16 % cfg.n_kv_heads == 0:
        # decode hillclimb: split `model` into (kv-heads x seq) so the KV
        # cache shards fully AND the per-step cache update stays local
        import jax.sharding as jsh
        from repro.launch.mesh import auto_axes
        kvh = cfg.n_kv_heads
        shp = ((2, 16, kvh, 16 // kvh) if multi_pod
               else (16, kvh, 16 // kvh))
        names = ax.MESH_AXES if multi_pod else ax.MESH_AXES[1:]
        mesh = jsh.Mesh(mesh.devices.reshape(shp), names,
                        axis_types=auto_axes(len(names)))
    n_chips = mesh.size
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        pspec = param_specs(cfg, mesh, specs["params"])
    else:
        pspec = serve_param_specs(cfg, mesh, specs["params"])
    p_shard = shardings_for(mesh, pspec, specs["params"])

    t0 = time.time()
    with mesh_context(mesh):
        if shape.kind == "train":
            step = make_train_step(cfg, mesh, lina=lina, fsdp=True,
                                   microbatches=microbatches)
            o_shard = shardings_for(mesh, opt_state_specs(pspec,
                                                          specs["opt_state"]),
                                    specs["opt_state"])
            b_shard = shardings_for(mesh, batch_specs(cfg, mesh, shape),
                                    specs["batch"])
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(specs["params"], specs["opt_state"],
                                   specs["batch"])
        elif shape.kind == "prefill":
            from repro.launch.sharding import serve_uses_fsdp
            plan = make_serve_plan(cfg, mesh)
            step = make_prefill_step(cfg, mesh, serve_plan=plan,
                                     fsdp=serve_uses_fsdp(cfg, mesh))
            b_shard = shardings_for(mesh, batch_specs(cfg, mesh, shape),
                                    specs["batch"])
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:
            from repro.launch.sharding import serve_uses_fsdp
            plan = make_serve_plan(cfg, mesh)
            step = make_decode_step(cfg, mesh, serve_plan=plan,
                                    fsdp=serve_uses_fsdp(cfg, mesh))
            cspec = cache_specs(cfg, mesh, specs["cache"])
            if kv_split and cspec.kv is not None:
                from repro.models.attention import KVCache
                from jax.sharding import PartitionSpec as P
                lead = specs["cache"].kv.k.ndim - 4
                dpx = ax.DP_AXES if multi_pod else (ax.DATA,)
                # [.., B->dp, S->tp, KV->model, hd]
                kv = KVCache(*(P(*(None,) * lead, dpx, ax.TP, ax.MODEL, None)
                               for _ in range(2)))
                cspec = cspec._replace(kv=kv)
            if cache_batch_only and cspec.kv is not None:
                # hillclimb variant: KV cache sharded on batch only (no
                # sequence sharding over `model`)
                from repro.models.attention import KVCache
                from jax.sharding import PartitionSpec as P
                lead = specs["cache"].kv.k.ndim - 4
                dpx = ax.DP_AXES if multi_pod else (ax.DATA,)
                kv = KVCache(*(P(*(None,) * lead, dpx, None, None, None)
                               for _ in range(2)))
                cspec = cspec._replace(kv=kv)
            c_shard = shardings_for(mesh, cspec, specs["cache"])
            from jax.sharding import NamedSharding, PartitionSpec as P
            tok_spec = P(ax.DP_AXES if multi_pod else (ax.DATA,)) \
                if shape.global_batch % dp_size(mesh) == 0 else P(None)
            t_shard = NamedSharding(mesh, tok_spec)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, t_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(specs["params"], specs["cache"],
                                   specs["token"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_summary(hlo)
    ana = analytic_cost(cfg, shape)

    hlo_flops_dev = float(cost.get("flops", 0.0))       # loop-blind; reference
    hlo_bytes_dev = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(ana.flops_global, ana.hbm_bytes_global,
                           coll["total_wire_bytes"], n_chips)

    # MODEL_FLOPS per spec: 6ND (train) / 2ND (inference), N = active params
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill") else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * cfg.active_param_count() * tokens

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "status": "ok", "lina": lina,
        "seq_parallel": seq_parallel, "microbatches": microbatches,
        "tag": tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "analytic_flops_global": ana.flops_global,
        "analytic_hbm_bytes_global": ana.hbm_bytes_global,
        "hlo_flops_per_device_loopblind": hlo_flops_dev,
        "hlo_bytes_per_device_loopblind": hlo_bytes_dev,
        "collectives": coll,
        "memory_analysis": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_estimate": int(mem.argument_size_in_bytes
                                       + mem.temp_size_in_bytes),
        },
        "roofline": terms,
        "model_flops_global": float(model_flops),
        "useful_flops_ratio": float(model_flops / max(ana.flops_global, 1)),
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    result["dominant_term"] = dom
    result["roofline_fraction"] = terms["compute_s"] / max(
        terms["compute_s"], terms["memory_s"], terms["collective_s"])
    if verbose:
        print(f"== {arch} x {shape_name} on {result['mesh']} "
              f"({n_chips} chips) lina={lina} ==")
        print(f"memory_analysis: {result['memory_analysis']}")
        print(f"analytic: flops={ana.flops_global:.3e} "
              f"hbm={ana.hbm_bytes_global:.3e} ({ana.notes})")
        print(f"hlo(loop-blind ref): flops/dev={hlo_flops_dev:.3e} "
              f"bytes/dev={hlo_bytes_dev:.3e}")
        print(f"collectives(trip-corrected): {coll['counts']} -> "
              f"{coll['total_wire_bytes']/1e9:.3f} GB wire/dev")
        print(f"roofline: compute={terms['compute_s']*1e3:.2f}ms "
              f"memory={terms['memory_s']*1e3:.2f}ms "
              f"collective={terms['collective_s']*1e3:.2f}ms "
              f"dominant={dom} useful_ratio={result['useful_flops_ratio']:.2f} "
              f"fraction={result['roofline_fraction']:.3f}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-lina", action="store_true",
                    help="baseline schedule (single a2a, no micro-ops)")
    ap.add_argument("--no-sp", action="store_true",
                    help="disable sequence parallelism (paper-baseline mode)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--cache-batch-only", action="store_true",
                    help="decode: shard KV cache on batch only")
    ap.add_argument("--dp-only", action="store_true",
                    help="no tensor parallelism: all axes FSDP/data")
    ap.add_argument("--kv-split", action="store_true",
                    help="decode: split model axis into (kv-heads x seq)")
    ap.add_argument("--tag", default="", help="label for §Perf iterations")
    ap.add_argument("--json", default=None, help="append result to this file")
    args = ap.parse_args(argv)

    res = run_cell(args.arch, args.shape, args.multi_pod,
                   lina=not args.no_lina, seq_parallel=not args.no_sp,
                   microbatches=args.microbatches,
                   cache_batch_only=args.cache_batch_only,
                   dp_only=args.dp_only, kv_split=args.kv_split,
                   tag=args.tag)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "a") as f:
            f.write(json.dumps(res) + "\n")
    return 0 if res["status"] in ("ok", "skip") else 1


if __name__ == "__main__":
    sys.exit(main())
