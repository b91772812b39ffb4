"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-moe-smoke \
        --steps 50 --batch 8 --seq 128 [--no-lina] [--ckpt-dir /tmp/ckpt]

Runs on whatever JAX finds: smoke-scale on CPU for tests, or on a TPU
(``--require-tpu`` refuses any other backend; ``--mesh`` lays the devices
out as data x model).
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from repro.configs import get_config
from repro.data import DataConfig
from repro.launch.device import enable_compile_cache, require_tpu
from repro.optim.adamw import AdamWConfig
from repro.optim.reduce import DEFAULT_PARTITION_BYTES
from repro.runtime import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--no-lina", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--schedule", default="implicit",
                    help="gradient-reduction schedule (optim.reduce."
                         "SCHEDULES); the default 'implicit' keeps XLA's "
                         "own DP reduction (explicit schedules add one "
                         "extra collective per step — use for the "
                         "ablation or with --grad-compression)")
    ap.add_argument("--partition-bytes", type=float,
                    default=DEFAULT_PARTITION_BYTES,
                    help="micro-op size for the partitioned schedules")
    ap.add_argument("--grad-compression", default=None,
                    choices=["bf16", "int8_ef"],
                    help="compress the DP reduce (bf16 cast or int8 with "
                         "error feedback)")
    ap.add_argument("--compute-backend", default=None,
                    choices=["auto", "xla", "pallas"],
                    help="MoE compute backend (MoEConfig.compute_backend): "
                         "Pallas kernels for gating/grouped FFN vs the XLA "
                         "einsum path; default keeps the arch config")
    ap.add_argument("--dispatch-backend", default="scatter",
                    choices=["einsum", "scatter", "pallas"],
                    help="token dispatch/combine backend "
                         "(core.dispatch.BACKENDS)")
    ap.add_argument("--n-microops", type=int, default=None,
                    help="a2a tensor-partition count (MoEConfig.n_microops);"
                         " non-divisors of the capacity resolve to the "
                         "largest valid divisor — the trainer logs the "
                         "requested value per step")
    ap.add_argument("--pipeline-ffn", dest="pipeline_ffn", default=None,
                    action="store_true",
                    help="pipeline expert FFN with a2a micro-ops (Fig. 8b)")
    ap.add_argument("--no-pipeline-ffn", dest="pipeline_ffn",
                    action="store_false",
                    help="baseline: one a2a, full FFN, one a2a")
    ap.add_argument("--shortcut", dest="shortcut", default=None,
                    action="store_true",
                    help="ScMoE shortcut-connected variant: dense branch "
                         "computes under the a2a shadow, summed into the "
                         "combine")
    ap.add_argument("--no-shortcut", dest="shortcut", action="store_false",
                    help="disable the shortcut variant even if the arch "
                         "config enables it")
    ap.add_argument("--mesh", default=None,
                    help="data x model mesh, e.g. 2x4 (needs that many "
                         "devices; on CPU force them with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (resume from its newest "
                         "checkpoint); checkpoints are off without it")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write the per-step metrics log (JSON rows)")
    ap.add_argument("--trace-dir", default=None,
                    help="enable span tracing and export the artifact set "
                         "(trace.json Chrome trace for Perfetto, spans.json, "
                         "metrics.prom/.json) into this directory")
    ap.add_argument("--jax-profile-dir", default=None,
                    help="capture a jax.profiler trace window "
                         "(steps 2..5) into this TensorBoard logdir — the "
                         "device-time fwd/bwd split the host spans cannot "
                         "see; fails the run if the capture cannot start")
    ap.add_argument("--require-tpu", action="store_true",
                    help="fail unless JAX's devices are TPUs")
    args = ap.parse_args(argv)
    if args.require_tpu:
        require_tpu()
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    cfg = get_config(args.arch)
    if args.compute_backend is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         compute_backend=args.compute_backend))
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 10, 1),
                          state_dtype=cfg.opt_state_dtype)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every, lina=not args.no_lina,
                         microbatches=args.microbatches, seed=args.seed,
                         schedule=None if args.schedule == "implicit"
                         else args.schedule,
                         partition_bytes=args.partition_bytes,
                         grad_compression=args.grad_compression,
                         dispatch_backend=args.dispatch_backend,
                         n_microops=args.n_microops,
                         pipeline_ffn=args.pipeline_ffn,
                         shortcut=args.shortcut)
    mesh = None
    if args.mesh:
        from repro.core import axes
        from repro.launch.mesh import make_mesh
        dp_n, ep_n = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((dp_n, ep_n), (axes.DATA, axes.MODEL))
    from repro.obs import ObsContext, StepProfiler
    obs = ObsContext.enabled() if args.trace_dir else ObsContext.disabled()
    trainer = Trainer(cfg, data_cfg, opt_cfg, tcfg, mesh=mesh, obs=obs)
    profiler = StepProfiler(args.jax_profile_dir) \
        if args.jax_profile_dir else None

    def log(step, m):
        if profiler is not None:
            profiler.on_step(step)
        if step % tcfg.log_every == 0:
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"aux {m['aux_loss']:.4f}  gnorm {m['grad_norm']:.3f}",
                  flush=True)

    trainer.run(on_step=log)
    if profiler is not None:
        profiler.close()
        print(f"jax profiler logdir: {args.jax_profile_dir}")
    if trainer.packing_decision:
        print(f"expert packing: {trainer.packing_decision}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(trainer.metrics_log, f)
    if args.trace_dir:
        paths = obs.export(args.trace_dir)
        print(f"trace artifacts: {paths['trace']} (open in "
              f"ui.perfetto.dev), {paths['spans']}, {paths['prom']}")
    first, last = trainer.metrics_log[0]["loss"], trainer.metrics_log[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
