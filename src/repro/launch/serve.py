"""Serving driver: profile expert-selection paths, then serve a request
trace through the continuous-batching engine with Lina's two-phase
popularity scheduling (queue -> prefill/decode micro-batches -> plan cache
-> distributed dispatch).  Each request generates ``--max-new-tokens``
tokens through the incremental KV-cache decode path; pass 0 for the
score-only (single-prefill) mode.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2-moe-smoke \
        --requests 24 --seq 64 --rate 20 --max-new-tokens 8 \
        [--policy uniform|lina] [--autoscale] [--workload drift] [--warmup]

``--workload`` picks a ``repro.sched.workloads`` scenario (drifting Zipf
topic mixture, flash crowd, diurnal tide, ...) instead of the stationary
Poisson trace; ``--autoscale`` attaches the telemetry-driven controller
(``repro.sched``) so per-layer placement adapts to the traffic between
micro-batches; ``--warmup`` pre-traces the (batch-bucket, min-replicas)
compile grid before the first request arrives.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.device import enable_compile_cache, require_tpu
from repro.models import lm as lm_mod
from repro.obs import ObsContext
from repro.runtime.engine import (EngineConfig, ServingEngine, simulate,
                                  summarize_results)
from repro.runtime.server import MoEServer, ServerConfig, profile_from_training
from repro.sched import (AdaptiveScheduler, ControllerConfig, SCENARIOS,
                         get_trace)

import jax


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=20,
                    help="number of requests in the Poisson trace")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean arrival rate (requests per virtual second)")
    ap.add_argument("--profile-batches", type=int, default=5)
    ap.add_argument("--batch-tokens", type=int, default=256,
                    help="engine micro-batch token budget")
    ap.add_argument("--batch-requests", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=8,
                    help="tokens to generate per request via incremental "
                         "decode (0 = score-only prefill)")
    ap.add_argument("--path-len", type=int, default=3)
    ap.add_argument("--policy", default="lina", choices=["lina", "uniform"])
    ap.add_argument("--compute-backend", default=None,
                    choices=["auto", "xla", "pallas"],
                    help="MoE compute backend for every serve-path layer "
                         "(fused gating + slot dispatch/combine + grouped "
                         "expert FFN on 'pallas'); default keeps the arch "
                         "config")
    ap.add_argument("--no-plan-cache", action="store_true",
                    help="ablation: re-plan every layer of every batch")
    ap.add_argument("--n-microops", type=int, default=None,
                    help="a2a tensor-partition count (MoEConfig.n_microops) "
                         "for the profiling forward passes; non-divisors "
                         "resolve to the largest valid divisor")
    ap.add_argument("--pipeline-ffn", dest="pipeline_ffn", default=None,
                    action="store_true",
                    help="pipeline expert FFN with a2a micro-ops in the "
                         "profiling forward passes")
    ap.add_argument("--no-pipeline-ffn", dest="pipeline_ffn",
                    action="store_false",
                    help="baseline a2a -> FFN -> a2a (no micro-op pipeline)")
    ap.add_argument("--shortcut", dest="shortcut", default=None,
                    action="store_true",
                    help="ScMoE shortcut-connected variant: allocate the "
                         "dense shortcut branch and fuse it under the a2a "
                         "shadow on training-style forwards (serve decode "
                         "adds the same branch outside the plan dispatch)")
    ap.add_argument("--no-shortcut", dest="shortcut", action="store_false",
                    help="disable the shortcut variant even if the arch "
                         "config enables it")
    ap.add_argument("--workload", default=None,
                    choices=sorted(SCENARIOS),
                    help="trace scenario (repro.sched.workloads); default "
                         "is a stationary Poisson trace")
    ap.add_argument("--autoscale", action="store_true",
                    help="attach the telemetry-driven autoscaling "
                         "controller (repro.sched): per-layer plans adapt "
                         "to traffic between micro-batches")
    ap.add_argument("--autoscale-interval", type=int, default=4,
                    help="engine steps between controller evaluations")
    ap.add_argument("--hysteresis", type=float, default=0.1,
                    help="min relative transfer-balance improvement "
                         "before the controller swaps a live plan")
    ap.add_argument("--headroom", type=float, default=0.2,
                    help="drift-rate -> replica-hedge gain")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-trace the (batch-bucket, min-replicas) "
                         "compile grid before serving")
    ap.add_argument("--trace-dir", default=None,
                    help="enable span tracing and export the artifact set "
                         "(trace.json Chrome trace for Perfetto, spans.json, "
                         "metrics.prom/.json) into this directory")
    ap.add_argument("--metrics-out", default=None,
                    help="write a Prometheus-text metrics snapshot here "
                         "(metrics are collected even without --trace-dir)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--require-tpu", action="store_true",
                    help="fail unless JAX's devices are TPUs")
    args = ap.parse_args(argv)
    if args.require_tpu:
        require_tpu()
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    cfg = get_config(args.arch)
    assert cfg.moe.enabled, "serve driver targets MoE archs"
    moe_over = {k: v for k, v in (
        ("compute_backend", args.compute_backend),
        ("n_microops", args.n_microops),
        ("pipeline_ffn", args.pipeline_ffn),
        ("shortcut", args.shortcut)) if v is not None}
    if moe_over:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
    print(f"moe knobs: n_microops={cfg.moe.n_microops} "
          f"pipeline_ffn={cfg.moe.pipeline_ffn} "
          f"shortcut={cfg.moe.shortcut} "
          f"compute_backend={cfg.moe.compute_backend}", flush=True)
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(args.seed))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=4, seed=args.seed)
    ds = SyntheticLM(dcfg)

    print("profiling expert-selection paths ...", flush=True)
    prof = profile_from_training(
        cfg, params, (ds.batch(i) for i in range(args.profile_batches)),
        path_len=args.path_len)

    obs = ObsContext.enabled() if args.trace_dir else ObsContext.disabled()
    server = MoEServer(cfg, params, prof,
                       ServerConfig(path_len=args.path_len,
                                    schedule_policy=args.policy,
                                    plan_cache=not args.no_plan_cache),
                       obs=obs)
    scheduler = None
    if args.autoscale:
        scheduler = AdaptiveScheduler(
            server, ControllerConfig(interval=args.autoscale_interval,
                                     hysteresis=args.hysteresis,
                                     headroom=args.headroom))
    engine = ServingEngine(server,
                           EngineConfig(max_batch_tokens=args.batch_tokens,
                                        max_batch_requests=args.batch_requests),
                           scheduler=scheduler)
    if args.warmup:
        print("warming up (pre-tracing the compile grid) ...", flush=True)
        n = engine.warmup(seqs=(args.seq,),
                          max_new_tokens=args.max_new_tokens)
        print(f"warm-up traced {n} calls", flush=True)

    if args.workload is not None:
        trace = get_trace(args.workload, cfg.vocab_size,
                          n_requests=args.requests, seq=args.seq,
                          rate_hz=args.rate, seed=1000 + args.seed)
        shape = args.workload
    else:
        rng = np.random.RandomState(1000 + args.seed)
        t, trace = 0.0, []
        for _ in range(args.requests):
            t += rng.exponential(1.0 / args.rate)
            trace.append((rng.randint(0, cfg.vocab_size, (args.seq,)), t))
        shape = "stationary-poisson"

    print(f"serving {args.requests} requests ({shape}, rate {args.rate}/s, "
          f"{args.max_new_tokens} new tokens each) ...", flush=True)
    results = simulate(engine, trace, max_new_tokens=args.max_new_tokens)

    m = summarize_results(results)
    stats = engine.layer_stats
    loads = np.stack([s.device_load for s in stats])
    print(f"policy={args.policy}  completed {m['n']} requests")
    print(f"latency p50 {m['latency_p50']*1e3:.1f} ms  "
          f"p95 {m['latency_p95']*1e3:.1f} ms")
    if args.max_new_tokens:
        print(f"TTFT p50 {m['ttft_p50']*1e3:.1f} ms  "
              f"p95 {m['ttft_p95']*1e3:.1f} ms")
        print(f"TPOT p50 {m['tpot_p50']*1e3:.1f} ms  "
              f"p95 {m['tpot_p95']*1e3:.1f} ms  "
              f"({m['gen_tok_s']:.1f} gen tok/s)")
    print(f"plan reuse {engine.plan_reuse_rate:.1%}  "
          f"fine-tune rate {engine.finetune_rate:.1%}  "
          f"estimation accuracy "
          f"{np.mean([s.est_accurate for s in stats]):.1%}")
    print(f"device load imbalance (max/mean): "
          f"{(loads.max(1) / np.maximum(loads.mean(1), 1e-9)).mean():.2f}x")
    if scheduler is not None:
        rep = scheduler.report()
        print(f"autoscaler: {rep['swaps']} swaps (+{rep['bootstraps']} "
              f"bootstraps) over {rep['steps']} steps "
              f"({rep['churn_per_100_steps']:.1f} swaps/100 steps), "
              f"{scheduler.controller.migrated_slots} expert stacks moved")
    if args.trace_dir:
        paths = obs.export(args.trace_dir)
        print(f"trace artifacts: {paths['trace']} (open in "
              f"ui.perfetto.dev), {paths['spans']}, {paths['prom']}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.to_prometheus())
        print(f"metrics snapshot: {args.metrics_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
