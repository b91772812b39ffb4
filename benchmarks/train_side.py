"""Training-side benchmarks: Table 1, Figs. 10-15, Table 3, and the
MEASURED schedule ablation (``schedules``).

Each function returns rows of (name, us_per_call, derived).  ``us_per_call``
is a real CPU wall-time of the corresponding smoke-scale jitted step (the
anchor proving the code path runs); ``derived`` carries the v5e-modelled
quantity the paper table reports.

``measured_schedule_ablation`` is different in kind: it runs every Lina §4
gradient-reduction schedule through the REAL jitted train step on a forced
multi-device CPU mesh (``XLA_FLAGS=--xla_force_host_platform_device_count``,
in a subprocess so the parent's jax stays single-device per the dry-run
rules) and reports measured wall time next to the analytic
``simulate_step`` number for the same schedule.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.commmodel import (MoEStepModel, simulate_backward,
                                  simulate_step, step_model_for)
from repro.configs import TRANSFORMER_XL, GPT2_MOE, BERT2GPT2, with_experts
from repro.configs.base import V5E, A100_IB
from repro.core.packing import choose_packing
from repro.data import DataConfig, SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import lm as lm_mod
from repro.optim.adamw import AdamWConfig, init_opt_state

PAPER_MODELS = {"transformer-xl": TRANSFORMER_XL, "gpt2": GPT2_MOE,
                "bert2gpt2": BERT2GPT2}
SEQ, BATCH = 1024, 64           # paper-scale shapes for the model
SCHEDULES = ["baseline", "priority", "priority+partition",
             "priority+partition+pipeline", "fixed"]
# Reproduction runs on the PAPER's hardware model (A100 + 100Gb IB); the
# v5e rows show the same mechanism on the TPU target (DESIGN.md §2).
HWS = {"paperhw": A100_IB, "v5e": V5E}


def _wall_time_smoke(cfg, lina: bool, steps: int = 3) -> float:
    """Real CPU wall time of the smoke-scale train step (us)."""
    sc = cfg.smoke()
    dc = DataConfig(vocab_size=sc.vocab_size, seq_len=32, global_batch=2)
    params = lm_mod.init_params(sc, jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig()
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(sc, None, opt_cfg, lina=lina, fsdp=False))
    batch = {k: jnp.asarray(v) for k, v in SyntheticLM(dc).batch(0).items()}
    step(params, opt, batch)  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, _ = step(params, opt, batch)
    jax.block_until_ready(opt.step)
    return (time.perf_counter() - t0) / steps * 1e6


def table1_a2a_fraction():
    """Table 1: a2a completion time and its share of step time."""
    rows = []
    for hw_name, hw in HWS.items():
        for n_exp in (4, 16):
            for lname, layers in (("12L", 12), ("24L", 24), ("36L", 36)):
                import dataclasses
                cfg = dataclasses.replace(with_experts(TRANSFORMER_XL, n_exp),
                                          n_layers=layers)
                m = step_model_for(cfg, SEQ, BATCH, n_devices=n_exp, hw=hw)
                r = simulate_step(m, "baseline")
                frac = r["a2a_time_total"] / max(r["step_time"], 1e-12)
                rows.append((f"table1/{hw_name}/txl-{lname}-{n_exp}e", 0.0,
                             f"a2a_ms={r['a2a_time_total']*1e3:.2f},"
                             f"fraction={frac:.3f}"))
    return rows


def fig10_training_speedup():
    """Figs. 10-13: step-time / a2a speedup of Lina over Baseline."""
    rows = []
    for hw_name, hw in HWS.items():
        for mname, base in PAPER_MODELS.items():
            anchor = None
            for n_exp in (2, 4, 8, 16):
                cfg = with_experts(base, n_exp)
                m = step_model_for(cfg, SEQ, BATCH, n_devices=n_exp, hw=hw)
                rb = simulate_step(m, "baseline")
                rl = simulate_step(m, "priority+partition+pipeline")
                if anchor is None and hw_name == "paperhw":
                    anchor = (_wall_time_smoke(cfg, lina=False),
                              _wall_time_smoke(cfg, lina=True))
                speed = rb["step_time"] / max(rl["step_time"], 1e-12)
                a2a_speed = (rb["bwd"]["a2a_time_total"]
                             / max(rl["bwd"]["a2a_time_total"], 1e-12))
                rows.append((f"fig10/{hw_name}/{mname}-{n_exp}e",
                             anchor[1] if anchor else 0.0,
                             f"step_speedup={speed:.2f},"
                             f"bwd_a2a_speedup={a2a_speed:.2f}"
                             + (f",cpu_baseline_us={anchor[0]:.0f}"
                                if anchor else "")))
    return rows


def fig14_design_ablation():
    """Fig. 14: incremental gains of priority / partitioning / pipelining."""
    rows = []
    for mname, base in PAPER_MODELS.items():
        for n_exp in (4, 16):
            cfg = with_experts(base, n_exp)
            m = step_model_for(cfg, SEQ, BATCH, n_devices=n_exp, hw=A100_IB)
            base_t = simulate_step(m, "baseline")["step_time"]
            parts = []
            for s in SCHEDULES[1:]:
                t = simulate_step(m, s)["step_time"]
                parts.append(f"{s.split('+')[-1]}={base_t / t:.2f}")
            rows.append((f"fig14/paperhw/{mname}-{n_exp}e", 0.0,
                         ",".join(parts)))
    return rows


def fig15_partition_size():
    """Fig. 15: step time vs micro-op partition size (10MB..200MB)."""
    rows = []
    cfg = with_experts(TRANSFORMER_XL, 16)
    m = step_model_for(cfg, SEQ, BATCH, n_devices=16, hw=A100_IB)
    for mb in (10e6, 30e6, 50e6, 100e6, 200e6):
        t = simulate_step(m, "priority+partition+pipeline",
                          partition_bytes=mb)["step_time"]
        rows.append((f"fig15/paperhw/txl-16e-{int(mb/1e6)}MB", 0.0,
                     f"step_ms={t*1e3:.3f}"))
    return rows


# ---------------------------------------------------------------------------
# measured (not simulated) schedule ablation
# ---------------------------------------------------------------------------

MEASURED_SCHEDULES = ("baseline", "priority", "fixed", "priority+partition",
                      "priority+partition+pipeline")


# The ablation times the SMOKE config (~1MB of gradients), so the paper-
# scale 30MB default would collapse every partitioned schedule to a single
# chunk; the sweep below (the smoke-scale Fig. 15) finds the measured
# minimum and the ablation runs at that size — 256KB is only the fallback
# when the sweep is disabled.
MEASURED_PARTITION_BYTES = 256e3
PARTITION_SWEEP = (64e3, 128e3, 256e3, 512e3, 1e6)


def _measure_schedules_inprocess(schedules, steps, batch, seq, microbatches,
                                 partition_bytes=MEASURED_PARTITION_BYTES,
                                 grad_compression=None, partition_sweep=()):
    """Worker body: time each schedule's jitted train step on THIS process's
    device set (the parent forces the device count via XLA_FLAGS).

    With ``partition_sweep`` the worker first times the
    ``priority+partition`` step at each candidate micro-op size (the
    measured, smoke-scale Fig. 15) and runs the main ablation at the
    measured minimum.  Returns (rows, sweep_rows, partition_bytes)."""
    from repro.launch.mesh import make_mesh, mesh_context
    from repro.launch.sharding import reduce_specs
    from repro.optim import reduce as reduce_mod

    n = jax.device_count()
    # dp first (the reduce under test runs over dp); ep>1 only when there
    # are enough devices for both axes (n>=4 -> a2a AND reduce contend)
    ep = 2 if n % 2 == 0 and n >= 4 else 1
    dp = max(n // ep, 1)
    mesh = make_mesh((dp, ep), ("data", "model"))
    cfg = GPT2_MOE.smoke()
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                    global_batch=batch)
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig()
    opt0 = init_opt_state(params, opt_cfg)
    data = {k: jnp.asarray(v) for k, v in SyntheticLM(dc).batch(0).items()}

    def time_schedule(sched, pb):
        step = jax.jit(make_train_step(
            cfg, mesh, opt_cfg, fsdp=False, microbatches=microbatches,
            schedule=sched, partition_bytes=pb,
            grad_compression=grad_compression))
        rstate = None
        if grad_compression == "int8_ef":
            rstate = reduce_mod.init_reduce_state(
                params, reduce_mod.ReduceConfig(sched,
                                                compression=grad_compression))
        args = (params, opt0, data) + ((rstate,) if rstate is not None else ())
        with mesh_context(mesh):
            r = step(*args)                        # compile + warm caches
            p, o = r[0], r[1]
            jax.block_until_ready(o.step)
            t0 = time.perf_counter()
            for _ in range(steps):
                r = step(p, o, data, *r[3:])
                p, o = r[0], r[1]
            jax.block_until_ready(o.step)
        return (time.perf_counter() - t0) / steps * 1e6

    sweep_rows = []
    sweep_times = {}
    if partition_sweep:
        for pb in partition_sweep:
            sweep_rows.append((float(pb), time_schedule("priority+partition",
                                                        pb)))
        sweep_times = dict(sweep_rows)
        partition_bytes = min(sweep_rows, key=lambda r: r[1])[0]

    # grads are params-shaped: report the micro-op count each schedule
    # actually compiled (non-partitioned schedules run one fused reduce);
    # the micro-ops partition each device's shard of the gradients
    part_chunks = reduce_mod.shard_n_chunks(
        mesh, params, reduce_specs(cfg, mesh, params), partition_bytes)
    out = []
    for sched in schedules:
        n_chunks = part_chunks if "partition" in sched else 1
        # the sweep already timed priority+partition at the chosen size —
        # reuse it instead of paying another compile + timed run
        us = sweep_times.get(partition_bytes) \
            if sched == "priority+partition" else None
        if us is None:
            us = time_schedule(sched, partition_bytes)
        out.append((sched, us, dp, ep, n_chunks))
    return out, sweep_rows, partition_bytes


# ---------------------------------------------------------------------------
# measured overlap efficiency (Fig. 8b pipeline + ScMoE shortcut)
# ---------------------------------------------------------------------------

OVERLAP_VARIANTS = ("pipelined", "pipelined+grouped", "shortcut")
OVERLAP_CHUNKS = (1, 2, 4, 8)


def _measure_overlap_inprocess(variants, chunk_counts, steps, batch, seq,
                               mode="train"):
    """Worker body: time the expert-parallel MoE layer per overlap variant
    and requested chunk count on THIS process's device mesh, next to its
    own serial (pipeline-off) baseline and an a2a-only reference, and
    report the measured fraction of a2a time the pipeline hides:
    ``(serial - pipelined) / a2a``, clipped to [0, 1].

    Variants: "pipelined" (xla compute), "pipelined+grouped" (the
    re-entrant grouped_ffn Pallas kernel per landed chunk), "shortcut"
    (ScMoE dense branch under the a2a shadow).  ``mode="train"`` times
    forward+backward; ``"infer"`` forward only.  Returns rows of
    (mode, variant, requested, chosen, pipe_us, serial_us, a2a_us,
    hidden_frac) — requested vs *chosen* chunk count are both surfaced
    (resolve_chunk_count; no silent caps)."""
    import dataclasses

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import microop
    from repro.core import moe as moe_mod
    from repro.core.gating import capacity
    from repro.launch.mesh import make_mesh

    n = jax.device_count()
    ep = 2 if n % 2 == 0 and n >= 4 else 1
    dp = max(n // ep, 1)
    mesh = make_mesh((dp, ep), ("data", "model"))
    cfg = GPT2_MOE.smoke()
    d, e, k = cfg.d_model, cfg.moe.n_experts, cfg.moe.top_k
    f = cfg.moe.d_ff or cfg.d_ff
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    params = moe_mod.init_moe_params(ks[0], d, f, e, cfg.ffn_type)
    sc_params = ((jax.random.normal(ks[1], (d, f)) * d ** -0.5),
                 (jax.random.normal(ks[2], (d, f)) * d ** -0.5),
                 (jax.random.normal(ks[3], (f, d)) * f ** -0.5))
    x = jax.random.normal(ks[4], (batch, seq, d))

    b_loc = batch // dp if batch % dp == 0 else batch
    s_loc = seq // ep if seq % ep == 0 else seq
    cap = capacity(b_loc * s_loc, e, k, cfg.moe.capacity_factor)

    def timed(fn, *args):
        out = fn(*args)                            # compile + warm caches
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e6

    def layer_time(moe_cfg, sc):
        def fwd(p, xx):
            out = moe_mod.moe_layer(mesh, xx, p, moe_cfg,
                                    ffn_type=cfg.ffn_type, lina=True,
                                    shortcut_params=sc)
            return (out.y.astype(jnp.float32) ** 2).sum()
        fn = jax.grad(fwd) if mode == "train" else fwd
        return timed(jax.jit(fn), params, x)

    # a2a-only reference: the layer's chunked dispatch + combine exchanges
    # with an identity expert — what the pipeline is trying to hide
    buf = jax.random.normal(key, (e, cap, d))

    def a2a_time(nc):
        def body(b):
            outs = microop.chunked_all_to_all(b, "model", nc)
            back = [microop.all_to_all_ec_inverse(o, "model", e)
                    for o in outs]
            return back[0] if len(back) == 1 else jnp.concatenate(back,
                                                                  axis=1)
        fn = shard_map(body, mesh=mesh, in_specs=(P(None, None, None),),
                       out_specs=P(None, None, None), check_vma=False)
        return timed(jax.jit(fn), buf)

    a2a_us = {nc: a2a_time(nc) for nc in chunk_counts}
    rows = []
    for variant in variants:
        backend = "pallas" if variant == "pipelined+grouped" else "xla"
        sc = sc_params if variant == "shortcut" else None
        base = dataclasses.replace(cfg.moe, compute_backend=backend)
        serial_us = layer_time(
            dataclasses.replace(base, pipeline_ffn=False), sc)
        for nc in chunk_counts:
            chosen = microop.resolve_chunk_count(cap, nc)
            pipe_us = layer_time(
                dataclasses.replace(base, n_microops=nc, pipeline_ffn=True),
                sc)
            hidden = max(0.0, min(1.0, (serial_us - pipe_us)
                                  / max(a2a_us[nc], 1e-9)))
            rows.append((mode, variant, nc, chosen, pipe_us, serial_us,
                         a2a_us[nc], hidden))
    return rows


def _forced_host_worker_env(device_count: int) -> dict:
    """Environment for a forced-host-device CPU worker process.

    Refuses on a TPU backend: this process then holds the chip, so a child
    that also reaches for it would fail or hang — and forced host devices
    are a CPU stand-in that says nothing about the chip."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "the forced-host-device benchmark workers run only on a CPU "
            "backend: this process holds the TPU, and a child process "
            "cannot share it (run with JAX_PLATFORMS=cpu)")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count"
                        f"={device_count}").strip()
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(repo, "src"), repo])
    return env


def overlap_rows_subprocess(device_count: int = 4, steps: int = 5,
                            batch: int = 4, seq: int = 32,
                            variants=OVERLAP_VARIANTS,
                            chunk_counts=OVERLAP_CHUNKS, mode="train",
                            timeout=1800):
    """Spawn the forced-device worker for the overlap microbench only and
    return the parsed rows (shared by the infer-side benchmark)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = _forced_host_worker_env(device_count)
    cmd = [sys.executable, "-m", "benchmarks.train_side",
           "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
           "--overlap-variants", ",".join(variants),
           "--overlap-chunks", ",".join(str(c) for c in chunk_counts),
           "--overlap-mode", mode]
    p = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                       text=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"overlap worker failed:\n{p.stderr[-3000:]}")
    return _parse_overlap_lines(p.stdout)


def _parse_overlap_lines(stdout: str):
    rows = []
    for line in stdout.splitlines():
        if not line.startswith("OVERLAP "):
            continue
        (_, mode, variant, req, chosen, pipe_us, serial_us, a2a_us,
         hidden) = line.split()
        rows.append({"mode": mode, "variant": variant,
                     "chunks_requested": int(req),
                     "chunks_chosen": int(chosen),
                     "us_per_call": float(pipe_us),
                     "serial_us": float(serial_us),
                     "a2a_us": float(a2a_us),
                     "a2a_hidden_frac": float(hidden)})
    return rows


def measured_schedule_ablation(device_count: int = 4, steps: int = 5,
                               batch: int = 4, seq: int = 32,
                               microbatches: int = 2,
                               schedules=MEASURED_SCHEDULES,
                               partition_bytes: float = None,
                               partition_sweep=PARTITION_SWEEP,
                               grad_compression=None,
                               overlap_variants=OVERLAP_VARIANTS,
                               overlap_chunks=OVERLAP_CHUNKS,
                               json_path: str = "BENCH_schedules.json"):
    """Measured wall time of each gradient-reduction schedule through the
    real jitted train step on a ``device_count``-device CPU mesh, with the
    analytic paper-hardware step time for the same schedule alongside.

    ``partition_bytes=None`` (the default) auto-picks the micro-op size:
    the worker times ``priority+partition`` over ``partition_sweep`` (the
    measured, smoke-scale analogue of Fig. 15) and the ablation runs at the
    measured minimum; the chosen value is recorded in ``json_path`` and in
    every row.  Pass an explicit float to pin it.

    The same worker also runs the overlap-efficiency microbench
    (``_measure_overlap_inprocess``): per variant x chunk count, the
    fraction of a2a time hidden by the chunk pipeline, written into
    ``json_path`` under ``"overlap"`` with requested *and* chosen chunk
    counts as columns.  Pass ``overlap_variants=()`` to skip."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = _forced_host_worker_env(device_count)
    cmd = [sys.executable, "-m", "benchmarks.train_side",
           "--schedules", ",".join(schedules), "--steps", str(steps),
           "--batch", str(batch), "--seq", str(seq),
           "--microbatches", str(microbatches)]
    if partition_bytes is None:
        cmd += ["--partition-sweep",
                ",".join(str(float(pb)) for pb in partition_sweep)]
    else:
        cmd += ["--partition-bytes", str(partition_bytes)]
    if grad_compression:
        cmd += ["--grad-compression", grad_compression]
    if overlap_variants and overlap_chunks:
        cmd += ["--overlap-variants", ",".join(overlap_variants),
                "--overlap-chunks", ",".join(str(c) for c in overlap_chunks),
                "--overlap-mode", "train"]
    p = subprocess.run(cmd, env=env, cwd=repo, capture_output=True,
                       text=True, timeout=1800)
    if p.returncode != 0:
        raise RuntimeError(f"measure worker failed:\n{p.stderr[-3000:]}")
    measured = {}
    notes = {}
    sweep = []
    chosen = partition_bytes or MEASURED_PARTITION_BYTES
    for line in p.stdout.splitlines():
        if line.startswith("MEASURED "):
            _, sched, us, dp, ep, nchunks = line.split()
            measured[sched] = float(us)
            notes[sched] = f"mesh={dp}x{ep},n_chunks={nchunks}"
        elif line.startswith("SWEEP "):
            _, pb, us = line.split()
            sweep.append((float(pb), float(us)))
        elif line.startswith("CHOSEN "):
            chosen = float(line.split()[1])
    overlap = _parse_overlap_lines(p.stdout)
    sim = step_model_for(with_experts(GPT2_MOE, 16), SEQ, BATCH,
                         n_devices=16, hw=A100_IB)
    rows = []
    jrows = []
    comp_note = f",compression={grad_compression}" if grad_compression else ""
    for pb, us in sweep:
        rows.append((f"schedules/partition-sweep/{int(pb/1e3)}KB", us,
                     f"chosen={pb == chosen}"))
    for sched in schedules:
        sim_ms = simulate_step(sim, sched)["step_time"] * 1e3
        rows.append((f"schedules/measured/gpt2-{sched}", measured[sched],
                     f"{notes[sched]},microbatches={microbatches}{comp_note},"
                     f"partition_bytes={chosen:.0f},"
                     f"sim_paperhw_step_ms={sim_ms:.3f}"))
        jrows.append({"schedule": sched, "us_per_step": measured[sched],
                      "notes": notes[sched],
                      "sim_paperhw_step_ms": sim_ms})
    if "baseline" in measured and "priority+partition+pipeline" in measured:
        base = measured["baseline"]
        lina = measured["priority+partition+pipeline"]
        rows.append(("schedules/measured/speedup", 0.0,
                     f"baseline_us={base:.0f},lina_us={lina:.0f},"
                     f"measured_speedup={base / max(lina, 1e-9):.3f}"))
    for o in overlap:
        rows.append((f"schedules/overlap/{o['variant']}"
                     f"-c{o['chunks_requested']}", o["us_per_call"],
                     f"chunks_requested={o['chunks_requested']},"
                     f"chunks_chosen={o['chunks_chosen']},"
                     f"serial_us={o['serial_us']:.1f},"
                     f"a2a_us={o['a2a_us']:.1f},"
                     f"a2a_hidden_frac={o['a2a_hidden_frac']:.3f}"))
    if not os.path.isabs(json_path):
        json_path = os.path.join(repo, json_path)
    with open(json_path, "w") as fh:
        json.dump({
            "partition_bytes": chosen,
            "partition_bytes_source": "measured-sweep-min" if sweep
            else "pinned",
            "partition_sweep": [{"bytes": pb, "us_per_step": us}
                                for pb, us in sweep],
            "microbatches": microbatches,
            "grad_compression": grad_compression,
            "rows": jrows,
            "overlap": overlap,
        }, fh, indent=1)
    return rows


def _worker_main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedules", default="",
                    help="comma-separated schedule names; empty skips the "
                         "schedule timing (overlap-only worker run)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--partition-bytes", type=float,
                    default=MEASURED_PARTITION_BYTES)
    ap.add_argument("--partition-sweep", default="",
                    help="comma-separated micro-op sizes; when given, the "
                         "measured minimum overrides --partition-bytes")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--overlap-variants", default="",
                    help="comma-separated overlap variants "
                         "(pipelined|pipelined+grouped|shortcut); empty "
                         "skips the overlap microbench")
    ap.add_argument("--overlap-chunks", default="",
                    help="comma-separated requested chunk counts")
    ap.add_argument("--overlap-mode", default="train",
                    choices=["train", "infer"],
                    help="train times forward+backward, infer forward only")
    args = ap.parse_args(argv)
    if args.schedules:
        sweep = tuple(float(s) for s in args.partition_sweep.split(",")) \
            if args.partition_sweep else ()
        rows, sweep_rows, chosen = _measure_schedules_inprocess(
            args.schedules.split(","), args.steps, args.batch, args.seq,
            args.microbatches, partition_bytes=args.partition_bytes,
            grad_compression=args.grad_compression, partition_sweep=sweep)
        for pb, us in sweep_rows:
            print(f"SWEEP {pb:.0f} {us:.1f}", flush=True)
        print(f"CHOSEN {chosen:.0f}", flush=True)
        for sched, us, dp, ep, n_chunks in rows:
            print(f"MEASURED {sched} {us:.1f} {dp} {ep} {n_chunks}",
                  flush=True)
    if args.overlap_variants and args.overlap_chunks:
        orows = _measure_overlap_inprocess(
            args.overlap_variants.split(","),
            tuple(int(c) for c in args.overlap_chunks.split(",")),
            args.steps, args.batch, args.seq, mode=args.overlap_mode)
        for (mode, variant, req, chosen_c, pipe_us, serial_us, a2a_us,
             hidden) in orows:
            print(f"OVERLAP {mode} {variant} {req} {chosen_c} "
                  f"{pipe_us:.1f} {serial_us:.1f} {a2a_us:.1f} "
                  f"{hidden:.4f}", flush=True)


def table3_packing():
    """Table 3: pipeline efficiency without / with expert packing."""
    rows = []
    for hw_name, hw in HWS.items():
        for mname, base in PAPER_MODELS.items():
            cfg = with_experts(base, 16)
            tokens = BATCH * SEQ // 16 // max(cfg.moe.n_microops, 1)
            no_pack = choose_packing(tokens, cfg.d_model,
                                     cfg.moe.d_ff or cfg.d_ff, 16, 16,
                                     ffn_mult=2, max_pack=1, hw=hw)
            packed = choose_packing(tokens, cfg.d_model,
                                    cfg.moe.d_ff or cfg.d_ff, 16, 16,
                                    ffn_mult=2, max_pack=8, hw=hw)
            rows.append((f"table3/{hw_name}/{mname}-16e", 0.0,
                         f"eff_no_pack={no_pack.pipeline_efficiency:.2f},"
                         f"eff_packed={packed.pipeline_efficiency:.2f},"
                         f"experts_per_device={packed.experts_per_device}"))
    return rows


if __name__ == "__main__":
    _worker_main()
