"""Training driver: ``Trainer.step_fn`` (``make_train_step``) on a
(data=1, model=chips) mesh.

Set-up builds the Trainer and its state from the benchmark's seeded
weights, and drives that same step through its first three steps on
step-indexed batches; their losses, the first gradient (from AdamW's first
moment after one step) and the weights' change after three are what the
reference checks.  The window then keeps stepping the same object: each
step is dispatched, the next step's batch is made on the host while the
device runs it, and the loss is read back (the Trainer's own per-step
sync), until ``--seconds`` have passed.
"""
from __future__ import annotations

import gc
import sys
import time
from functools import partial

import numpy as np

from bench import flops, reference, traffic_gen, weights
from bench.drivers.common import (Record, Result, Window, annotate,
                                  count_compiles, memory_peak, program_config)

CHECK_STEPS = 3


def first_gradient(block, opt_state, beta1: float, cfg: dict) -> dict:
    """The first (clipped) gradient as AdamW got it, from its first moment
    after one step (m / (1 - beta1)), as float32 host arrays of the
    benchmark's flat shapes."""
    flat = weights.shapes(block, cfg)
    return {n: np.asarray(x, np.float32).reshape(flat[n][0]) / (1.0 - beta1)
            for n, x in weights.named_leaves(block, opt_state.m).items()}


def change_norms(block, a, b) -> dict:
    import jax
    import jax.numpy as jnp
    na, nb = weights.named_leaves(block, a), weights.named_leaves(block, b)
    norms = jax.jit(lambda x, y: {n: jnp.sqrt(jnp.sum(jnp.square(
        x[n].astype(jnp.float32) - y[n].astype(jnp.float32)))) for n in x})(
        na, nb)
    return {n: float(v) for n, v in norms.items()}


def leaf_gaps(prog: dict, ref: dict, names) -> list:
    """|prog - ref| / max(ref, median ref), per leaf."""
    med = float(np.median([ref[n] for n in ref]))
    return [abs(prog[n] - ref[n]) / max(ref[n], med) for n in names]


def compare(prog: dict, ref: dict) -> dict:
    """The numbers a limit can hold: the loss gap (worst step, first
    step), the first gradient's norm gap and the norm of its difference
    (worst leaf, median leaf; both against max(the reference leaf's norm,
    the median leaf's)), and the change norm gap (worst moving leaf,
    median leaf; leaves whose reference gradient is under 1e-3 of the
    median leaf's move by round-off under Adam, and are left out)."""
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    g_ref = {n: float(np.linalg.norm(g)) for n, g in ref["grad0"].items()}
    g_prog = {n: float(np.linalg.norm(prog["grad0"][n])) for n in g_ref}
    g_diff = {n: float(np.linalg.norm(prog["grad0"][n] - ref["grad0"][n]))
              for n in g_ref}
    g_med = float(np.median(list(g_ref.values())))
    moving = [n for n, g in g_ref.items() if g >= 1e-3 * g_med]
    grad = leaf_gaps(g_prog, g_ref, g_ref)
    diff = [g_diff[n] / max(g_ref[n], g_med) for n in g_ref]
    change = leaf_gaps(prog["change"], ref["change"], moving)
    return {"loss_gap": max(loss), "loss_gap_step0": loss[0],
            "grad_gap": max(grad), "grad_gap_median": float(np.median(grad)),
            "grad_diff": max(diff), "grad_diff_median": float(np.median(diff)),
            "head_grad_diff": head_diff(prog["grad0"]["lm_head"],
                                        ref["grad0"]["lm_head"]),
            "change_gap": max(change),
            "change_gap_median": float(np.median(change))}


def head_diff(prog, ref) -> float:
    """Median over the LM head's vocabulary columns of the norm of the
    first gradient's difference, against the reference column's norm.
    A flipped expert choice changes a few tokens' rows of every gradient;
    a column's median sees the rounding of the rest."""
    ref_n = np.linalg.norm(ref, axis=0)
    live = ref_n > 0
    return float(np.median(np.linalg.norm(prog - ref, axis=0)[live]
                           / ref_n[live]))


def reference_readings(block, cfg: dict, mix: dict, seed: int, devices,
                       **kw):
    """The reference's readings of the checked steps on the cell's chips
    (the block's expert weights split over them); ``kw`` selects the
    control or a fault (``reference.train_readings``)."""
    import jax.numpy as jnp
    shardings = None
    if len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        rmesh = Mesh(np.array(devices), ("x",))
        shardings = {n: NamedSharding(rmesh, P(None, "x") if n in
                                      block.EXPERT_SHARDED else P())
                     for n in weights.shapes(block, cfg)}
    stream = traffic_gen.LMStream(mix, cfg["vocab_size"], seed)
    oc = mix["optimizer"]
    return reference.train_readings(
        block, weights.make(block, cfg, seed, jnp.float32, shardings),
        [stream.batch(s) for s in range(CHECK_STEPS)], cfg,
        dict(oc, betas=tuple(oc["betas"])), ep=len(devices),
        shardings=shardings, **kw)


def run(spec) -> Result:
    import jax
    import jax.numpy as jnp

    from repro.core import axes
    from repro.data import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.models import lm as lm_mod
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.runtime import Trainer, TrainerConfig

    cfg, mix, seed, block = spec.cfg, spec.mix, spec.seed, spec.block
    chips = len(spec.devices)
    mc = program_config(cfg, block)
    oc = mix["optimizer"]
    opt = AdamWConfig(lr=oc["lr"], betas=tuple(oc["betas"]), eps=oc["eps"],
                      weight_decay=oc["weight_decay"],
                      grad_clip=oc["grad_clip"],
                      warmup_steps=oc["warmup_steps"],
                      total_steps=oc["total_steps"])
    mesh = make_mesh((1, chips), (axes.DATA, axes.MODEL),
                     devices=spec.devices)
    trainer = Trainer(mc, DataConfig(vocab_size=cfg["vocab_size"],
                                     seq_len=mix["seq"],
                                     global_batch=mix["batch"]),
                      opt, TrainerConfig(steps=1, ckpt_dir=None,
                                         schedule=mix["schedule"]),
                      mesh=mesh)
    struct = jax.eval_shape(partial(lm_mod.init_params, mc),
                            jax.random.PRNGKey(0))
    make_params = partial(weights.make_program, block, cfg, seed,
                          jnp.float32, struct, trainer._param_sh)
    params = make_params()
    opt_state = jax.device_put(init_opt_state(params, opt), trainer._opt_sh)
    stream = traffic_gen.LMStream(mix, cfg["vocab_size"], seed)

    def feed(step):
        return {k: jnp.asarray(v) for k, v in stream.batch(step).items()}

    prog = {"loss": []}
    for step in range(CHECK_STEPS):
        params, opt_state, m = trainer.step_fn(params, opt_state, feed(step))
        prog["loss"].append(float(m["loss"]))
        if step == 0:
            prog["grad0"] = first_gradient(block, opt_state, opt.betas[0],
                                           cfg)
    p0 = make_params()
    prog["change"] = change_norms(block, params, p0)
    del p0

    tracing = spec.trace
    n_steps, bad, step = 0, 0, CHECK_STEPS
    setup_s = spec.process_age()
    with count_compiles() as comp, Window(tracing, spec.devices) as win:
        t0 = time.perf_counter()
        with annotate(tracing, "bench.batch"):
            batch = feed(step)
        while True:
            with annotate(tracing, "bench.train_step"):
                params, opt_state, m = trainer.step_fn(params, opt_state,
                                                       batch)
            # the next batch is made while the device runs this step
            with annotate(tracing, "bench.batch"):
                batch = feed(step + 1)
            with annotate(tracing, "bench.loss_sync"):
                loss = float(m["loss"])
            bad += not np.isfinite(loss)
            n_steps += 1
            step += 1
            if time.perf_counter() - t0 >= spec.seconds:
                break
        t1 = time.perf_counter()
        win.close(t0, t1)
    mem = memory_peak(spec.devices)
    del params, opt_state, m, trainer
    gc.collect()

    ref = reference_readings(block, cfg, mix, seed, spec.devices)
    readings = compare(prog, ref)
    for name, v in readings.items():
        print(f"reading {name} = {v!r}", file=sys.stderr)
    checks = [{"name": n, "value": readings[n], "limit": spec.limits[n]}
              for n in spec.limits]

    window_s = t1 - t0
    tokens = n_steps * mix["batch"] * mix["seq"]
    e2e = {"train_tok_s": tokens / window_s, "setup_s": setup_s}
    rows = tokens * cfg["top_k"] * ref["kept_share"]
    rec = Record(cfg=cfg, mix=mix,
                 peak=spec.peak,
                 window_s=window_s, n_chips=chips,
                 counters={"compiles_in_window": comp["n"], "steps": n_steps},
                 work={"model_flops": tokens * flops.train_flops_per_token(
                     block, cfg, mix["seq"]),
                     "kept_rows": rows,
                     "experts_touched": n_steps * block.moe_layers(cfg)
                     * cfg["n_experts"]},
                 trace=win.trace)
    return Result(e2e=e2e, record=rec, attempted=n_steps, failed=bad,
                  checks=checks, memory_peak_bytes=mem, trace=win.trace)
