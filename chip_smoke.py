#!/usr/bin/env python3
"""Smoke test on the chip: Lina's serve and train entry points at
gpt2-moe's published widths (d_model 768, 12 heads, d_ff 3072, 16 experts,
vocab 50257), random weights from a seed.

    python chip_smoke.py            # one chip: kernel parity, serve, train
    python chip_smoke.py --chips 4  # four chips: expert-parallel path only

One chip runs four phases in order, each printing its own line:

  1. device   — refuse anything but a TPU backend;
  2. parity   — one ``serve_moe_layer`` call with the Pallas kernels and
                with the XLA path on the same weights, input and placement
                plan: same experts chosen, outputs equal within bf16
                tolerance, and the Pallas program holds Mosaic kernels;
  3. serve    — ``MoEServer`` -> ``ServingEngine`` (policy ``lina``) at the
                full 12 layers: warm-up, then 8 generating requests through
                ``simulate``; every request completes with finite logits;
  4. train    — ``Trainer`` -> ``make_train_step`` for 3 steps at depth 4
                (12 layers of f32 params + grads + AdamW moments do not fit
                16 GB), schedule ``priority+partition``, checkpoints off;
                every loss finite.

``--chips 4`` runs only what exists across chips, on a (data=1, model=4)
mesh: one ``Trainer`` step of the full 12-layer model under ``baseline``
and under ``priority+partition+pipeline`` (loss and grad norm must match,
and each chip must hold its own 4 experts of every expert weight),
and ``serve_moe_layer`` on the mesh against its one-chip result.

Everything runs in this one process; nothing here starts a child.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every phase passed.  Times printed are smoke-run times,
not benchmark measurements.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------

def device_phase(chips: int):
    import jax

    from repro.launch.device import enable_compile_cache, require_tpu
    devs = require_tpu()
    check(len(devs) >= chips,
          f"{chips} chips requested, JAX found {len(devs)}")
    cache = enable_compile_cache()
    log(f"[device] {devs[0].device_kind} x{len(devs)}  jax {jax.__version__}"
        f"  compile cache {cache}")
    return devs


# ---------------------------------------------------------------------------
# phase 2: Pallas vs XLA on one serve_moe_layer call
# ---------------------------------------------------------------------------

def layer_case(cfg, n_tokens: int, n_dev: int, seed: int = SEED):
    """Seeded bf16 expert weights, input tokens and a Lina placement plan
    for one MoE layer of ``cfg``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import PlanArrays, init_moe_params, plan_placement
    k_w, k_x = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(k_w, cfg.d_model, cfg.moe.d_ff or cfg.d_ff,
                             cfg.moe.n_experts, ffn_type=cfg.ffn_type,
                             dtype=jnp.bfloat16)
    x = jax.random.normal(k_x, (n_tokens, cfg.d_model)).astype(jnp.bfloat16)
    pop = np.random.RandomState(seed).dirichlet(
        np.ones(cfg.moe.n_experts) * 0.5)
    plan = plan_placement(pop, n_dev, max_pack=4)
    return params, x, PlanArrays.from_plan(plan), int(plan.n_replicas.min())


def serve_layer_fn(cfg, backend: str, mesh, min_replicas: int):
    import jax

    from repro.core.serving import serve_moe_layer
    mcfg = dataclasses.replace(cfg.moe, compute_backend=backend)
    return jax.jit(lambda x, p, pl: serve_moe_layer(
        mesh, x, p, mcfg, pl, ffn_type=cfg.ffn_type, top_k=cfg.moe.top_k,
        min_replicas=min_replicas))


def rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# bf16 carries 8 mantissa bits (relative step 2**-8 = 0.0039): the two
# backends round the expert FFN's intermediates at different points, so
# they agree to a few bf16 steps, not bitwise
BF16_REL_TOL = 2e-2


def parity_phase(cfg, n_tokens: int = 2048):
    import jax
    import numpy as np

    params, x, plan, min_rep = layer_case(cfg, n_tokens, cfg.moe.n_experts)
    out = {}
    for backend in ("pallas", "xla"):
        fn = serve_layer_fn(cfg, backend, None, min_rep)
        t0 = time.perf_counter()
        compiled = fn.lower(x, params, plan).compile()
        t_compile = time.perf_counter() - t0
        y, idx, probs = jax.block_until_ready(compiled(x, params, plan))
        out[backend] = (np.asarray(y, np.float32), np.asarray(idx),
                        compiled.as_text(), t_compile)
        log(f"[parity] {backend}: compiled in {t_compile:.2f} s")
    y_p, idx_p, text_p, _ = out["pallas"]
    y_x, idx_x, _, _ = out["xla"]
    check("tpu_custom_call" in text_p,
          "the Pallas program holds no tpu_custom_call: kernels did not "
          "compile natively")
    check(np.isfinite(y_p).all(), "non-finite Pallas output")
    n_diff = int((idx_p != idx_x).sum())
    check(n_diff == 0, f"{n_diff} expert choices differ between backends")
    err = rel_err(y_p, y_x)
    check(err <= BF16_REL_TOL,
          f"Pallas vs XLA relative error {err:.3g} > {BF16_REL_TOL}")
    log(f"[parity] T={n_tokens} d={cfg.d_model} F={cfg.moe.d_ff or cfg.d_ff}"
        f" E={cfg.moe.n_experts} k={cfg.moe.top_k} bf16: expert ids equal,"
        f" rel err {err:.3e}, tpu_custom_call present")


# ---------------------------------------------------------------------------
# phase 3: serve through the engine
# ---------------------------------------------------------------------------

# JAX's monitoring events for lowering a jaxpr and for building one
# executable (a backend compile, or a fetch from the compile cache)
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def compile_work():
    """Seconds spent lowering and compiling inside the window, and the
    number of executables built: {"lower_s", "compile_s", "programs"}."""
    from jax import monitoring
    tot = {"lower_s": 0.0, "compile_s": 0.0, "programs": 0}

    def on(event, secs, **_):
        if event == LOWER_EVENT:
            tot["lower_s"] += secs
        elif event == COMPILE_EVENT:
            tot["compile_s"] += secs
            tot["programs"] += 1
    monitoring.register_event_duration_secs_listener(on)
    try:
        yield tot
    finally:
        monitoring.unregister_event_duration_listener(on)

def serve_phase(cfg, n_requests: int = 8, prompt: int = 128,
                new_tokens: int = 16, seed: int = SEED):
    import jax
    import numpy as np

    from repro.analysis.retrace import no_retrace
    from repro.data import DataConfig, SyntheticLM
    from repro.models import lm as lm_mod
    from repro.runtime.engine import (EngineConfig, ServingEngine, simulate,
                                      summarize_results)
    from repro.runtime.server import (MoEServer, ServerConfig,
                                      profile_from_training)

    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, "
        f"{cfg.param_count() / 1e9:.3f} B params")
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(seed))
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=prompt,
                                global_batch=4, seed=seed))
    t0 = time.perf_counter()
    prof = profile_from_training(cfg, params,
                                 (ds.batch(i) for i in range(2)), path_len=3)
    log(f"[serve] profiled expert paths in {time.perf_counter() - t0:.2f} s")
    server = MoEServer(cfg, params, prof,
                       ServerConfig(path_len=3, schedule_policy="lina"))
    engine = ServingEngine(server, EngineConfig(
        max_batch_tokens=n_requests * prompt, max_batch_requests=n_requests))
    t0 = time.perf_counter()
    n_traced = engine.warmup(seqs=(prompt,), max_new_tokens=new_tokens)
    warm_s = time.perf_counter() - t0
    log(f"[serve] warm-up traced {n_traced} calls, compile {warm_s:.2f} s")

    rng = np.random.RandomState(1000 + seed)
    trace, t = [], 0.0
    for _ in range(n_requests):
        t += rng.exponential(1.0 / 20.0)
        trace.append((rng.randint(0, cfg.vocab_size, (prompt,)), t))
    met = engine.obs.metrics
    layers0 = met.value("server_layers_served_total")
    tunes0 = met.value("server_phase2_finetunes_total")
    t0 = time.perf_counter()
    with no_retrace("simulate", strict=False) as traced, \
            compile_work() as work:
        results = simulate(engine, trace, max_new_tokens=new_tokens)
    sim_s = time.perf_counter() - t0
    layers = met.value("server_layers_served_total") - layers0
    tunes = met.value("server_phase2_finetunes_total") - tunes0
    log(f"[serve] simulate {sim_s:.2f} s: {traced.count} jit traces, "
        f"{work['programs']} executables built in {work['compile_s']:.2f} s "
        f"(+{work['lower_s']:.2f} s lowering); phase-2 fine-tunes "
        f"{tunes:.0f} of {layers:.0f} layer batches")
    offered = met.value("engine_requests_offered_total")
    completed = met.value("engine_requests_completed_total")
    check(len(results) == n_requests,
          f"{len(results)} of {n_requests} requests completed")
    check(offered == completed == n_requests,
          f"offered {offered} != completed {completed}")
    for r in results:
        check(r.n_generated == new_tokens,
              f"request {r.rid} generated {r.n_generated} tokens")
        check(np.isfinite(r.logits).all(),
              f"request {r.rid} has non-finite logits")
    m = summarize_results(results, engine)
    log(f"[serve] TTFT p50 {m['ttft_p50'] * 1e3:.1f} ms  TPOT p50 "
        f"{m['tpot_p50'] * 1e3:.1f} ms  plan reuse "
        f"{engine.plan_reuse_rate:.1%}  (smoke-run times, not a benchmark)")
    log(f"[serve] {n_requests} requests x {new_tokens} new tokens: all "
        f"completed, logits finite, offered == completed == {n_requests}")


# ---------------------------------------------------------------------------
# phase 4: train
# ---------------------------------------------------------------------------

def make_trainer(cfg, *, steps: int, batch: int, seq: int, schedule: str,
                 mesh=None, seed: int = SEED):
    from repro.data import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.runtime import Trainer, TrainerConfig
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                          global_batch=batch, seed=seed)
    opt_cfg = AdamWConfig(lr=1e-3, total_steps=steps, warmup_steps=1,
                          state_dtype=cfg.opt_state_dtype)
    tcfg = TrainerConfig(steps=steps, ckpt_dir=None, log_every=1,
                         schedule=schedule, seed=seed)
    return Trainer(cfg, data_cfg, opt_cfg, tcfg, mesh=mesh)


def run_trainer(trainer, tag: str):
    import numpy as np
    t0 = time.perf_counter()
    state = trainer.run()
    wall = time.perf_counter() - t0
    rows = trainer.metrics_log
    check(len(rows) == trainer.cfg.steps and not trainer.skipped_steps,
          f"{tag}: {len(rows)} steps logged, skipped {trainer.skipped_steps}")
    for r in rows:
        check(np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"]),
              f"{tag}: step {r['step']} loss {r['loss']} "
              f"grad norm {r['grad_norm']}")
        log(f"[{tag}] step {r['step']}  loss {r['loss']:.6f}  grad norm "
            f"{r['grad_norm']:.6f}  {r['dt']:.2f} s")
    log(f"[{tag}] {len(rows)} steps in {wall:.2f} s, first step includes "
        f"compilation (smoke-run times, not a benchmark)")
    return state, rows


def train_phase(cfg, depth: int = 4, steps: int = 3, batch: int = 8,
                seq: int = 512):
    cut = dataclasses.replace(cfg, n_layers=depth)
    log(f"[train] {cfg.name}: n_layers {cfg.n_layers}->{depth} "
        f"(f32 params + grads + AdamW moments at {cfg.n_layers} layers: "
        f"{16 * cfg.param_count() / 1e9:.1f} GB), batch {batch}, seq {seq}, "
        f"schedule priority+partition, checkpoints off")
    run_trainer(make_trainer(cut, steps=steps, batch=batch, seq=seq,
                             schedule="priority+partition"), "train")


# ---------------------------------------------------------------------------
# four chips: the expert-parallel path and what it is compared with
# ---------------------------------------------------------------------------

def expert_shards_ok(wi, n_dev: int) -> None:
    """Every device holds a distinct 1/n_dev slice of the expert axis
    (dim 1 of the stacked [layers, E, d, f] weight)."""
    e = wi.shape[1]
    seen = {}
    for s in wi.addressable_shards:
        sl = s.index[1]
        start = sl.start or 0
        stop = e if sl.stop is None else sl.stop
        seen[s.device.id] = (start, stop)
    check(len(seen) == n_dev, f"expert weight on {len(seen)} devices")
    spans = sorted(seen.values())
    check(all(b - a == e // n_dev for a, b in spans)
          and len(set(spans)) == n_dev,
          f"expert slices per device {seen}")
    log(f"[4chip] wi {tuple(wi.shape)}: device -> experts "
        + ", ".join(f"{d}:{a}-{b - 1}" for d, (a, b) in sorted(seen.items())))


# The schedules reduce the same gradients in a different order, so the
# grad norm agrees to float reassociation (a few 1e-7), not bitwise.  The
# comparison is made on the first step: from the second on, AdamW's first
# update (about lr * sign(g) on every entry, however small) has turned that
# noise into different parameters, and the comparison would test Adam's
# conditioning instead of the schedules.
SCHEDULE_RTOL = 1e-5


def four_chip_phase(cfg, devs, steps: int = 1, batch: int = 8,
                    seq: int = 512, n_tokens: int = 2048):
    import jax
    import numpy as np

    from repro.core import axes
    from repro.launch.mesh import make_mesh
    mesh4 = make_mesh((1, 4), (axes.DATA, axes.MODEL), devices=devs[:4])
    mesh1 = make_mesh((1, 1), (axes.DATA, axes.MODEL), devices=devs[:1])

    # serve layer: 4-way expert-parallel vs the same call on one chip
    params, x, plan, min_rep = layer_case(cfg, n_tokens, cfg.moe.n_experts)
    ys = {}
    for tag, mesh in (("1chip", mesh1), ("4chip", mesh4)):
        y, idx, _ = jax.block_until_ready(
            serve_layer_fn(cfg, "pallas", mesh, min_rep)(x, params, plan))
        ys[tag] = (np.asarray(y, np.float32), np.asarray(idx))
    check((ys["1chip"][1] == ys["4chip"][1]).all(),
          "expert choices differ between 1 and 4 chips")
    err = rel_err(ys["4chip"][0], ys["1chip"][0])
    check(err <= BF16_REL_TOL, f"serve layer 4 vs 1 chip rel err {err:.3g}")
    log(f"[4chip] serve_moe_layer on (data=1, model=4) vs one chip: expert "
        f"ids equal, rel err {err:.3e}")

    log(f"[4chip] train {cfg.name}: {cfg.n_layers} layers, "
        f"{cfg.moe.n_experts // 4} experts per chip, batch {batch}, "
        f"seq {seq}")
    logs = {}
    for sched in ("baseline", "priority+partition+pipeline"):
        trainer = make_trainer(cfg, steps=steps, batch=batch, seq=seq,
                               schedule=sched, mesh=mesh4)
        state, rows = run_trainer(trainer, f"4chip {sched}")
        expert_shards_ok(state["params"].stack.moe.wi, 4)
        logs[sched] = rows
        del trainer, state
        gc.collect()
    for a, b in zip(*logs.values()):
        for key in ("loss", "grad_norm"):
            check(np.isclose(a[key], b[key], rtol=SCHEDULE_RTOL, atol=0.0),
                  f"step {a['step']} {key}: baseline {a[key]} vs "
                  f"pipeline {b[key]}")
    log(f"[4chip] baseline and priority+partition+pipeline agree on loss "
        f"and grad norm over {steps} step(s) (rtol {SCHEDULE_RTOL})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {REPO / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))

    try:
        devs = device_phase(args.chips)
        from repro.configs import get_config
        cfg = get_config("gpt2-moe")
        if args.chips == 4:
            four_chip_phase(cfg, devs)
        else:
            parity_phase(cfg)
            serve_phase(cfg)
            gc.collect()
            import jax
            jax.clear_caches()
            train_phase(cfg)
    except Exception:
        traceback.print_exc()
        print("chip_smoke.py: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
