"""Fault-tolerant training loop.

Production behaviors exercised here (and in tests):
  * checkpoint/restart: atomic keep-k checkpoints; on start the Trainer
    resumes from the latest checkpoint and — because the data pipeline is
    step-indexed — reproduces the exact batch sequence (bitwise resume);
  * failure injection: ``fail_at_step`` raises mid-run to simulate a node
    loss; the restart test proves recovery;
  * straggler watchdog: per-step wall time is tracked against a rolling
    median; outliers are logged (on a real cluster this feeds the
    reallocation logic; here it is observable behavior under test);
  * non-finite guard (repro.resilience): a step whose loss/metrics come
    back NaN/inf is SKIPPED — params/opt state keep their pre-step values —
    and ``max_bad_steps`` consecutive bad steps trigger a rollback to the
    newest verified checkpoint; step-indexed data keeps the replay exact;
  * expert packing controller (paper §6.1): after ``pack_warmup`` steps the
    Trainer re-evaluates experts-per-device from measured FFN vs a2a
    micro-op times (the analytic v5e model stands in for CUDA events).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.obs import ObsContext
from repro.configs.base import ModelConfig
from repro.core.moe import default_mesh
from repro.core.packing import choose_packing
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import ep_size
from repro.launch.sharding import (opt_state_specs, param_specs,
                                   shardings_for)
from repro.launch.steps import make_train_step
from repro.models import lm as lm_mod
from repro.optim import reduce as reduce_mod
from repro.optim.adamw import AdamWConfig, init_opt_state


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None           # None: checkpoints off
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    lina: bool = True
    microbatches: int = 1
    # Lina §4 gradient-reduction schedule (optim/reduce.py).  "baseline" is
    # an explicit single fused psum; the priority* schedules order/partition
    # it after the backward a2a.  Default None keeps the implicit XLA
    # reduction: the explicit reduce runs ON TOP of the partitioner's own
    # DP reduction (one extra param-sized collective per step), so it is
    # opt-in — for the measured ablation, schedule experiments, and
    # compression — not the steady-state default.
    schedule: Optional[str] = None
    partition_bytes: float = reduce_mod.DEFAULT_PARTITION_BYTES
    grad_compression: Optional[str] = None   # None | "bf16" | "int8_ef"
    # token dispatch/combine backend (core.dispatch.BACKENDS): "scatter"
    # (jnp production), "einsum" (oracle), or "pallas" (fused kernels —
    # pairs with MoEConfig.compute_backend="pallas")
    dispatch_backend: str = "scatter"
    # Overlap knobs (None = keep the model config's values).  Applied onto
    # ``model_cfg.moe`` at construction so CLI flags (launch/train.py) reach
    # the shard-map body; the effective values are logged per step like
    # ``schedule`` is.
    n_microops: Optional[int] = None
    pipeline_ffn: Optional[bool] = None
    shortcut: Optional[bool] = None
    fail_at_step: Optional[int] = None       # failure injection (tests)
    straggler_factor: float = 3.0
    pack_warmup: int = 10                    # paper: packing decided at step 10
    seed: int = 0
    # non-finite guard: skip steps with NaN/inf metrics; roll back to the
    # newest checkpoint after this many CONSECUTIVE bad steps (0 = guard off)
    max_bad_steps: int = 3
    nan_at_steps: tuple = ()                 # fault injection: force these
    #                                          steps' metrics non-finite


class Trainer:
    def __init__(self, model_cfg: ModelConfig, data_cfg: DataConfig,
                 opt_cfg: AdamWConfig, cfg: TrainerConfig, mesh=None,
                 obs: Optional[ObsContext] = None):
        self.obs = obs or ObsContext.disabled()
        moe_over = {k: v for k, v in (("n_microops", cfg.n_microops),
                                      ("pipeline_ffn", cfg.pipeline_ffn),
                                      ("shortcut", cfg.shortcut))
                    if v is not None}
        if moe_over:
            model_cfg = replace(model_cfg,
                                moe=replace(model_cfg.moe, **moe_over))
        self.model_cfg = model_cfg
        self.data_cfg = data_cfg
        self.opt_cfg = opt_cfg
        self.cfg = cfg
        self.mesh = mesh
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep=cfg.keep)
                     if cfg.ckpt_dir else None)
        self.dataset = SyntheticLM(data_cfg)
        self.stateful_reduce = cfg.grad_compression == "int8_ef"
        # params and moments live on the mesh (the 1-device default mesh
        # when none is given) under the training sharding rules — experts
        # split over `model`, so each device holds its share — and the step
        # returns them with the same shardings: step 1 then reuses step 0's
        # program instead of compiling again for differently placed inputs
        state_mesh = mesh if mesh is not None else default_mesh()
        ps = jax.eval_shape(partial(lm_mod.init_params, model_cfg),
                            jax.random.PRNGKey(0))
        pspec = param_specs(model_cfg, state_mesh, ps)
        os_ = jax.eval_shape(partial(init_opt_state, cfg=opt_cfg), ps)
        self._param_sh = shardings_for(state_mesh, pspec, ps)
        self._opt_sh = shardings_for(state_mesh, opt_state_specs(pspec, os_),
                                     os_)
        n_rest = 2 if self.stateful_reduce else 1    # metrics (+ reduce state)
        self.step_fn = jax.jit(make_train_step(
            model_cfg, mesh, opt_cfg, lina=cfg.lina,
            dispatch_backend=cfg.dispatch_backend,
            microbatches=cfg.microbatches, fsdp=False,
            schedule=cfg.schedule, partition_bytes=cfg.partition_bytes,
            grad_compression=cfg.grad_compression),
            out_shardings=(self._param_sh, self._opt_sh) + (None,) * n_rest)
        self.metrics_log: list = []
        self.straggler_events: list = []
        self.packing_decision = None
        self.skipped_steps: list = []        # non-finite guard: steps skipped
        self.rollbacks = 0                   # checkpoint rollbacks performed

    def init_state(self):
        params = jax.device_put(
            lm_mod.init_params(self.model_cfg,
                               jax.random.PRNGKey(self.cfg.seed)),
            self._param_sh)
        state = {"params": params,
                 "opt_state": jax.device_put(
                     init_opt_state(params, self.opt_cfg), self._opt_sh)}
        if self.stateful_reduce:
            # int8-EF residual rides in the checkpoint so resume is bitwise
            state["reduce_state"] = reduce_mod.init_reduce_state(
                params, reduce_mod.ReduceConfig(
                    schedule=self.cfg.schedule,
                    partition_bytes=self.cfg.partition_bytes,
                    compression=self.cfg.grad_compression))
        return state

    def run(self, on_step: Optional[Callable] = None) -> dict:
        state = self.init_state()
        start_step = 0
        if self.ckpt is not None:
            start, restored = self.ckpt.restore_latest(state)
            if restored is not None:
                state, start_step = restored, start

        times: list = []
        consec_bad = 0
        tr = self.obs.tracer
        met = self.obs.metrics
        sched_name = self.cfg.schedule or "implicit"
        for step in range(start_step, self.cfg.steps):
            if self.cfg.fail_at_step is not None and step == self.cfg.fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            with tr.span("train.step", step=step,
                         schedule=sched_name) as ssp:
                with tr.span("data.batch"):
                    batch = {k: jax.numpy.asarray(v)
                             for k, v in self.dataset.batch(step).items()}
                # fwd+bwd+update runs as ONE jitted call — the host-side
                # span carries the schedule attribution; the true device
                # split lives in a jax.profiler capture (obs.StepProfiler)
                with tr.timed("fwd_bwd", schedule=sched_name) as sw:
                    if self.stateful_reduce:
                        params, opt_state, m, rstate = self.step_fn(
                            state["params"], state["opt_state"], batch,
                            state["reduce_state"])
                    else:
                        params, opt_state, m = self.step_fn(
                            state["params"], state["opt_state"], batch)
                    m = {k: float(v) for k, v in m.items()}
                if step in (self.cfg.nan_at_steps or ()):
                    m = dict(m, loss=float("nan"))   # injected divergence
                dt = sw.dt
                met.counter("trainer_steps_total").inc()
                met.histogram("trainer_step_s").observe(dt)
                # --- non-finite guard: a diverged step must not commit -----
                if self.cfg.max_bad_steps and \
                        not all(np.isfinite(v) for v in m.values()):
                    self.skipped_steps.append(step)
                    self.metrics_log.append({"step": step, **m, "dt": dt,
                                             "skipped": True})
                    met.counter("trainer_skipped_steps_total").inc()
                    ssp.set(skipped=True)
                    consec_bad += 1
                    if consec_bad >= self.cfg.max_bad_steps:
                        rb_state = None
                        if self.ckpt is not None:
                            _, rb_state = self.ckpt.restore_latest(state)
                        if rb_state is not None:
                            state = rb_state
                            self.rollbacks += 1
                            met.counter("trainer_rollbacks_total").inc()
                            ssp.set(rollback=True)
                        consec_bad = 0
                    continue     # params/opt_state keep pre-step values
                consec_bad = 0
                state = {"params": params, "opt_state": opt_state}
                if self.stateful_reduce:
                    state["reduce_state"] = rstate
                times.append(dt)
                med = float(np.median(times[-20:]))
                if len(times) > 5 and dt > self.cfg.straggler_factor * med:
                    self.straggler_events.append({"step": step, "dt": dt,
                                                  "median": med})
                    met.counter("trainer_straggler_events_total").inc()
                # per-schedule step time: the measured ablation keys on
                # this; overlap knobs logged alongside so ablations over
                # n_microops/pipeline/shortcut are attributable per step
                moe = self.model_cfg.moe
                self.metrics_log.append({"step": step, **m, "dt": dt,
                                         "schedule": sched_name,
                                         "n_microops": moe.n_microops,
                                         "pipeline_ffn": moe.pipeline_ffn,
                                         "shortcut": moe.shortcut})
                if step == self.cfg.pack_warmup and self.model_cfg.moe.enabled:
                    self._decide_packing()
                if on_step:
                    on_step(step, m)
                if self.ckpt is not None and (
                        (step + 1) % self.cfg.ckpt_every == 0
                        or step + 1 == self.cfg.steps):
                    with tr.span("checkpoint", step=step + 1):
                        self.ckpt.save(step + 1, state)
        return state

    def _decide_packing(self):
        mc = self.model_cfg
        # EP group size from the actual mesh; the paper's one-expert-per-
        # device assumption only stands in when there is no mesh to ask
        ep = ep_size(self.mesh) if self.mesh is not None else mc.moe.n_experts
        tokens = (self.data_cfg.global_batch * self.data_cfg.seq_len
                  // max(ep, 1) // max(mc.moe.n_microops, 1))
        self.packing_decision = choose_packing(
            max(tokens, 1), mc.d_model, mc.moe.d_ff or mc.d_ff,
            mc.moe.n_experts, ep,
            ffn_mult=3 if mc.ffn_type == "swiglu" else 2)
