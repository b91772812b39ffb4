"""Two-phase MoE serving runtime (paper §5/§6.2).

Per MoE layer the Server:
  phase 1: estimates next-layer expert popularity from each token's sample
           path (PathProfile Ψ lookup — overlapped with compute on a real
           cluster), then *reuses the layer's cached PlacementPlan* while
           the estimate's top-2k set still matches the popularity the plan
           was built from (PlanCache); only on drift does it re-plan
           (Eq. 1 + FFD replication/packing);
  gate:    the layer's block call (below) ends in the actual gating
           network's top-k, read on the host once (the full MoE dispatch
           re-derives the identical gating inside jit);
  phase 2: compares top-2k estimated vs actual experts; on deviation,
           re-plans from the actual popularity (blocking — the paper's
           ~23% fine-tune case) and refreshes the cache;
  dispatch: executes the MoE layer through the *distributed plan-honoring
           path* ``core.serving.serve_moe_layer`` — replica round-robin
           routing, packed experts, a2a to slot owners — under the final
           plan.  Device loads are additionally recorded for the latency
           model.

That per-layer core (``_serve_moe``) backs three entry points:

  ``serve_batch``    full-sequence scoring (no cache; the PR-1 path)
  ``prefill_batch``  full-sequence + KV-cache capture: returns last-token
                     logits, an ``LMCache`` sized to ``cache_len`` and the
                     rolling path-ID state, so generation can continue
                     incrementally;
  ``decode_batch``   ONE token per request against the cache — the paper's
                     latency-bound decoding regime (§5): tiny batches,
                     popularity skew, per-layer plan-scheduled dispatch.

Each MoE layer of a forward enqueues two compiled programs around the host
planner: a block call (the previous layer's residual add, the group's
attention and dense sublayers, the router's top-k) and the dispatch; one
head call ends the forward (final norm, unembed, the cache it hands on).
Decode reads the stacked KV cache in place and writes its new rows into a
copy, so a cache once returned stays valid.

The Server drives real model weights (GroupParams stacks: the paper models,
mixtral, llama4) and produces exact logits plus per-layer scheduling stats.
``runtime.engine`` wraps it in a continuous-batching front end (request
queue, prefill/decode lifecycle, token-budget micro-batches, per-request
path + KV state).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.gating import capacity
from repro.core.placement import (PlacementPlan, PlanCache, identity_plan,
                                  needs_finetune, plan_from_replicas,
                                  plan_placement, route_weights)
from repro.core.popularity import PathProfile
from repro.core.serving import (PlanArrays, dp_shard_count,
                                mask_dead_route_weights,
                                replica_token_counts, serve_moe_layer,
                                slot_capacity)
from repro.kernels.ref import router_logits
from repro.models import lm as lm_mod
from repro.models.attention import (KVCache, attention,
                                    decode_attention_rows, write_decode_rows)
from repro.models.layers import rms_norm
from repro.models.lm import LMCache
from repro.obs import ObsContext


@dataclass
class ServerConfig:
    top_k: int = 1                 # paper: top-1 gating at inference
    path_len: int = 3
    max_pack: int = 4
    n_devices: int = 0             # 0 => n_experts (paper: 1 expert/device)
    use_estimation: bool = True    # ablation: False = schedule after gating
    use_finetuning: bool = True    # ablation: False = never fine-tune
    schedule_policy: str = "lina"  # lina | uniform (DeepSpeed baseline)
    plan_cache: bool = True        # reuse plans across batches until drift
    route_mode: str = "weighted"   # weighted (§5 histogram split) |
    #                                round_robin (positional ablation)
    phase2_timeout_s: float = 0.0  # watchdog: a phase-2 re-plan slower than
    #                                this suppresses further fine-tunes for
    #                                ``phase2_backoff`` plan calls (0 = off)
    phase2_backoff: int = 8


@dataclass
class LayerStats:
    layer: int
    est_pop: np.ndarray
    actual_pop: np.ndarray
    finetuned: bool
    est_accurate: bool
    plan_reused: bool              # plan came from the cache (no re-plan)
    device_load: np.ndarray        # token share per device (actual workload)
    n_tokens: int = 0              # valid tokens this layer dispatched
    replica_load: Optional[np.ndarray] = None
    #                                [n_slots] realized valid-token count per
    #                                (device, sub-slot) after replica routing
    #                                (host mirror of the device split)


class ServeResult(NamedTuple):
    logits: np.ndarray             # [B, V] last-valid-token logits
    stats: List[LayerStats]
    path_ids: np.ndarray           # [B, S] final rolling path state


class PrefillResult(NamedTuple):
    logits: np.ndarray             # [B, V] last-valid-token logits
    stats: List[LayerStats]
    path_ids: np.ndarray           # [B, S] final rolling path state
    cache: LMCache                 # KV cache sized to cache_len, pos=lengths


class DecodeResult(NamedTuple):
    logits: np.ndarray             # [B, V] next-token logits
    stats: List[LayerStats]
    path_state: np.ndarray         # [B] rolling path state after this token
    cache: LMCache                 # updated KV cache, pos advanced by 1


class MoEServer:
    def __init__(self, cfg: ModelConfig, params, profile: PathProfile,
                 scfg: Optional[ServerConfig] = None, mesh=None,
                 obs: Optional[ObsContext] = None):
        assert cfg.moe.enabled, "MoEServer serves MoE architectures"
        scfg = scfg or ServerConfig()
        self.cfg = cfg
        self.params = params
        self.profile = profile
        self.scfg = scfg
        self.mesh = mesh
        # shared observability context: ``ServingEngine`` installs its own
        # here when given one, so one flag traces the whole serving stack
        self.obs = obs or ObsContext.disabled()
        self.n_dev = scfg.n_devices or cfg.moe.n_experts
        self.every = cfg.moe.every
        self.plan_cache = PlanCache(top_k=scfg.top_k) if scfg.plan_cache \
            else None
        # a forward is 2 * n_moe + 1 compiled calls: per MoE layer one
        # block (everything before the planner) and one dispatch (after
        # it), then one head; nothing runs eagerly in between.  Block and
        # head round every intermediate to the compute dtype, as the
        # op-by-op walk they replace did: XLA's default excess precision
        # would keep fused bf16 intermediates in f32 and move the served
        # token wherever two logits nearly tie
        rounded = {"xla_allow_excess_precision": False}
        self._block = jax.jit(self._block_fn, compiler_options=rounded)
        self._dispatch = jax.jit(self._dispatch_fn,
                                 static_argnames=("min_replicas", "cap"))
        self._head = jax.jit(self._head_fn, static_argnames=("cache_len",),
                             compiler_options=rounded)
        # weights are static across requests: cast once; the block and head
        # calls index the stacked params by a device-resident group index,
        # the dispatch takes one layer's experts, sliced once
        self._cparams = lm_mod.cast_for_compute(cfg, params)
        self.n_groups = cfg.n_layers // self.every
        self._gidx = [jnp.asarray(g, jnp.int32) for g in range(self.n_groups)]
        self._moe_cache: dict = {}
        self._plan_arrays: dict = {}
        # controller-published per-layer plans (repro.sched): while a layer
        # has an override the per-batch planner (phase 1 + phase 2) is
        # bypassed for it — the control loop owns placement at its own
        # cadence instead of per micro-batch
        self._plan_override: dict = {}
        self._override_fresh: set = set()
        # --- resilience state (repro.resilience) ---
        # devices masked out of planning and routing; fault_hook, when set,
        # is called as fault_hook("plan", layer) before each primary plan
        # build (the injection point for planner-crash faults)
        self.dead_devices: set = set()
        self.fault_hook = None
        self.degrade_stats: dict = {"planner_errors": 0, "phase2_timeouts": 0,
                                    "emergency_replans": 0}
        self._phase2_suppress = 0

    # --- adaptive scheduling (repro.sched) ---------------------------------
    def publish_plans(self, plans: dict) -> None:
        """Install controller-published plans ({layer: PlacementPlan}).

        Takes effect at the next micro-batch; in-flight decode state (KV
        caches, rolling path ids) is untouched — plans move experts across
        devices, they do not change the math (see
        ``test_engine_plan_swap_mid_decode_is_transparent``)."""
        self._plan_override.update(plans)
        self._override_fresh.update(plans.keys())

    # --- graceful degradation (repro.resilience) ---------------------------
    def fail_devices(self, devices) -> None:
        """Mask failed devices out of routing and planning, without touching
        in-flight decode state.

        Three rungs, cheapest first: (1) every served plan's route weights
        get their dead-replica columns zeroed (``_plan_device`` re-applies
        ``mask_dead_route_weights`` on upload — zero-migration, the kernel
        simply stops sending tokens there); (2) cached plans that placed an
        expert on a dead device are invalidated so the next batch re-plans
        under the mask; (3) a controller-published override plan that left
        some expert with NO surviving replica is emergency-rebuilt in place
        (incremental ``plan_from_replicas`` keeps surviving replicas where
        they are)."""
        devs = {int(d) for d in devices if 0 <= d < self.n_dev}
        if not devs - self.dead_devices:
            return
        self.dead_devices |= devs
        self._plan_arrays.clear()      # route-weight mask must re-apply
        if self.plan_cache is not None:
            self.plan_cache.invalidate_devices(self.dead_devices)
        rebuilt = {}
        for li, plan in self._plan_override.items():
            if self._plan_orphaned(plan):
                rebuilt[li] = plan_from_replicas(
                    plan.popularity, plan.n_replicas, self.n_dev,
                    max_pack=self.scfg.max_pack,
                    rep_width=plan.replica_of.shape[1], prev=plan,
                    dead_devices=self.dead_devices)
        if rebuilt:
            self.degrade_stats["emergency_replans"] += len(rebuilt)
            self.obs.metrics.counter(
                "server_degrade_total",
                kind="emergency_replan").inc(len(rebuilt))
            self.publish_plans(rebuilt)

    def revive_devices(self, devices) -> None:
        """Return repaired devices to the pool; plans re-expand onto them at
        the next re-plan (cache drift / controller cadence)."""
        self.dead_devices -= {int(d) for d in devices}
        self._plan_arrays.clear()

    def _plan_orphaned(self, plan: PlacementPlan) -> bool:
        """True iff some expert's every live replica sits on a dead device
        (zero-weight masking alone would drop its tokens)."""
        if not self.dead_devices:
            return False
        ro = np.asarray(plan.replica_of)
        live = (np.arange(ro.shape[1])[None, :]
                < np.clip(plan.n_replicas, 1, ro.shape[1])[:, None]) \
            & (ro >= 0)
        on_dead = np.zeros(ro.shape, bool)
        dev = np.where(live, ro // plan.max_pack, -1)
        for d in self.dead_devices:
            on_dead |= dev == d
        return bool((live & ~on_dead).sum(1).min() == 0)

    def warmup(self, *, seqs=(), rows=(1,), min_replicas_grid=(1, 2),
               max_new_tokens: int = 8) -> int:
        """Pre-trace the jitted serve paths so neither the first request nor
        a plan swap to an already-seen replica count is compile-dominated.

        Two grids:
          - full prefill (+ one decode step) at each prompt length in
            ``seqs`` with a single-row batch — the first-request p95 path;
          - the plan-honoring dispatch at every (decode row-bucket, cap,
            min_replicas, replica-table width) combination reachable from
            ``rows`` x ``min_replicas_grid`` — the shapes a controller plan
            swap or a new decode-batch bucket would otherwise compile
            inside a timed step.

        Plan-cache contents/stats and published overrides are restored, so
        warm-up leaves no scheduling trace.  Returns the number of traced
        calls.
        """
        import dataclasses as _dc

        cache = self.plan_cache
        saved_cache = (dict(cache._plans),
                       _dc.replace(cache.stats)) if cache is not None else None
        saved_ov = (dict(self._plan_override), set(self._override_fresh))
        traced = 0
        try:
            for s in seqs:
                pre = self.prefill_batch(np.zeros((1, int(s)), np.int64),
                                         cache_len=int(s) + max_new_tokens)
                traced += 1
                if max_new_tokens:
                    self.decode_batch(np.zeros((1,), np.int64), pre.cache,
                                      np.zeros((1,), np.int64))
                    traced += 1
            traced += self._warmup_dispatch(rows, min_replicas_grid)
        finally:
            if saved_cache is not None:
                cache._plans.clear()
                cache._plans.update(saved_cache[0])
                cache.stats.hits = saved_cache[1].hits
                cache.stats.misses = saved_cache[1].misses
                cache.stats.invalidations = saved_cache[1].invalidations
            self._plan_override = saved_ov[0]
            self._override_fresh = saved_ov[1]
        return traced

    def _warmup_dispatch(self, rows, min_replicas_grid) -> int:
        """Compile ``_dispatch`` for the (bucket, cap, min_replicas, width)
        grid; dedupes combinations that collapse to the same static key."""
        from repro.core.placement import plan_from_replicas

        cfg = self.cfg
        moe_p = self._moe_params(0)
        combos = set()
        for n_valid in sorted(set(int(r) for r in rows)):
            bucket = 1 << (n_valid - 1).bit_length()
            cap = self._valid_capacity(n_valid, bucket)
            for r in min_replicas_grid:
                r = int(min(r, (self.n_dev * self.scfg.max_pack)
                            // cfg.moe.n_experts, self.n_dev))
                if r < 1:
                    r = 1
                # controller plans carry an n_dev-wide replica table, the
                # per-batch planner a max_pack-wide one — trace both
                for width in {self.n_dev, self.scfg.max_pack}:
                    combos.add((bucket, cap, r, width))
        for bucket, cap, r, width in sorted(combos):
            plan = plan_from_replicas(
                np.full((cfg.moe.n_experts,), 1.0 / cfg.moe.n_experts),
                np.full((cfg.moe.n_experts,), r, np.int64),
                self.n_dev, max_pack=self.scfg.max_pack, rep_width=width)
            se, ro, nr, rw = self._plan_device(plan)
            h2 = jnp.zeros((bucket, cfg.d_model), jnp.dtype(cfg.dtype))
            jax.block_until_ready(self._dispatch(
                moe_p, h2, se, ro, nr, rw,
                min_replicas=int(plan.n_replicas.min()), cap=cap))
        return len(combos)

    # --- the walk's compiled calls ----------------------------------------
    def _launch(self, fn, *args, **kwargs):
        """Enqueue one compiled program of the walk: a block, a dispatch or
        the head.  Each launch counts once in ``server_program_calls_total``
        (2 * n_moe + 1 a forward)."""
        self.obs.metrics.counter("server_program_calls_total").inc()
        return fn(*args, **kwargs)

    def _block_fn(self, cp, g, carry, kv, pos):
        """One MoE layer's device work before Lina's planner, one program
        for every layer: the previous MoE layer's residual add (the
        embedding lookup for the first layer), the group's attention and
        dense FFN sublayers, the MoE sublayer's input norm and the router's
        top-k.

        cp: the compute params, stacked over groups; g: int32 group index;
        carry: tokens [B, S] (first layer) or the previous MoE layer's
        (x [B, S, d], h2 [T, d], y [T, d]); kv: None for a full-sequence
        forward, else the decode step's stacked cache (k, v)
        [G, every, B, S_cap, KV, hd], read at ``g`` in place; pos: [B]
        decode positions.  Returns (x, h2, idx [T, top_k], k, v), k/v the
        group's [every, B, S, KV, hd] over the sequence, or its
        [every, B, KV, hd] decode rows."""
        cfg = self.cfg
        st = cp.stack
        if isinstance(carry, tuple):
            x = self._moe_residual(st, g - 1, *carry)
        else:
            x = cp.embed[carry].astype(jnp.dtype(cfg.dtype))
        ks, vs = [], []
        for j in range(self.every):
            a_p = jax.tree.map(lambda a: a[g, j], st.attn)
            h = rms_norm(x, st.ln1[g, j], cfg.norm_eps)
            if kv is None:
                y, kv_j = attention(None, a_p, h, cfg)
                k_j, v_j = kv_j
            else:
                y, k_j, v_j = decode_attention_rows(
                    a_p, h, KVCache(kv.k[g, j], kv.v[g, j]), pos, cfg)
            x = x + y
            ks.append(k_j)
            vs.append(v_j)
            h = rms_norm(x, st.ln2[g, j], cfg.norm_eps)
            if j < self.every - 1:
                x = x + self._dense_ffn(
                    jax.tree.map(lambda a: a[g, j], st.ffn), h)
        b, s, d = x.shape
        h2 = h.reshape(b * s, d)
        probs = jax.nn.softmax(
            router_logits(h2, st.moe.router[g]).astype(jnp.float32), -1)
        _, idx = jax.lax.top_k(probs, self.scfg.top_k)
        return x, h2, idx.astype(jnp.int32), jnp.stack(ks), jnp.stack(vs)

    def _head_fn(self, cp, carry, pos, rows, kv, *, cache_len: int):
        """The device work after the last MoE layer's dispatch, one program
        a forward: its residual add, the final norm, each row's last valid
        position, the unembed and the cache the forward hands on.

        pos: [B] valid lengths (full sequence) or decode positions; rows:
        the block calls' (k, v) per group, or None (scoring: no cache); kv:
        the decode step's input cache, or None (full sequence); cache_len:
        the capacity of a captured cache.  Returns (logits [B, V], LMCache
        or None).  A decode step writes its rows into a copy of ``kv``:
        callers may read the input cache again."""
        cfg = self.cfg
        x = self._moe_residual(cp.stack, self.n_groups - 1, *carry)
        x = rms_norm(x, cp.final_norm, cfg.norm_eps)
        if kv is None:
            x = x[jnp.arange(x.shape[0]), jnp.maximum(pos - 1, 0)]
        else:
            x = x[:, 0]
        logits = x @ lm_mod.unembed_weight(cp)
        if rows is None:
            return logits, None
        k = jnp.stack([r[0] for r in rows])
        v = jnp.stack([r[1] for r in rows])
        if kv is not None:
            kv = KVCache(write_decode_rows(kv.k, k, pos, cfg),
                         write_decode_rows(kv.v, v, pos, cfg))
            return logits, LMCache(kv, None, None, pos + 1)
        pad = ((0, 0),) * 3 + ((0, cache_len - k.shape[3]),) + ((0, 0),) * 2
        kv = KVCache(jnp.pad(k, pad), jnp.pad(v, pad))
        return logits, LMCache(kv, None, None, pos)

    def _moe_residual(self, st, g, x, h2, y):
        """x + the MoE layer of group ``g``'s output (``y`` from the
        dispatch, plus the shared expert on ``h2`` if the params hold one)."""
        moe_y = y.reshape(x.shape)
        if st.shared is not None:
            shared = jax.tree.map(lambda a: a[g], st.shared)
            moe_y = moe_y + self._dense_ffn(shared, h2.reshape(x.shape))
        return x + moe_y

    def _dense_ffn(self, p, h):
        return lm_mod._ffn_apply(p, h, self.cfg.ffn_type, None)

    def _dispatch_fn(self, moe_p, h2, se, ro, nr, rw, *, min_replicas: int,
                     cap: int):
        """The distributed MoE layer under the final plan: weighted (or
        round-robin) replica split + packed experts via ``serve_moe_layer``
        (shard_map; collapses to single-device collectives on the default
        mesh)."""
        plan = PlanArrays(se, ro, nr, rw)
        y, _, _ = serve_moe_layer(self.mesh, h2, moe_p, self.cfg.moe, plan,
                                  ffn_type=self.cfg.ffn_type,
                                  top_k=self.scfg.top_k,
                                  min_replicas=min_replicas,
                                  cap_override=cap,
                                  route_mode=self.scfg.route_mode)
        return y

    def _valid_capacity(self, n_valid: int, n_total: int) -> int:
        """Per-device gating capacity sized from the *valid* token count so
        engine padding rows cannot change real tokens' dispatch (pad rows
        sort after real rows in slot order; with capacity fixed they can
        only be dropped, never displace)."""
        shards = dp_shard_count(self.mesh, n_total)
        return capacity(-(-n_valid // shards), self.cfg.moe.n_experts,
                        self.scfg.top_k, self.cfg.moe.capacity_factor)

    # --- planning ----------------------------------------------------------
    def _plan_layer(self, li: int, est: np.ndarray, actual: np.ndarray):
        """Phase 1 (cache-aware) + phase 2.  Returns
        (plan, finetuned, accurate, reused)."""
        cfg, scfg = self.cfg, self.scfg
        met = self.obs.metrics
        accurate = not needs_finetune(est, actual, scfg.top_k)
        reused = False
        finetuned = False
        override = self._plan_override.get(li)
        if override is not None:
            # the control loop owns this layer's placement: no per-batch
            # re-plan, no blocking phase-2 — drift is handled at the
            # controller's cadence.  ``reused`` is False exactly once per
            # publish (the swap itself), True while the plan is live.
            fresh = li in self._override_fresh
            self._override_fresh.discard(li)
            met.counter("server_plan_lookup_total", result="override").inc()
            return override, False, accurate, not fresh
        if scfg.schedule_policy == "uniform":
            # the uniform layout is static: look up before building so a
            # hit skips plan construction entirely
            uniform = np.full((cfg.moe.n_experts,),
                              1.0 / cfg.moe.n_experts, np.float32)
            if self.plan_cache is not None:
                with self.obs.tracer.span("plan.lookup", layer=li):
                    cached = self.plan_cache.lookup(li, uniform)
                if cached is not None:
                    met.counter("server_plan_lookup_total",
                                result="hit").inc()
                    return cached, False, accurate, True
            met.counter("server_plan_lookup_total", result="miss").inc()
            plan = identity_plan(cfg.moe.n_experts, self.n_dev,
                                 scfg.max_pack)
            if self.plan_cache is not None:
                self.plan_cache.store(li, plan)
            return plan, False, accurate, False

        # the popularity basis the final plan must honor: the estimate in
        # the common case, the realized popularity when phase 2 triggers
        # (or when estimation is ablated away entirely).  The watchdog's
        # backoff window suppresses the blocking phase-2 re-plan and serves
        # from the phase-1 estimate instead.
        suppressed = self._phase2_suppress > 0
        if suppressed:
            self._phase2_suppress -= 1
        if not scfg.use_estimation:
            basis, phase2 = actual, False
        elif scfg.use_finetuning and not accurate and not suppressed:
            basis, phase2 = actual, True
        else:
            basis, phase2 = est, False
        plan = None
        if self.plan_cache is not None:
            with self.obs.tracer.span("plan.lookup", layer=li):
                plan = self.plan_cache.lookup(li, basis)
            reused = plan is not None
        met.counter("server_plan_lookup_total",
                    result="hit" if reused else "miss").inc()
        # a cache hit absorbs the phase-2 case: the blocking re-plan (the
        # paper's ~23% fine-tune cost) only happens when the basis drifted
        finetuned = phase2 and not reused
        if plan is None:
            plan = self._build_plan(li, basis, est, phase2)
            if self.plan_cache is not None:
                self.plan_cache.store(li, plan)
        return plan, finetuned, accurate, reused

    def _build_plan(self, li: int, basis: np.ndarray, est: np.ndarray,
                    phase2: bool) -> PlacementPlan:
        """Plan build wrapped in the phase-2 watchdog: a planner exception
        falls back down a degradation ladder (phase-1 estimate, then the
        masked uniform layout) instead of failing the batch, and a phase-2
        build slower than ``phase2_timeout_s`` suppresses further
        fine-tunes for ``phase2_backoff`` plan calls.  Either event arms
        the backoff and bumps ``degrade_stats``."""
        scfg = self.scfg
        met = self.obs.metrics
        # the watchdog stopwatch doubles as the phase-2 span: ``timed``
        # always measures (the timeout decision is functional), and records
        # a ``phase2.finetune`` / ``plan.build`` span when tracing is on
        sw = self.obs.tracer.timed(
            "phase2.finetune" if phase2 else "plan.build", layer=li)
        try:
            with sw:
                if self.fault_hook is not None:
                    self.fault_hook("plan", li)
                plan = plan_placement(basis, self.n_dev, scfg.max_pack,
                                      dead_devices=self.dead_devices)
        except Exception:
            self.degrade_stats["planner_errors"] += 1
            met.counter("server_degrade_total", kind="planner_error").inc()
            self._phase2_suppress = max(self._phase2_suppress,
                                        scfg.phase2_backoff)
            try:
                return plan_placement(est, self.n_dev, scfg.max_pack,
                                      dead_devices=self.dead_devices)
            except Exception:
                e = self.cfg.moe.n_experts
                return plan_from_replicas(
                    np.full((e,), 1.0 / e), np.ones((e,), np.int64),
                    self.n_dev, max_pack=scfg.max_pack,
                    dead_devices=self.dead_devices)
        if phase2 and scfg.phase2_timeout_s > 0 and \
                sw.dt > scfg.phase2_timeout_s:
            self.degrade_stats["phase2_timeouts"] += 1
            met.counter("server_degrade_total", kind="phase2_timeout").inc()
            self._phase2_suppress = scfg.phase2_backoff
        return plan

    # --- the shared per-layer two-phase core -------------------------------
    def _serve_moe(self, li: int, h2, idx, valid: np.ndarray,
                   path_ids: np.ndarray, has_state: bool):
        """Phase-1 estimate -> PlanCache lookup -> phase-2 fine-tune on
        drift -> plan-honoring dispatch, for one MoE layer.

        h2: [T, d] hidden states and idx: [T, top_k] the gate's choices,
        both from the layer's block call; valid: [T] bool; path_ids: [T]
        rolling path hashes.  ``has_state`` marks carried path state
        (incremental decode), which lets early layers use the profile
        instead of the uniform cold-start estimate.  Returns (y [T, d],
        top1 [T], stats).
        """
        cfg, scfg = self.cfg, self.scfg
        tr = self.obs.tracer
        with tr.span("server.layer", layer=li) as lsp:
            with tr.span("phase1.estimate"):
                override = self._plan_override.get(li)
                if override is not None:
                    # controller-owned layer: the plan's own popularity basis
                    # (the telemetry EWMA it was built from) stands in for
                    # the per-batch Ψ estimate — no per-token profile lookup
                    # on the hot path
                    est = np.asarray(override.popularity, np.float32)
                elif scfg.schedule_policy == "uniform" or \
                        not scfg.use_estimation or \
                        (li < scfg.path_len and not has_state):
                    est = np.full((cfg.moe.n_experts,),
                                  1.0 / cfg.moe.n_experts, np.float32)
                else:
                    est = self.profile.estimate_popularity(
                        li, path_ids[valid] if valid.any() else path_ids)

            # the layer's one device->host read: the first choice feeds the
            # popularity and the path state, all of it the replica mirror
            idx = self._to_host("sync.top1", idx)
            top1 = idx[:, 0]
            actual = np.bincount(top1, weights=valid.astype(np.float64),
                                 minlength=cfg.moe.n_experts)
            actual = actual / max(actual.sum(), 1.0)

            plan, finetuned, accurate, reused = self._plan_layer(li, est,
                                                                 actual)

            with tr.span("dispatch"):
                # dispatch under the final plan (distributed path); capacity
                # sized from valid tokens, not the padded batch
                cap = self._valid_capacity(int(valid.sum()), h2.shape[0])
                min_rep = int(plan.n_replicas.min())
                se, ro, nr, rw = self._plan_device(plan)
                y = self._launch(self._dispatch, self._moe_params(li), h2,
                                 se, ro, nr, rw, min_replicas=min_rep,
                                 cap=cap)

            with tr.span("server.mirror"):
                # host mirror of the replica split: realized valid-token
                # count per (device, sub-slot) — what the telemetry
                # bus/controller observes as post-routing imbalance
                rep_load = replica_token_counts(
                    idx, self._host_plan(plan),
                    cap, slot_capacity(cap, min_rep), valid=valid,
                    dp_shards=dp_shard_count(self.mesh, h2.shape[0]),
                    route_mode=scfg.route_mode)
            lsp.set(finetuned=finetuned, reused=reused, accurate=accurate)

        met = self.obs.metrics
        met.counter("server_layers_served_total").inc()
        if finetuned:
            met.counter("server_phase2_finetunes_total").inc()

        # loads are always evaluated against the ACTUAL popularity — the
        # plan decides placement, the workload decides load
        stat = LayerStats(li, np.asarray(est), np.asarray(actual), finetuned,
                          accurate, reused,
                          plan.device_load(actual.astype(np.float32)),
                          n_tokens=int(valid.sum()),
                          replica_load=rep_load)
        return y, top1, stat

    def _to_host(self, name: str, x) -> np.ndarray:
        """Read a device array on the host: the host waits here until the
        device has computed ``x``.  Every such read on the serving path
        goes through here, as one ``sync.*`` span (``name``) and one
        ``server_host_syncs_total``."""
        self.obs.metrics.counter("server_host_syncs_total").inc()
        with self.obs.tracer.span(name):
            return np.asarray(x)

    def _plan_device(self, plan: PlacementPlan):
        """Device-resident plan arrays, cached per plan object — the
        PlanCache keeps plan identity stable across batches/steps, so the
        host->device upload (and the route-weight IPF) happens once per
        (layer, popularity regime)."""
        ent = self._plan_arrays.get(id(plan))
        if ent is None or ent[0] is not plan:
            if len(self._plan_arrays) > 256:
                self._plan_arrays.clear()
            host_rw = route_weights(plan)
            if self.dead_devices:
                # degradation rung 1: zero-migration re-route — dead-replica
                # columns drop to weight 0 so the weighted split sends them
                # nothing (``fail_devices`` cleared this cache to re-apply)
                host_rw = np.asarray(mask_dead_route_weights(
                    host_rw, plan.replica_of, plan.max_pack,
                    self.dead_devices, xp=np), np.float32)
            with self.obs.tracer.span("plan.upload"):
                ent = (plan, jnp.asarray(plan.slot_expert),
                       jnp.asarray(plan.replica_of),
                       jnp.asarray(plan.n_replicas), jnp.asarray(host_rw),
                       PlanArrays(plan.slot_expert, plan.replica_of,
                                  plan.n_replicas, host_rw))
            self._plan_arrays[id(plan)] = ent
        return ent[1], ent[2], ent[3], ent[4]

    def _host_plan(self, plan: PlacementPlan) -> PlanArrays:
        """Host-side (numpy-leaf) PlanArrays for ``plan``, sharing the
        cached route-weight table with ``_plan_device``."""
        self._plan_device(plan)
        return self._plan_arrays[id(plan)][5]

    def _moe_params(self, g):
        """Group ``g``'s MoE params, sliced out of the stack once."""
        moe_p = self._moe_cache.get(g)
        if moe_p is None:
            moe_p = jax.tree.map(lambda a: a[g], self._cparams.stack.moe)
            self._moe_cache[g] = moe_p
        return moe_p

    # --- serving loop -------------------------------------------------------
    def serve(self, tokens: np.ndarray, lengths=None) -> tuple:
        """tokens: [B, S] -> (last logits [B, V], stats list[LayerStats])."""
        res = self.serve_batch(tokens, lengths=lengths)
        return res.logits, res.stats

    def serve_batch(self, tokens: np.ndarray, lengths=None,
                    path_init: Optional[np.ndarray] = None) -> ServeResult:
        """Serve one (micro-)batch through the full model (no cache).

        tokens:    [B, S] token ids (rows may be right-padded)
        lengths:   optional [B] valid-token counts; 0 marks an all-padding
                   row (engine batch-shape bucketing).  Padded positions
                   still flow through the network (static shapes) but are
                   excluded from popularity statistics, and each row's
                   logits are read at its last *valid* position.
        path_init: optional [B, S] rolling path-ID state from a previous
                   step of the same requests (engine-carried).
        """
        logits, stats, path_ids, _ = self._forward(tokens, lengths, path_init,
                                                   cache_len=0)
        return ServeResult(logits, stats, path_ids)

    def prefill_batch(self, tokens: np.ndarray, lengths=None,
                      path_init: Optional[np.ndarray] = None,
                      cache_len: Optional[int] = None) -> PrefillResult:
        """serve_batch + KV-cache capture: the prompt phase of generation.

        ``cache_len`` sizes the per-row cache capacity (>= S; pass
        prompt_len + max_new_tokens so decode never overflows).  The
        returned cache's ``pos`` is each row's valid length, so
        ``decode_batch`` continues exactly where the prompt ended.
        """
        s = np.asarray(tokens).shape[1]
        cache_len = max(cache_len or s, s)
        # the incremental path writes the cache linearly (no ring); a
        # sliding-window model whose context exceeded the window would
        # silently diverge from full re-prefill — reject it loudly
        if self.cfg.sliding_window and cache_len > self.cfg.sliding_window:
            raise NotImplementedError(
                "incremental decode does not support sliding-window "
                f"contexts beyond the window ({cache_len} > "
                f"{self.cfg.sliding_window})")
        logits, stats, path_ids, cache = self._forward(
            tokens, lengths, path_init, cache_len=cache_len)
        return PrefillResult(logits, stats, path_ids, cache)

    def _walk_stack(self, carry, *, kv, pos, valid, path_ids, has_state):
        """The layer walk shared by full-sequence forward and incremental
        decode: per MoE layer one block call, Lina's planner on its one
        read, and one dispatch call.  ``carry`` is the tokens; ``kv``/``pos``
        the decode step's cache and positions (None over a sequence).
        Returns (carry for the head, stats, path_ids, rows), rows the
        block calls' (k, v) per group."""
        cfg = self.cfg
        stats: List[LayerStats] = []
        rows = []
        tr = self.obs.tracer
        for g in range(self.n_groups):
            with tr.span("server.block", layer=g):
                x, h2, idx, k, v = self._launch(
                    self._block, self._cparams, self._gidx[g], carry, kv, pos)
            rows.append((k, v))
            y, top1, stat = self._serve_moe(g, h2, idx, valid, path_ids,
                                            has_state=has_state)
            carry = (x, h2, y)
            stats.append(stat)
            path_ids = (path_ids * cfg.moe.n_experts + top1) \
                % self.profile.n_buckets
        return carry, stats, path_ids, rows

    def _forward(self, tokens, lengths, path_init, *, cache_len: int):
        """Full-sequence forward; captures an LMCache when cache_len > 0."""
        tokens = np.asarray(tokens)
        b, s = tokens.shape
        if lengths is None:
            lengths = np.full((b,), s, np.int64)
        lengths = np.asarray(lengths, np.int64)
        valid = (np.arange(s)[None, :] < lengths[:, None]).reshape(b * s)
        path_ids = np.zeros((b * s,), np.int64) if path_init is None \
            else np.asarray(path_init, np.int64).reshape(b * s)
        carry, stats, path_ids, rows = self._walk_stack(
            tokens.astype(np.int32), kv=None, pos=None, valid=valid,
            path_ids=path_ids, has_state=False)
        with self.obs.tracer.span("server.head"):
            logits, cache = self._launch(
                self._head, self._cparams, carry, lengths.astype(np.int32),
                rows if cache_len else None, None, cache_len=cache_len)
        logits = self._to_host("sync.logits", logits)
        return logits, stats, path_ids.reshape(b, s), cache

    def decode_batch(self, tokens, cache: LMCache, path_state,
                     valid=None) -> DecodeResult:
        """One incremental decode step: ONE token per in-flight request.

        tokens:     [B] the most recent token of each request
        cache:      LMCache from prefill_batch / a previous decode_batch
                    (kv: [G, every, B, S_cap, KV, hd]; pos: [B])
        path_state: [B] rolling path-ID state (most recent token's hash)
        valid:      optional [B] bool; False rows are batch padding

        Runs the SAME per-layer two-phase core as prefill — estimate from
        the carried path state, PlanCache with top-2k drift invalidation,
        phase-2 fine-tune on miss, plan-honoring dispatch — in the regime
        the paper's §5 targets: tiny latency-bound batches.  Per-layer
        top-1 choices keep rolling the path state during generation.
        """
        tokens = np.asarray(tokens).reshape(-1, 1)
        b = tokens.shape[0]
        if valid is None:
            valid = np.ones((b,), bool)
        valid = np.asarray(valid, bool)
        path_ids = np.asarray(path_state, np.int64).reshape(b).copy()
        carry, stats, path_ids, rows = self._walk_stack(
            tokens.astype(np.int32), kv=cache.kv, pos=cache.pos,
            valid=valid, path_ids=path_ids, has_state=True)
        with self.obs.tracer.span("server.head"):
            logits, new_cache = self._launch(
                self._head, self._cparams, carry, cache.pos, rows, cache.kv,
                cache_len=0)
        logits = self._to_host("sync.logits", logits)
        return DecodeResult(logits, stats, path_ids, new_cache)


def profile_from_training(cfg: ModelConfig, params, batches,
                          path_len: int = 3, mesh=None) -> PathProfile:
    """Profiling stage (§5.2): replay data through the model, collect
    per-layer top-1 expert choices, accumulate Ψ tables."""
    n_moe = cfg.n_moe_layers
    prof = PathProfile(n_layers=n_moe, n_experts=cfg.moe.n_experts,
                       path_len=path_len)
    fwd = jax.jit(lambda p, b: lm_mod.forward_train(
        mesh, cfg, p, b, lina=False).expert_choices)
    for batch in batches:
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        choices = np.asarray(fwd(params, b))       # [n_moe, T]
        prof.profile_batch(choices)
    return prof
