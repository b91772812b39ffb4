"""Serving engine: plan cache (§5.2 drift invalidation), slot capacity under
replication, continuous-batching queue/micro-batch behavior, numerics of
the distributed dispatch path the server routes through, and the
prefill/decode split (incremental KV-cache decoding must reproduce full
re-prefill logits, and the engine must never re-run prefill mid-decode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, get_config
from repro.configs.base import MoEConfig
from repro.core import init_moe_params, moe_layer
from repro.core.placement import (PlanCache, identity_plan, needs_finetune,
                                  plan_placement, PlacementPlan)
from repro.core.popularity import (PathProfile, estimation_accuracy,
                                   top2k_sets_match)
from repro.core.serving import (PlanArrays, serve_moe_layer, slot_capacity,
                                stack_plan_arrays)
from repro.models import lm as lm_mod
from repro.runtime.engine import EngineConfig, ServingEngine, simulate
from repro.runtime.server import MoEServer, ServerConfig


# --- top-2k check: one implementation, pinned semantics ---------------------

def test_top2k_check_is_single_implementation():
    est = np.array([.4, .3, .1, .05, .05, .04, .03, .03])
    same = est + 1e-3
    flipped = est[::-1].copy()
    for a, b in [(est, same), (est, flipped), (same, flipped)]:
        for k in (1, 2):
            assert estimation_accuracy(a, b, k) == top2k_sets_match(a, b, k)
            assert needs_finetune(a, b, k) == (not top2k_sets_match(a, b, k))
    # set semantics: order within the top-2k does not matter
    a = np.array([.5, .3, .1, .1])
    b = np.array([.3, .5, .1, .1])           # top-2 swapped, same set
    assert top2k_sets_match(a, b, 1)
    # 2k clips at E
    assert top2k_sets_match(a, b, 8)


# --- plan cache -------------------------------------------------------------

def test_plan_cache_reuse_and_invalidation():
    e = 8
    pop = np.array([.4, .2, .1, .1, .05, .05, .05, .05])
    cache = PlanCache(top_k=1)
    assert cache.lookup(0, pop) is None              # cold miss
    plan = plan_placement(pop, e, max_pack=4)
    cache.store(0, plan)
    # same top-2k set -> hit, even with perturbed magnitudes
    assert cache.lookup(0, pop * 1.1) is plan
    # drift: a different expert enters the top-2k -> invalidate
    drifted = pop.copy()
    drifted[7] = 0.9
    assert cache.lookup(0, drifted) is None
    assert cache.stats.hits == 1
    assert cache.stats.misses == 2
    assert cache.stats.invalidations == 1
    # entry was evicted: next lookup with the original pop misses again
    assert cache.lookup(0, pop) is None
    np.testing.assert_allclose(cache.stats.reuse_rate, 1 / 4)


def test_plan_cache_is_per_layer():
    pop = np.ones(4) / 4
    cache = PlanCache(top_k=1)
    cache.store(0, plan_placement(pop, 4))
    assert cache.lookup(1, pop) is None
    assert cache.lookup(0, pop) is not None


# --- slot capacity under replication ----------------------------------------

def test_slot_capacity_shrinks_with_replication():
    assert slot_capacity(64, 1) == 64
    assert slot_capacity(64, 2) == 32        # replicated -> smaller buffers
    assert slot_capacity(64, 3) == 22        # ceil division
    assert slot_capacity(16, 4) == 8         # floored at 8
    assert slot_capacity(24, 0) == 24        # degenerate guard


def test_serve_layer_replicated_buffers_match_unreplicated():
    """End-to-end regression: a fully-replicated plan served with shrunken
    per-slot buffers (min_replicas=2) matches the min_replicas=1 numerics
    and the reference training layer."""
    cfg = MoEConfig(n_experts=4, top_k=1, d_ff=32, capacity_factor=4.0)
    params = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    # uniform popularity over 4 experts on 8 devices -> every expert gets
    # 2 replicas (Eq. 1: n_e = 8 * 0.25 = 2)
    plan = plan_placement(np.ones(4) / 4, 8, max_pack=4)
    assert int(plan.n_replicas.min()) == 2
    pa = PlanArrays.from_plan(plan)
    y1, _, _ = serve_moe_layer(None, x, params, cfg, pa, top_k=1,
                               min_replicas=1)
    y2, _, _ = serve_moe_layer(None, x, params, cfg, pa, top_k=1,
                               min_replicas=2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    ref = moe_layer(None, x.reshape(4, 16, 16), params, cfg, lina=False,
                    top_k=1).y.reshape(64, 16)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref), atol=1e-4)


# --- server: plan cache wired into the serve loop ---------------------------

def _smoke_server(policy="lina", plan_cache=True, capacity_factor=None,
                  arch="gpt2-moe"):
    cfg = get_config(arch).smoke()
    if capacity_factor is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=capacity_factor))
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    prof = PathProfile(n_layers=cfg.n_moe_layers,
                       n_experts=cfg.moe.n_experts, path_len=2)
    scfg = ServerConfig(path_len=2, schedule_policy=policy,
                        plan_cache=plan_cache)
    return cfg, MoEServer(cfg, params, prof, scfg)


def test_server_plan_cache_amortizes_across_batches():
    cfg, server = _smoke_server()
    toks = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    _, stats1 = server.serve(toks)
    assert not any(s.plan_reused for s in stats1)    # cold caches
    _, stats2 = server.serve(toks)                   # identical traffic
    assert all(s.plan_reused for s in stats2)        # full reuse
    st = server.plan_cache.stats
    assert st.hits == len(stats2) and st.misses == len(stats1)


def test_server_without_plan_cache_never_reuses():
    cfg, server = _smoke_server(plan_cache=False)
    toks = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    for _ in range(2):
        _, stats = server.serve(toks)
        assert not any(s.plan_reused for s in stats)
    assert server.plan_cache is None


def test_server_config_default_not_shared():
    cfg = get_config("gpt2-moe").smoke()
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    prof = PathProfile(n_layers=cfg.n_moe_layers,
                       n_experts=cfg.moe.n_experts, path_len=2)
    a = MoEServer(cfg, params, prof)
    b = MoEServer(cfg, params, prof)
    assert a.scfg is not b.scfg                      # no shared default


# --- continuous-batching engine ---------------------------------------------

def test_engine_microbatch_formation_token_budget():
    cfg, server = _smoke_server()
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=32,
                                             max_batch_requests=8))
    rng = np.random.RandomState(0)
    for _ in range(5):
        eng.submit(rng.randint(0, cfg.vocab_size, (16,)), arrival=0.0)
    batch = eng._form_microbatch()
    assert len(batch) == 2                           # 2 * 16 fills the budget
    assert [r.rid for r in batch] == [0, 1]          # FCFS
    assert eng.pending() == 3
    # an over-budget single request still progresses
    eng2 = ServingEngine(server, EngineConfig(max_batch_tokens=8))
    eng2.submit(rng.randint(0, cfg.vocab_size, (16,)), arrival=0.0)
    assert len(eng2._form_microbatch()) == 1


def test_engine_serves_requests_and_matches_server():
    cfg, server = _smoke_server(capacity_factor=16.0)
    toks = np.random.RandomState(1).randint(0, cfg.vocab_size, (1, 16))
    ref_logits, _ = server.serve(toks)

    cfg2, server2 = _smoke_server(capacity_factor=16.0)
    eng = ServingEngine(server2, EngineConfig(max_batch_tokens=16))
    eng.submit(toks[0], arrival=0.0)
    results = eng.run()
    assert len(results) == 1
    np.testing.assert_allclose(results[0].logits, ref_logits[0],
                               atol=1e-4, rtol=1e-4)
    assert results[0].n_tokens == 16
    assert np.isfinite(results[0].logits).all()


def test_engine_ragged_batch_and_path_state():
    cfg, server = _smoke_server()
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64))
    rng = np.random.RandomState(2)
    r1 = eng.submit(rng.randint(0, cfg.vocab_size, (16,)), arrival=0.0)
    r2 = eng.submit(rng.randint(0, cfg.vocab_size, (9,)), arrival=0.0)
    results = eng.step(now=0.0)
    assert sorted(r.rid for r in results) == [r1, r2]
    by_rid = {r.rid: r for r in results}
    assert by_rid[r2].n_tokens == 9
    # per-request rolling path state persisted, sized to the request
    ps1 = eng.request_path_state(r1)
    ps2 = eng.request_path_state(r2)
    assert ps1.shape == (16,) and ps2.shape == (9,)
    assert (ps1 < server.profile.n_buckets).all()
    # a follow-up request carries its stream's rolling path state
    r3 = eng.submit(np.zeros(9, np.int64), arrival=1.0, prev_rid=r2)
    np.testing.assert_array_equal(eng.request_path_state(r3), ps2)
    results2 = eng.step(now=1.0)
    assert len(results2) == 1 and np.isfinite(results2[0].logits).all()
    # ... and its own final state differs from the seed after serving
    assert eng.request_path_state(r3).shape == (9,)


def test_engine_padding_rows_do_not_change_logits():
    """Bucketing 5 requests to 8 rows (3 all-pad rows) must not perturb the
    real requests' logits at the default capacity factor: capacity is sized
    from valid tokens and pad rows sort after real rows in slot order."""
    cfg, server = _smoke_server()
    rng = np.random.RandomState(5)
    reqs = [rng.randint(0, cfg.vocab_size, (12,)) for _ in range(5)]
    _, server_direct = _smoke_server()
    direct = server_direct.serve_batch(np.stack(reqs))
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=60,
                                             max_batch_requests=5))
    rids = [eng.submit(r, arrival=0.0) for r in reqs]
    results = {r.rid: r for r in eng.step(now=0.0)}
    for i, rid in enumerate(rids):
        np.testing.assert_allclose(results[rid].logits, direct.logits[i],
                                   atol=1e-4, rtol=1e-4)


# --- incremental decode: prefill + decode_batch vs full re-prefill ----------

def test_prefill_then_decode_matches_full_serve():
    """The distributed analog of test_decode_matches_prefill: prefill the
    first 8 tokens, then 4 incremental decode_batch steps (each one token
    per request through the per-layer two-phase core) must reproduce the
    full 12-token re-prefill logits."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(11)
    toks = rng.randint(0, cfg.vocab_size, (2, 12))
    _, ref_server = _smoke_server(capacity_factor=16.0)
    ref = ref_server.serve_batch(toks)

    pre = server.prefill_batch(toks[:, :8], cache_len=12)
    logits, cache, path = pre.logits, pre.cache, pre.path_ids[:, 7]
    for i in range(8, 12):
        dec = server.decode_batch(toks[:, i], cache, path)
        logits, cache, path = dec.logits, dec.cache, dec.path_state
        assert len(dec.stats) == cfg.n_moe_layers   # two-phase core per layer
    np.testing.assert_allclose(logits, ref.logits, atol=1e-3, rtol=1e-3)
    assert (np.asarray(cache.pos) == 12).all()
    # the rolling path state kept advancing during decode
    assert (path < server.profile.n_buckets).all()


def test_prefill_batch_matches_serve_batch_logits():
    """Cache capture must not perturb the forward numerics."""
    cfg, server = _smoke_server()
    toks = np.random.RandomState(12).randint(0, cfg.vocab_size, (2, 10))
    _, ref_server = _smoke_server()
    ref = ref_server.serve_batch(toks)
    pre = server.prefill_batch(toks, cache_len=16)
    np.testing.assert_allclose(pre.logits, ref.logits, atol=1e-5)
    np.testing.assert_array_equal(pre.path_ids, ref.path_ids)
    assert pre.cache.kv.k.shape[3] == 16            # [G, every, B, cap, ...]


def test_decode_batch_padding_rows_are_inert():
    """Bucketed decode batches carry all-padding rows; they must not change
    valid rows' logits (capacity is sized from valid tokens)."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(13)
    toks = rng.randint(0, cfg.vocab_size, (2, 8))
    pre = server.prefill_batch(toks, cache_len=10)
    dec = server.decode_batch(toks[:, -1] * 0 + 7, pre.cache,
                              pre.path_ids[:, -1])

    _, server2 = _smoke_server(capacity_factor=16.0)
    pre2 = server2.prefill_batch(toks, cache_len=10)
    k, v = pre2.cache.kv.k, pre2.cache.kv.v
    pad = jnp.zeros_like(k[:, :, :1])
    cache4 = lm_mod.LMCache(
        lm_mod.KVCache(jnp.concatenate([k, pad, pad], axis=2),
                       jnp.concatenate([v, pad, pad], axis=2)),
        None, None, jnp.concatenate([pre2.cache.pos,
                                     jnp.zeros((2,), jnp.int32)]))
    dec4 = server2.decode_batch(
        np.array([7, 7, 0, 0]), cache4,
        np.concatenate([np.asarray(pre2.path_ids[:, -1]), [0, 0]]),
        valid=np.array([True, True, False, False]))
    np.testing.assert_allclose(dec4.logits[:2], dec.logits, atol=1e-4,
                               rtol=1e-4)


# --- the serving walk: block / dispatch / head calls ------------------------

@pytest.mark.parametrize("arch", ["gpt2-moe", "llama4-maverick-400b-a17b"])
def test_walk_matches_lm_prefill_and_decode_step(arch):
    """The walk's block and head calls compute what the one-program model
    computes: prefill logits against ``lm.forward_prefill`` and a decode
    step's logits and cache against ``lm.decode_step``, both under the
    same uniform plan.  llama4's smoke stack has ``every = 2`` (a dense
    sublayer inside each block call) and a shared expert (added with the
    residual in the next block or the head)."""
    cfg, server = _smoke_server(capacity_factor=16.0, arch=arch)
    if arch != "gpt2-moe":
        assert server.every == 2 and server._cparams.stack.shared is not None
    toks = np.random.RandomState(14).randint(0, cfg.vocab_size, (2, 9))
    plan = PlanArrays.from_plan(identity_plan(
        cfg.moe.n_experts, server.n_dev, server.scfg.max_pack))
    pre = server.prefill_batch(toks[:, :8], cache_len=12)
    ref = lm_mod.forward_prefill(None, cfg, server.params,
                                 {"tokens": jnp.asarray(toks[:, :8])},
                                 serve_plan=plan, serve_top_k=1)
    np.testing.assert_allclose(pre.logits, np.asarray(ref.logits),
                               atol=1e-5, rtol=1e-5)
    dec = server.decode_batch(toks[:, 8], pre.cache, pre.path_ids[:, -1])
    ref_logits, ref_cache, ref_top1 = lm_mod.decode_step(
        None, cfg, server.params, pre.cache, jnp.asarray(toks[:, 8]),
        serve_plan=plan, serve_top_k=1)
    np.testing.assert_allclose(dec.logits, np.asarray(ref_logits),
                               atol=1e-5, rtol=1e-5)
    for got, want in ((dec.cache.kv.k, ref_cache.kv.k),
                      (dec.cache.kv.v, ref_cache.kv.v),
                      (dec.cache.pos, ref_cache.pos)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
    np.testing.assert_array_equal(
        [s.actual_pop for s in dec.stats],
        [np.bincount(t, minlength=cfg.moe.n_experts) / 2
         for t in np.asarray(ref_top1)])


def test_returned_caches_stay_valid_after_later_decodes():
    """A cache that ``prefill_batch`` or ``decode_batch`` returned is never
    donated or written: the engine decodes again from a kept batch and cuts
    slot rows out of it after later steps (``DecodeSlot.batch_ref``), as
    the serving benchmark's warm-up does."""
    from repro.runtime.engine import DecodeSlot
    cfg, server = _smoke_server(capacity_factor=16.0)
    toks = np.random.RandomState(15).randint(0, cfg.vocab_size, (2, 8))
    pre = server.prefill_batch(toks, cache_len=12)
    path = pre.path_ids[:, -1]
    host = lambda c: [np.array(a) for a in (c.kv.k, c.kv.v, c.pos)]
    pre_host = host(pre.cache)
    d1 = server.decode_batch(toks[:, -1], pre.cache, path)
    d1_again = server.decode_batch(toks[:, -1], pre.cache, path)
    np.testing.assert_array_equal(d1.logits, d1_again.logits)
    d1_host = host(d1.cache)
    d2 = server.decode_batch(toks[:, 0], d1.cache, d1.path_state)
    server.decode_batch(toks[:, 1], d2.cache, d2.path_state)
    for cache, want in ((pre.cache, pre_host), (d1.cache, d1_host)):
        for got, ref in zip(host(cache), want):
            np.testing.assert_array_equal(got, ref)
    slot = DecodeSlot(rid=0, arrival=0.0, prompt_len=8, max_new_tokens=4,
                      cap=12, kv_k=None, kv_v=None, pos=9, path_scalar=0,
                      path_history=[0], gen_tokens=[0], ttft=0.0,
                      batch_ref=d1.cache, batch_row=1)
    k, v = slot.materialize()
    np.testing.assert_array_equal(np.asarray(k), d1_host[0][:, :, 1])
    np.testing.assert_array_equal(np.asarray(v), d1_host[1][:, :, 1])
    for a in (*pre.cache.kv, *d1.cache.kv, *d2.cache.kv):
        assert not a.is_deleted()


def test_decode_step_launches_two_programs_a_moe_layer_and_runs_no_op():
    """After warm-up, a decode forward launches 2 * n_moe + 1 compiled
    programs (a block and a dispatch a MoE layer, then the head) and
    nothing else, and makes n_moe + 1 device->host reads.  With jit's C++
    fast path off, every jitted call (a ``jnp`` op's too) goes through
    ``_run_python_pjit`` and every primitive run op by op through
    ``EvalTrace.process_primitive``: the hooks see each launch."""
    from jax._src import core as jax_core
    from jax._src import pjit as jax_pjit
    cfg, server = _smoke_server(capacity_factor=16.0)
    toks = np.random.RandomState(16).randint(0, cfg.vocab_size, (2, 8))
    pre = server.prefill_batch(toks, cache_len=12)
    path = pre.path_ids[:, -1]
    met = server.obs.metrics
    launched = []
    process = jax_core.EvalTrace.process_primitive
    run_python = jax_pjit._run_python_pjit

    def op_by_op(trace, prim, args, params):
        launched.append(prim.name)
        return process(trace, prim, args, params)

    def jitted(p, args_flat, fun, *args, **kwargs):
        launched.append(fun.__name__)
        return run_python(p, args_flat, fun, *args, **kwargs)

    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax_pjit, "_get_fastpath_data", lambda *a, **k: None)
            mp.setattr(jax_pjit, "_run_python_pjit", jitted)
            mp.setattr(jax_core.EvalTrace, "process_primitive", op_by_op)
            jax.clear_caches()
            server.decode_batch(toks[:, -1], pre.cache, path)   # warm-up
            launched.clear()
            jnp.ones((2,)) + 1                 # the hooks see eager work
            assert {"broadcast_in_dim", "add"} <= set(launched)
            launched.clear()
            calls0 = met.value("server_program_calls_total")
            syncs0 = met.value("server_host_syncs_total")
            server.decode_batch(toks[:, -1], pre.cache, path)
    finally:
        jax.clear_caches()                     # fast paths back on
    n_moe = cfg.n_moe_layers
    assert launched == ["_block_fn", "_dispatch_fn"] * n_moe + ["_head_fn"]
    assert met.value("server_program_calls_total") - calls0 == 2 * n_moe + 1
    assert met.value("server_host_syncs_total") - syncs0 == n_moe + 1


# --- stacked per-layer plans through decode_step -----------------------------

def test_decode_step_stacked_plans_and_expert_choices():
    """decode_step must accept one plan per MoE layer (stacked PlanArrays)
    and surface per-layer top-1 expert choices; heterogeneous placements
    must not change logits (plans move experts, not math)."""
    cfg = REGISTRY["mixtral-8x22b"].smoke()
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(1))
    b = 2
    cache = lm_mod.init_cache(cfg, b, 8, jnp.float32)
    tok = jnp.zeros((b,), jnp.int32)
    e = cfg.moe.n_experts
    n_groups = cfg.n_layers // cfg.moe.every

    single = PlanArrays.from_plan(identity_plan(e, e, max_pack=2))
    l1, _, e1 = lm_mod.decode_step(None, cfg, params, cache, tok,
                                   serve_plan=single, serve_top_k=1)
    same = stack_plan_arrays([identity_plan(e, e, max_pack=2)] * n_groups)
    assert same.stacked and same.slot_expert.shape[0] == n_groups
    l2, _, e2 = lm_mod.decode_step(None, cfg, params, cache, tok,
                                   serve_plan=same, serve_top_k=1)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    assert e1.shape == (n_groups, b)

    skew = [plan_placement(np.roll([.7, .1, .1, .1], i), e, max_pack=2)
            for i in range(n_groups)]
    l3, _, e3 = lm_mod.decode_step(None, cfg, params, cache, tok,
                                   serve_plan=stack_plan_arrays(skew),
                                   serve_top_k=1)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l3), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e3))


# --- engine generation lifecycle ---------------------------------------------

def _counting_server(server):
    calls = {"prefill": 0, "decode": 0, "serve": 0, "decode_tokens": []}
    orig_p, orig_d, orig_s = (server.prefill_batch, server.decode_batch,
                              server.serve_batch)

    def prefill(*a, **k):
        calls["prefill"] += 1
        return orig_p(*a, **k)

    def decode(tokens, *a, **k):
        calls["decode"] += 1
        calls["decode_tokens"].append(np.asarray(tokens).size)
        return orig_d(tokens, *a, **k)

    def serve(*a, **k):
        calls["serve"] += 1
        return orig_s(*a, **k)

    server.prefill_batch = prefill
    server.decode_batch = decode
    server.serve_batch = serve
    return calls


def test_engine_decoding_never_reruns_prefill():
    """A generating request prefills exactly once; every later step is a
    single-token decode whose batch size is the number of in-flight
    requests — per-output-token cost independent of prompt length."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    calls = _counting_server(server)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64))
    rng = np.random.RandomState(21)
    eng.submit(rng.randint(0, cfg.vocab_size, (24,)), arrival=0.0,
               max_new_tokens=5)
    results = eng.run()
    assert len(results) == 1
    assert calls["prefill"] == 1 and calls["serve"] == 0
    assert calls["decode"] == 4                      # 5 tokens: 1 + 4 steps
    assert all(n == 1 for n in calls["decode_tokens"])   # never the prompt
    r = results[0]
    assert r.n_generated == 5 and r.tokens.shape == (5,)
    assert r.ttft is not None and r.ttft <= r.completion
    assert (r.tokens < cfg.vocab_size).all() and np.isfinite(r.logits).all()


def test_engine_generation_matches_manual_decode():
    cfg, server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(22)
    toks = rng.randint(0, cfg.vocab_size, (10,))
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=32))
    eng.submit(toks, arrival=0.0, max_new_tokens=4)
    out = eng.run()[0]

    _, ref = _smoke_server(capacity_factor=16.0)
    pre = ref.prefill_batch(toks[None], cache_len=14)
    cur, gen = int(np.argmax(pre.logits[0])), []
    gen.append(cur)
    cache, path = pre.cache, pre.path_ids[:, -1]
    for _ in range(3):
        dec = ref.decode_batch([cur], cache, path)
        cur = int(np.argmax(dec.logits[0]))
        gen.append(cur)
        cache, path = dec.cache, dec.path_state
    np.testing.assert_array_equal(out.tokens, gen)


def test_engine_mixes_decodes_with_new_prefills():
    """An in-flight decode and a newly arrived prefill share one step."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    calls = _counting_server(server)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64))
    rng = np.random.RandomState(23)
    r1 = eng.submit(rng.randint(0, cfg.vocab_size, (8,)), arrival=0.0,
                    max_new_tokens=3)
    eng.step(now=0.0)                                # prefill r1
    assert eng.active() == 1 and calls["prefill"] == 1
    r2 = eng.submit(rng.randint(0, cfg.vocab_size, (8,)), arrival=0.1,
                    max_new_tokens=2)
    eng.step(now=0.1)                # decode r1 AND prefill r2 in one step
    assert calls["prefill"] == 2 and calls["decode"] == 1
    assert eng.active() == 2
    results = eng.run()
    assert sorted(r.rid for r in results) == [r1, r2]
    assert {r.rid: r.n_generated for r in results} == {r1: 3, r2: 2}


def test_engine_mixed_score_and_generation_batch():
    """Score-only and generating requests admitted in the same step run as
    separate forwards: the score-only row completes via serve_batch (no
    cache allocated for it), the generating row prefills with a cache
    sized only to ITS prompt + budget."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    calls = _counting_server(server)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64))
    rng = np.random.RandomState(26)
    rg = eng.submit(rng.randint(0, cfg.vocab_size, (8,)), arrival=0.0,
                    max_new_tokens=2)
    rs = eng.submit(rng.randint(0, cfg.vocab_size, (12,)), arrival=0.0)
    done = eng.step(now=0.0)
    assert calls["serve"] == 1 and calls["prefill"] == 1
    assert [r.rid for r in done] == [rs]              # score-only finishes
    assert done[0].tokens is None
    results = eng.run()
    assert results[0].rid == rg and results[0].n_generated == 2


def test_engine_state_cache_never_evicts_active_requests():
    """state_cache overflow must not drop the path state of a request that
    is still mid-decode (satellite guard)."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64,
                                             state_cache=2))
    rng = np.random.RandomState(24)
    ra = eng.submit(rng.randint(0, cfg.vocab_size, (6,)), arrival=0.0,
                    max_new_tokens=8)
    eng.step(now=0.0)                                 # ra enters decode
    assert eng.active() == 1
    for i in range(4):                # churn completed states past the cap
        eng.submit(rng.randint(0, cfg.vocab_size, (6,)), arrival=0.1 + i)
        eng.step(now=0.1 + i)
    assert len(eng._path_states) <= 2 + 1             # cap + pinned active
    assert ra in eng._path_states                     # pinned, not evicted
    assert eng.request_path_state(ra) is not None
    results = eng.run()                               # ra finishes cleanly
    assert any(r.rid == ra and r.n_generated == 8 for r in results)


def test_engine_backpressure_bounds_active_slots():
    """Prefill admission is gated on free decode slots, so the in-flight
    KV working set never exceeds max_batch_requests; every request still
    completes (FCFS, no starvation)."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64,
                                             max_batch_requests=2))
    rng = np.random.RandomState(27)
    rids = [eng.submit(rng.randint(0, cfg.vocab_size, (6,)), arrival=0.0,
                       max_new_tokens=4) for _ in range(5)]
    results = []
    for _ in range(100):
        results.extend(eng.step(now=0.0))
        assert eng.active() <= 2
        if not eng.has_work():
            break
    assert sorted(r.rid for r in results) == rids
    assert all(r.n_generated == 4 for r in results)


def test_engine_simulate_generates_and_reports_tpot():
    cfg, server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(25)
    trace = [(rng.randint(0, cfg.vocab_size, (8,)), 0.01 * i)
             for i in range(4)]
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=32))
    results = simulate(eng, trace, max_new_tokens=3)
    assert len(results) == 4
    for r in results:
        assert r.n_generated == 3
        assert r.arrival <= r.ttft <= r.completion
        assert r.tpot is not None and r.tpot >= 0
    assert not eng.has_work()


def _tokens_of(results):
    return {r.rid: (None if r.tokens is None else r.tokens.tolist())
            for r in results}


def test_engine_plan_swap_mid_decode_is_transparent():
    """Controller-triggered plan swaps between micro-batches must not
    change any request's generated tokens: plans move experts across
    devices, they do not change the math, and decode state (KV cache +
    rolling path ids) survives the swap untouched."""
    from repro.sched import AdaptiveScheduler, ControllerConfig

    cfg, ref_server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, cfg.vocab_size, (10,)) for _ in range(3)]

    ref_eng = ServingEngine(ref_server, EngineConfig(max_batch_tokens=64))
    for p in prompts:
        ref_eng.submit(p, arrival=0.0, max_new_tokens=6)
    ref = _tokens_of(ref_eng.run())

    _, server = _smoke_server(capacity_factor=16.0)
    sched = AdaptiveScheduler(server, ControllerConfig(
        interval=1, min_swap_interval=1, min_observations=1,
        hysteresis=0.0, migration_weight=0.0))
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64),
                        scheduler=sched)
    for p in prompts:
        eng.submit(p, arrival=0.0, max_new_tokens=6)
    results = []
    swapped_mid_decode = False
    while eng.has_work():
        before = sched.controller.swaps + sched.controller.bootstraps
        results.extend(eng.step(now=0.0))
        published = sched.controller.swaps + sched.controller.bootstraps
        if eng.active() and published > before:
            swapped_mid_decode = True
    assert swapped_mid_decode            # plans were live while decoding
    assert server._plan_override         # controller owns layers now
    assert _tokens_of(results) == ref    # ... and tokens are identical
    # overridden layers bypass the blocking phase-2 fine-tune entirely
    post_stats = [s for s in list(eng.layer_stats)[-4 * cfg.n_moe_layers:]]
    assert not any(s.finetuned for s in post_stats)


def test_engine_warmup_pretraces_and_leaves_no_trace():
    """Warm-up compiles the (batch-bucket, min_replicas) dispatch grid and
    the prefill/decode paths, restores the PlanCache untouched, and a
    subsequent same-shape serve hits the jit cache instead of compiling."""
    cfg, server = _smoke_server(capacity_factor=16.0)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64,
                                             max_batch_requests=4))
    n = eng.warmup(seqs=(12,), max_new_tokens=3, min_replicas_grid=(1, 2))
    assert n > 0
    # no scheduling trace: cache empty, stats zeroed, no overrides
    assert server.plan_cache._plans == {}
    st = server.plan_cache.stats
    assert (st.hits, st.misses, st.invalidations) == (0, 0, 0)
    assert server._plan_override == {}
    size = server._dispatch._cache_size()
    assert size > 0
    # a second warm-up at the same grid re-traces nothing: the engine's own
    # jit-cache accounting AND the analyzer's jit tracing-cache counter
    # (repro.analysis pass 3) must both stay flat
    from repro.analysis.retrace import no_retrace, supported
    with no_retrace("second engine warmup at an identical grid") as rep:
        eng.warmup(seqs=(12,), max_new_tokens=3, min_replicas_grid=(1, 2))
    assert server._dispatch._cache_size() == size
    if supported():
        assert rep.count == 0


def test_engine_simulate_open_loop_latency():
    cfg, server = _smoke_server()
    rng = np.random.RandomState(3)
    toks = rng.randint(0, cfg.vocab_size, (16,))
    # steady traffic: identical requests -> stable popularity -> plan reuse
    trace = [(toks, 0.01 * i) for i in range(6)]
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=32))
    results = simulate(eng, trace)
    assert len(results) == 6
    assert all(r.latency >= 0 for r in results)
    assert all(r.completion >= r.arrival for r in results)
    # steady traffic + cached plans => some reuse after the first batch
    assert eng.plan_reuse_rate > 0.0


def test_engine_device_failure_mid_decode_keeps_tokens_bitwise():
    """A device failing mid-decode must degrade transparently: the dead
    device's route weights are zeroed (zero-migration re-route), affected
    cached plans are invalidated and replanned under the device mask, and
    — because every replica serves the identical expert math and capacity
    has headroom — every request's generated tokens stay bitwise identical
    to the fault-free run.  Decode slots are never lost."""
    cfg, ref_server = _smoke_server(capacity_factor=16.0)
    rng = np.random.RandomState(47)
    prompts = [rng.randint(0, cfg.vocab_size, (10,)) for _ in range(3)]

    ref_eng = ServingEngine(ref_server, EngineConfig(max_batch_tokens=64))
    for p in prompts:
        ref_eng.submit(p, arrival=0.0, max_new_tokens=6)
    ref = _tokens_of(ref_eng.run())

    _, server = _smoke_server(capacity_factor=16.0)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64))
    for p in prompts:
        eng.submit(p, arrival=0.0, max_new_tokens=6)
    results, failed_mid_decode, post_fail_stats = [], False, []
    while eng.has_work():
        results.extend(eng.step(now=0.0))
        if not server.dead_devices and eng.active():
            server.fail_devices({1})             # die mid-decode
            failed_mid_decode = eng.active() > 0
            n_before = len(eng.layer_stats)
        if server.dead_devices:
            post_fail_stats = list(eng.layer_stats)[n_before:]
    assert failed_mid_decode                     # requests were in flight
    assert server.dead_devices == {1}
    assert _tokens_of(results) == ref            # bitwise-identical output
    # the re-route is real: no realized load lands on the dead device
    assert post_fail_stats
    for s in post_fail_stats:
        assert float(np.asarray(s.device_load)[1]) == 0.0
