"""Serving driver: ``ServingEngine`` -> ``MoEServer`` (policy ``lina``) in
wall-clock mode under open-loop arrivals.

Set-up makes the weights, profiles expert paths on traffic of the mix,
builds the server and the engine, runs ``engine.warmup`` on the mix's
prompt lengths, then serves a pre-roll of the same open-loop traffic so
that the window opens on a busy engine.  The window then runs for
``--seconds``: requests are submitted when due (their arrival stamped with
the due time) and the engine steps whenever it has work.  After the window
the engine drains what is in flight, and every finished request is
compared with the plain reference.

Token times: every request in a decode slot decodes one token in every
engine step (the slots never outnumber ``max_batch_requests``), so a
request's tokens were produced at its first-token time and at the end of
every later step up to its completion.
"""
from __future__ import annotations

import bisect
import gc
import sys
import time
from functools import partial
from types import SimpleNamespace

import numpy as np

from bench import flops, reference, traffic_gen, weights
from bench.drivers.common import (Record, Result, Window, annotate,
                                  count_compiles, memory_peak, program_config)

PLANNER_SPANS = ("phase1.estimate", "plan.lookup", "phase2.finetune",
                 "plan.build")


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def token_times(r, ends) -> list:
    """First-token time, then the end of every later step to completion."""
    lo = bisect.bisect_right(ends, r.ttft)
    hi = bisect.bisect_right(ends, r.completion)
    return [r.ttft] + ends[lo:hi]


def build(spec):
    """Set-up: weights, expert-path profile, server, engine, warm-up."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm as lm_mod
    from repro.obs import ObsContext
    from repro.runtime.engine import EngineConfig, ServingEngine
    from repro.runtime.server import (MoEServer, ServerConfig,
                                      profile_from_training)

    cfg, mix, seed = spec.cfg, spec.mix, spec.seed
    e_cfg, s_cfg = mix["engine"], mix["server"]
    rows = e_cfg["max_batch_requests"]
    if reference.capacity(rows, cfg["n_experts"], cfg["serve_top_k"],
                          cfg["capacity_factor"]) < rows:
        raise ValueError("a decode step could drop tokens; the reference's "
                         "capacity rule would not hold")
    mc = program_config(cfg, spec.block)
    struct = jax.eval_shape(partial(lm_mod.init_params, mc),
                            jax.random.PRNGKey(0))
    params = weights.make_program(spec.block, cfg, seed, jnp.bfloat16,
                                  struct)
    prof = profile_from_training(
        mc, params, traffic_gen.profile_batches(
            mix, cfg["vocab_size"], seed, **mix["profile"]),
        path_len=s_cfg["path_len"])
    obs = ObsContext.enabled() if spec.trace else ObsContext.disabled()
    server = MoEServer(mc, params, prof, ServerConfig(
        top_k=cfg["serve_top_k"], path_len=s_cfg["path_len"],
        max_pack=s_cfg["max_pack"], n_devices=s_cfg["n_devices"],
        schedule_policy=s_cfg["schedule_policy"]))
    engine = ServingEngine(server, EngineConfig(
        max_batch_requests=rows, max_batch_tokens=e_cfg["max_batch_tokens"],
        stats_window=1 << 22), obs=obs)
    engine.warmup(seqs=tuple(mix["prompt_lens"]),
                  max_new_tokens=max(mix["output_lens"]))
    warm_decode_batches(engine, mix)
    return engine


def warm_decode_batches(engine, mix) -> int:
    """Run every decode batch the window can form once, through the
    engine's own batch assembly (``ServingEngine._run_decodes``), so that
    neither the decode step nor the re-stacking of slot caches compiles in
    the window: each (row bucket, cache length) of the step, and each
    (source batch, cache length, slot length) a slot's cache row is cut
    from.  ``engine.warmup`` covers prefill and one-row decode only.  The
    engine has no public warm-up for decode batches, so this drives its
    private method; if that is renamed, set-up fails here."""
    from repro.runtime.engine import DecodeSlot
    run_decodes = engine._run_decodes
    rows = engine.ecfg.max_batch_requests
    buckets = sorted({1 << (n - 1).bit_length() for n in range(1, rows + 1)})
    pairs = sorted({(p, p + o) for p in mix["prompt_lens"]
                    for o in mix["output_lens"]})
    caps = sorted({c for _, c in pairs})
    # real prefill caches, made as the engine makes them
    prefilled = {}
    for p, c in pairs:
        prefilled[c] = engine.server.prefill_batch(
            np.zeros((1, p), np.int64), lengths=np.full((1,), p, np.int64),
            path_init=np.zeros((1, p), np.int64), cache_len=c).cache
    rid = [-1]

    def slot(src, c):
        rid[0] -= 1
        return DecodeSlot(rid=rid[0], arrival=0.0, prompt_len=c - 1,
                        max_new_tokens=1 << 20, cap=c, kv_k=None, kv_v=None,
                        pos=c - 1, path_scalar=0, path_history=[0],
                        gen_tokens=[0], ttft=0.0, batch_ref=src, batch_row=0)

    calls = 0
    batches = {}
    # every step shape, each shorter slot padded up to the longest, first
    # as a new batch and then with the batch kept from the step before
    for b in buckets:
        for s in caps:
            shorter = [c for c in caps if c <= s]
            batch = [slot(prefilled[s], s)] + [
                slot(prefilled[c], c) for c in (shorter * b)[:b - 1]]
            batches[b, s] = run_decodes(batch).cache
            run_decodes(batch)
            calls += 2
    # a batch short of its bucket (padding rows), for each cache length
    for s in caps:
        if rows >= 3:
            run_decodes([slot(prefilled[s], s) for _ in range(3)])
            calls += 1
    # every cut of a slot's row out of an earlier decode batch
    cuts = [slot(batches[b, s], c) for b in buckets for s in caps
            for c in caps if c <= s]
    for k in range(0, len(cuts), rows):
        run_decodes(cuts[k:k + rows])
        calls += 1
    engine._dec_batch = None
    return calls


def serve_window(engine, spec, sched, preroll_s: float) -> SimpleNamespace:
    """Serve ``sched`` open loop: a pre-roll of ``preroll_s``, then the
    measured window of ``spec.seconds``; then drain what is in flight.
    A traced run traces the window's last ``trace_s`` seconds (the mix's,
    else all of it): writing the trace out takes about three times as long
    as it covers, and happens after the window."""
    clock = time.perf_counter
    tracing = spec.trace
    obs = engine.obs
    met = obs.metrics
    n_req = len(sched)
    results, rid_of, rejected = {}, {}, 0
    ends = []                       # engine completion stamp of each step
    dec_rows = []                   # decode rows of each window step
    backlog = []                    # (time, queued + in flight) per step
    win = Window(tracing, spec.devices)
    t0 = t_stop = t_trace = None
    stats_tr = 0
    i = 0
    t_start = clock()
    t0_nominal = t_start + preroll_s
    try:
        with count_compiles() as comp:
            while True:
                now = clock()
                if t0 is None and now >= t0_nominal:
                    t0, t_stop = now, now + spec.seconds
                    setup_s = spec.process_age()
                    comp0, comp_s0 = comp["n"], comp["seconds"]
                    layers0 = met.value("server_layers_served_total")
                    tunes0 = met.value("server_phase2_finetunes_total")
                    stats0 = len(engine.layer_stats)
                    obs.tracer.clear()
                if (tracing and t_trace is None and t0 is not None
                        and now >= t_stop - spec.mix.get("trace_s",
                                                          spec.seconds)):
                    t_trace = now
                    stats_tr = len(engine.layer_stats)
                    win.__enter__()
                if t0 is not None and now >= t_stop:
                    break
                while i < n_req and t_start + sched[i].due_s <= now:
                    with annotate(tracing, "bench.submit"):
                        rid = engine.submit(
                            sched[i].tokens, arrival=t_start + sched[i].due_s,
                            max_new_tokens=sched[i].max_new_tokens)
                    if rid < 0:
                        rejected += t0 is not None
                    else:
                        rid_of[rid] = i
                    i += 1
                if engine.has_work():
                    n_dec = engine.active()
                    with annotate(tracing, "bench.engine_step"):
                        done = engine.step()
                    ends.append(engine.last_step_end)
                    if t0 is not None:
                        dec_rows.append(n_dec)
                        backlog.append((ends[-1] - t0,
                                        engine.pending() + engine.active()))
                    for r in done:
                        results[r.rid] = r
                else:
                    limit = t_stop if t0 is not None else t0_nominal
                    nxt = t_start + sched[i].due_s if i < n_req else limit
                    with annotate(tracing, "bench.wait"):
                        time.sleep(max(0.0, min(nxt, limit) - clock()))
            t1 = clock()
            compiles = comp["n"] - comp0
        win.close(t_trace, t1)
    finally:
        win.__exit__(None, None, None)
    counters = {
        "server_layers_served_total":
            met.value("server_layers_served_total") - layers0,
        "server_phase2_finetunes_total":
            met.value("server_phase2_finetunes_total") - tunes0,
        "compiles_in_window": compiles}
    spans = {name: 0.0 for name in PLANNER_SPANS}
    for root in obs.tracer.roots:
        for sp in root.walk():
            if sp.name in spans and t0 <= sp.start <= t1:
                spans[sp.name] += sp.duration
    window_stats = list(engine.layer_stats)[stats0:]
    traced_stats = list(engine.layer_stats)[stats_tr:] if tracing else []
    mem = memory_peak(spec.devices)
    n_win = len(dec_rows)
    durs = np.diff([t0] + ends[len(ends) - n_win:]) if n_win else [0.0]
    print(f"serve window: {n_win} engine steps in {t1 - t0:.2f} s, step "
          f"median {1e3 * np.median(durs):.1f} ms max {1e3 * np.max(durs):.1f}"
          f" ms; {compiles} executables built "
          f"({comp['seconds'] - comp_s0:.2f} s)", file=sys.stderr)
    # drain what is in flight (no new arrivals)
    drain_end = t1 + spec.mix["drain_s"]
    while engine.has_work() and clock() < drain_end:
        for r in engine.step():
            results[r.rid] = r
        ends.append(engine.last_step_end)
    failed = rejected + len(engine.shed_records)
    return SimpleNamespace(
        results=results, rid_of=rid_of, failed=failed, ends=ends,
        dec_rows=dec_rows, backlog=backlog, t_start=t_start, t0=t0, t1=t1,
        t_stop=t_stop, setup_s=setup_s, counters=counters, spans=spans,
        window_stats=window_stats, traced_stats=traced_stats, mem=mem,
        trace=win.trace)


def measure(sv, sched, cfg, block):
    """End-to-end metrics of a window, the finished requests by schedule
    index, the model FLOPs processed in the window, and the time to first
    token of every request due in it."""
    t0, t1 = sv.t0, sv.t1
    win_due = [j for j, r in enumerate(sched) if r.segment == 1]
    by_req = {sv.rid_of[rid]: r for rid, r in sv.results.items()}
    ttft, gaps, n_tok, work_flops = [], [], 0, 0.0
    failed = sv.failed
    for j, r in by_req.items():
        times = token_times(r, sv.ends)
        if len(times) != r.n_generated or not np.isfinite(r.logits).all():
            failed += j in win_due
            continue
        plen = len(sched[j].tokens)
        for n, t in enumerate(times):
            if t0 <= t <= t1:
                n_tok += 1
                work_flops += (
                    flops.serve_prefill_flops(block, cfg, plen) if n == 0
                    else flops.serve_decode_flops(block, cfg, plen + n - 1))
                if n:
                    gaps.append(times[n] - times[n - 1])
    for j in win_due:
        r = by_req.get(j)
        t_first = r.ttft if r is not None and r.ttft <= t1 else t1
        ttft.append(t_first - (sv.t_start + sched[j].due_s))
    if not ttft or not gaps:
        raise RuntimeError("the window served no request")
    print(f"serve window: {len(ttft)} requests due, ttft p50 "
          f"{1e3 * pct(ttft, 50):.1f} ms p95 {1e3 * pct(ttft, 95):.1f} ms; "
          f"{len(gaps)} token gaps, itl p50 {1e3 * pct(gaps, 50):.1f} ms",
          file=sys.stderr)
    e2e = {"serve_tok_s": n_tok / (t1 - t0),
           "ttft_p50_ms": 1e3 * pct(ttft, 50),
           "itl_p95_ms": 1e3 * pct(gaps, 95),
           "setup_s": sv.setup_s}
    return e2e, by_req, win_due, failed, work_flops, ttft


def run(spec, control: bool = False) -> Result:
    """One run of the cell; with ``control`` the served tokens are judged
    by the fp8 reference in the program's place (``bench/control.py``):
    at every position of the same prompts and tokens, the gap of the token
    that the control puts first."""
    import jax.numpy as jnp

    cfg, mix, seed, block = spec.cfg, spec.mix, spec.seed, spec.block
    engine = build(spec)
    sched = traffic_gen.serve_schedule(
        mix, cfg["vocab_size"], seed,
        (mix["preroll_s"], spec.seconds, spec.seconds))
    sv = serve_window(engine, spec, sched, mix["preroll_s"])
    del engine
    gc.collect()
    e2e, by_req, win_due, failed, work_flops, ttft = measure(sv, sched, cfg,
                                                             block)
    window_s = sv.t1 - sv.t0

    # --- correctness: every finished request against the reference -------
    finished = [(sched[j].tokens, np.asarray(r.tokens, np.int32))
                for j, r in sorted(by_req.items())]
    w = weights.make(block, cfg, seed, jnp.bfloat16)

    def gap_readings(who, ctl):
        gaps = np.concatenate(reference.serve_gaps(block, w, finished, cfg,
                                                   control=ctl))
        out = {"max_logit_gap": float(gaps.max()),
               "mean_logit_gap": float(gaps.mean()),
               "p90_logit_gap": float(np.percentile(gaps, 90))}
        for name, v in out.items():
            print(f"{who} {name} = {v!r}", file=sys.stderr)
        return out, gaps.size
    readings, n_checked = gap_readings("reading", False)
    if control:
        # the same checks hold the control's readings; such a run has to
        # come out not correct
        readings, _ = gap_readings("control", True)
    del w
    checks = [{"name": n, "value": readings[n], "limit": spec.limits[n]}
              for n in spec.limits]
    # too few served tokens checked is a failed check, not a pass
    checks.append({"name": "unchecked_tokens",
                   "value": float(max(0, mix["min_checked_tokens"]
                                      - n_checked)), "limit": 0.0})

    # --- what the per-layer readers read ---------------------------------
    # the kernels' work is counted over the traced part of the window
    stats = sv.traced_stats
    kept = sum(float(np.sum(s.replica_load)) for s in stats
               if s.replica_load is not None)
    touched = sum(int(np.count_nonzero(s.actual_pop)) for s in stats)
    tokens = sum(int(s.n_tokens) for s in stats)
    rec = Record(cfg=cfg, mix=mix, peak=spec.peak,
                 window_s=window_s, n_chips=len(spec.devices),
                 counters=sv.counters, spans=sv.spans, steps=sv.dec_rows,
                 requests={"ttft_s": ttft},
                 work={"model_flops": work_flops, "kept_rows": kept,
                       "experts_touched": touched, "tokens": tokens},
                 trace=sv.trace)
    return Result(e2e=e2e, record=rec, attempted=len(win_due), failed=failed,
                  checks=checks, memory_peak_bytes=sv.mem, trace=sv.trace)
