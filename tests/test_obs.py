"""Unified observability layer (repro.obs): span-tree invariants, the
TTFT = queue + prefill + insert identity on a real engine run (including
the Chrome-trace export round-trip the validator gates in CI), the
disabled fast path (no span allocation, bounded overhead), histogram
quantile accuracy against exact quantiles, the Prometheus text
round-trip, the admission ledger read back through the metrics view, the
device-trace annotation of every recorded span, and the wall-clock engine
step: its span tree, and every device->host read of the serving path
inside a ``sync.*`` span counted by ``server_host_syncs_total``."""
import json
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.popularity import PathProfile
from repro.obs import (Histogram, MetricsRegistry, NOOP, ObsContext, Tracer,
                       check_span_tree, parse_prometheus, tree_from_chrome)
from repro.obs import tracer as tracer_mod
from repro.obs.__main__ import check_ledger, check_request_ttft
from repro.obs.__main__ import main as obs_validate
from repro.models import lm as lm_mod
from repro.runtime.engine import EngineConfig, ServingEngine, simulate
from repro.runtime import engine as engine_mod
from repro.runtime import server as server_mod
from repro.runtime.server import MoEServer, ServerConfig
from repro.sched import get_trace

# --- tracer core ------------------------------------------------------------

class _FakeClock:
    """Deterministic monotonic clock: each read advances by ``tick``."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def test_span_tree_invariants_catch_violations():
    tr = Tracer(enabled=True)
    ok = tr.begin("step", start=0.0)
    ok.child("a", 0.0, 0.4)
    ok.child("b", 0.4, 0.9)
    ok.end_at(1.0)
    assert check_span_tree(tr.roots) == []

    overlapping = tr.begin("step2", start=0.0)
    overlapping.child("x", 0.0, 0.8)
    overlapping.child("y", 0.2, 0.9)           # phases overlap: sum 1.5 > 1.0
    overlapping.end_at(1.0)
    errs = check_span_tree(tr.roots)
    assert any("sum" in e for e in errs)

    tr.clear()
    escape = tr.begin("step3", start=0.0)
    escape.child("z", 0.0, 2.0)                # child past parent end
    escape.end_at(1.0)
    tr.begin("never_closed", start=0.0)        # left open
    errs = check_span_tree(tr.roots)
    assert any("escapes" in e for e in errs)
    assert any("open span" in e for e in errs)


def test_stack_spans_nest_and_add_lands_under_open_span():
    tr = Tracer(enabled=True, clock=_FakeClock())
    with tr.span("outer", layer=3) as outer:
        with tr.span("inner"):
            pass
        tr.add("manual", outer.start + 0.1, outer.start + 0.2, tag="m")
    assert len(tr.roots) == 1
    assert [c.name for c in tr.roots[0].children] == ["inner", "manual"]
    assert tr.roots[0].attrs == {"layer": 3}
    assert check_span_tree(tr.roots) == []
    # outside any open span, add() becomes a root
    tr.add("rootish", 100.0, 101.0)
    assert tr.roots[-1].name == "rootish"


def test_disabled_tracer_allocates_no_spans():
    tr = Tracer(enabled=False)
    assert tr.span("s") is NOOP
    assert tr.begin("s") is NOOP
    assert tr.add("s", 0.0, 1.0) is NOOP
    with tr.span("s", layer=1) as sp:
        assert sp is NOOP
        assert sp.set(a=1) is NOOP
        assert sp.begin_child("c", 0.0) is NOOP
        assert sp.child("c", 0.0, 1.0).end_at(2.0) is NOOP
    assert tr.roots == [] and tr._stack == []
    # the stopwatch still measures (its dt is functional), but records nothing
    with tr.timed("sw") as sw:
        pass
    assert sw.dt >= 0.0
    assert tr.roots == []


def test_root_cap_counts_drops_instead_of_silently_capping():
    tr = Tracer(enabled=True, max_roots=2)
    for i in range(5):
        tr.add(f"r{i}", float(i), float(i) + 0.5)
    assert len(tr.roots) == 2
    assert tr.dropped_roots == 3


# --- metrics ----------------------------------------------------------------

def test_histogram_quantiles_match_exact_within_bucket_resolution():
    rng = np.random.RandomState(0)
    xs = rng.lognormal(mean=-5.0, sigma=1.2, size=5000)   # ~ms-scale latencies
    h = Histogram()
    for x in xs:
        h.observe(float(x))
    assert h.count == xs.size
    np.testing.assert_allclose(h.sum, xs.sum(), rtol=1e-9)
    for q in (0.50, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        got = h.quantile(q)
        # default buckets are 4/octave: ~19% relative resolution
        assert abs(got - exact) / exact < 0.20, (q, got, exact)
    assert Histogram().quantile(0.5) != Histogram().quantile(0.5)  # NaN


def test_prometheus_round_trip_is_sample_exact():
    reg = MetricsRegistry()
    reg.counter("reqs_total", policy="lina").inc(3)
    reg.counter("reqs_total", policy="uniform").inc()
    reg.gauge("queue_depth").set(2.5)
    h = reg.histogram("lat_s", policy="lina")
    for v in (1e-4, 3e-4, 2e-3, 0.5, 2000.0):              # incl. overflow
        h.observe(v)
    text = reg.to_prometheus()
    assert parse_prometheus(text) == reg.to_samples()
    # the le label is emitted sorted in with the user labels, and the
    # overflow observation lands in the +Inf bucket
    assert 'lat_s_bucket{le="+Inf",policy="lina"} 5' in text
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")                            # type collision


# --- engine runs ------------------------------------------------------------

def _smoke_stack(obs, capacity_factor=16.0, **ecfg_kw):
    import dataclasses
    import jax
    cfg = get_config("gpt2-moe").smoke()
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe,
                                     capacity_factor=capacity_factor))
    params = lm_mod.init_params(cfg, jax.random.PRNGKey(0))
    prof = PathProfile(n_layers=cfg.n_moe_layers,
                       n_experts=cfg.moe.n_experts, path_len=2)
    server = MoEServer(cfg, params, prof,
                       ServerConfig(path_len=2, schedule_policy="lina"),
                       obs=obs)
    eng = ServingEngine(server, EngineConfig(max_batch_tokens=64,
                                             max_batch_requests=4,
                                             **ecfg_kw))
    return cfg, eng


@pytest.fixture(scope="module")
def traced_drift_run(tmp_path_factory):
    """One drift-workload engine run with tracing enabled, exported."""
    obs = ObsContext.enabled()
    cfg, eng = _smoke_stack(obs)
    trace = get_trace("drift", cfg.vocab_size, n_requests=6, seq=8,
                      rate_hz=50.0, seed=3)
    results = simulate(eng, trace, max_new_tokens=3)
    out = str(tmp_path_factory.mktemp("obs_drift"))
    paths = obs.export(out)
    return obs, eng, results, out, paths


def test_ttft_identity_holds_on_drift_run(traced_drift_run):
    obs, eng, results, _out, _paths = traced_drift_run
    assert len(results) == 6
    spans = obs.tracer.roots
    assert check_span_tree(spans) == []
    errs, n = check_request_ttft(spans, tol=1e-6)
    assert errs == [] and n == 6
    # the span-tree TTFT agrees with the engine's own result objects
    by_rid = {r.rid: r for r in results}
    for root in spans:
        if root.name != "request" or "ttft_s" not in root.attrs:
            continue
        r = by_rid[root.attrs["rid"]]
        assert abs(root.attrs["ttft_s"] - r.ttft_latency) < 1e-9
        assert root.attrs["outcome"] == "done"
    # ... and with the registry histograms the benchmark columns read
    h = obs.metrics.get("engine_ttft_s")
    assert h is not None and h.count == 6


def test_chrome_export_round_trips_the_decomposition(traced_drift_run):
    obs, _eng, _results, out, paths = traced_drift_run
    with open(paths["trace"]) as f:
        chrome = json.load(f)
    assert chrome["traceEvents"], "empty Chrome trace"
    trees = tree_from_chrome(chrome)
    errs, n = check_request_ttft(trees, tol=1e-5)
    assert errs == [] and n == 6
    # the CLI validator (the CI gate) passes on the exported artifact set
    assert obs_validate(["validate", "--trace-dir", out,
                         "--require-requests", "6"]) == 0


def test_engine_step_spans_carry_phase_children(traced_drift_run):
    obs, _eng, _results, _out, _paths = traced_drift_run
    steps = [r for r in obs.tracer.roots if r.name == "engine.step"]
    assert steps
    for st in steps:
        names = {c.name for c in st.children}
        assert names <= {"decode", "prefill", "insert"}
    assert any("decode" in {c.name for c in st.children} for st in steps)


@pytest.fixture(scope="module")
def untraced_run():
    """The same engine path with the default (tracing-off) context."""
    obs = ObsContext.disabled()
    cfg, eng = _smoke_stack(obs)
    rng = np.random.RandomState(5)
    trace = [(rng.randint(0, cfg.vocab_size, (8,)), 0.02 * i)
             for i in range(6)]
    results = simulate(eng, trace, max_new_tokens=3)
    return obs, eng, results


def test_disabled_run_allocates_no_spans_but_keeps_metrics(untraced_run):
    obs, eng, results = untraced_run
    assert len(results) == 6
    assert obs.tracer.roots == []
    assert eng._req_spans == {}
    # the ledgers stay live: metrics are always on
    assert obs.metrics.value("engine_requests_offered_total") == 6
    assert obs.metrics.value("engine_requests_completed_total") == 6
    assert obs.metrics.get("engine_ttft_queue_s").count == 6


def test_disabled_tracing_overhead_within_2pct(untraced_run):
    """The per-call cost of a disabled span, times a generous bound on
    obs calls per engine step, must stay under 2% of a measured step's
    service time — the guard that keeps production serving free to leave
    tracing off-by-default without a perf tax."""
    obs, _eng, _results = untraced_run
    tr = Tracer(enabled=False)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("s", layer=0):
            pass
    per_call = (time.perf_counter() - t0) / n
    step_h = obs.metrics.get("engine_step_service_s")
    assert step_h is not None and step_h.count > 0
    mean_step = step_h.sum / step_h.count
    calls_per_step = 64          # at most 42 on this stack (9 a MoE layer)
    assert per_call * calls_per_step < 0.02 * mean_step, \
        (per_call, mean_step)


def test_admission_ledger_closes_through_metrics_view():
    obs = ObsContext.disabled()
    cfg, eng = _smoke_stack(obs, max_queue=1)
    rng = np.random.RandomState(9)
    # a same-instant burst against a depth-1 queue: most submits bounce,
    # and with no retry budget the client records give-ups on the ledger
    trace = [(rng.randint(0, cfg.vocab_size, (8,)), 0.0) for _ in range(8)]
    results = simulate(eng, trace, max_new_tokens=2, retry_backoff_s=0.0)
    assert eng.shed_records                    # some traffic was refused
    samples = parse_prometheus(obs.metrics.to_prometheus())
    assert check_ledger(samples) == []
    offered = samples["engine_requests_offered_total"]
    completed = samples["engine_requests_completed_total"]
    shed = sum(v for k, v in samples.items()
               if k.startswith("engine_requests_shed_total"))
    assert shed == len(eng.shed_records) > 0
    assert offered == completed + shed == len(trace)
    assert completed == len(results)


# --- device-trace annotations ---------------------------------------------

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs open/close."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("open", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name))
        return False


@pytest.mark.parametrize("enabled", [True, False])
def test_recorded_spans_open_one_trace_annotation_each(monkeypatch, enabled):
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    tr = Tracer(enabled=enabled)
    with tr.span("outer"):
        with tr.timed("watch") as sw:
            with tr.span("inner"):
                pass
        with tr.timed("quiet", record=False):
            pass
        tr.add("explicit", 0.0, 1.0)
    tr.begin("manual", start=0.0).end_at(1.0)
    assert sw.dt >= 0.0                       # the stopwatch always runs
    if not enabled:
        assert _FakeAnnotation.log == []
        return
    # stack and recorded timed spans only, opened and closed as they nest
    assert _FakeAnnotation.log == [
        ("open", "repro.outer"), ("open", "repro.watch"),
        ("open", "repro.inner"), ("close", "repro.inner"),
        ("close", "repro.watch"), ("close", "repro.outer")]
    # a recorded timed span is a stack span: what opens inside nests in it
    outer = tr.roots[0]
    assert [c.name for c in outer.children] == ["watch", "explicit"]
    watch = outer.children[0]
    assert [c.name for c in watch.children] == ["inner"]
    assert watch.duration == pytest.approx(sw.dt)


# --- the wall-clock engine step -------------------------------------------

class _ReadLog:
    """Every device->host read of the serving modules, by the innermost
    open span at the time.  Reads are caught where numpy meets a device
    array in ``runtime.server`` / ``runtime.engine`` and, for the scalar
    and ``__array__`` conversions, in ``jax.Array``'s host value."""

    def __init__(self, monkeypatch, tracer):
        import types

        import jax
        from jax._src import array as jax_array
        self.tracer = tracer
        self.sites = []
        self._inside = False
        log = self

        def where():
            stack = log.tracer._stack
            return stack[-1].name if stack else None

        class _Numpy(types.ModuleType):
            def __getattr__(self, name):
                attr = getattr(np, name)
                if isinstance(attr, (type, types.ModuleType)) or \
                        not callable(attr):
                    return attr

                def call(*args, **kwargs):
                    dev = any(isinstance(a, jax.Array) for a in
                              list(args) + list(kwargs.values()))
                    if dev:
                        log.sites.append(where())
                    log._inside = dev
                    try:
                        return attr(*args, **kwargs)
                    finally:
                        log._inside = False
                return call

        host_value = jax_array.ArrayImpl._value

        def value(arr):
            if not log._inside:
                log.sites.append(where())
            return host_value.fget(arr)

        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(value))
        for mod in (server_mod, engine_mod):
            monkeypatch.setattr(mod, "np", _Numpy("numpy"))

    def take(self):
        out, self.sites = self.sites, []
        return out


@pytest.fixture(scope="module")
def wall_run(tmp_path_factory):
    """A traced engine driven on the wall clock (``step()``), as a
    deployment serves: two generating requests, prefill then decodes."""
    obs = ObsContext.enabled()
    cfg, eng = _smoke_stack(obs)
    rng = np.random.RandomState(11)
    for _ in range(2):
        eng.submit(rng.randint(0, cfg.vocab_size, (8,)), max_new_tokens=4)
    results = eng.run()
    out = str(tmp_path_factory.mktemp("obs_wall"))
    obs.export(out)
    return cfg, obs, eng, results, out


def test_wall_step_is_one_stack_tree_per_step(wall_run):
    _cfg, obs, _eng, results, out = wall_run
    assert len(results) == 2
    roots = obs.tracer.roots
    assert check_span_tree(roots) == []
    steps = [r for r in roots if r.name == "engine.step"]
    assert len(steps) == obs.metrics.value("engine_steps_total") == 4
    # no replay-layout duplicates, and the server spans nest in the phases
    assert {r.name for r in roots} == {"engine.step", "request"}
    for st in steps:
        assert {c.name for c in st.children} == {
            "engine.decode", "engine.prefill", "engine.finish"}
        for ph in st.children:
            names = {c.name for c in ph.children}
            if ph.name == "engine.finish" or not ph.duration:
                continue
            assert "server.layer" in names or not names
    decode = [ph for st in steps for ph in st.children
              if ph.name == "engine.decode" and ph.children]
    assert decode
    names = {sp.name for ph in decode for sp in ph.walk()}
    assert {"server.block", "server.layer", "sync.top1", "dispatch",
            "server.mirror", "server.head", "sync.logits"} <= names
    assert obs_validate(["validate", "--trace-dir", out,
                         "--require-requests", "2"]) == 0


def test_wall_run_keeps_ttft_decomposition(wall_run):
    _cfg, obs, _eng, results, _out = wall_run
    errs, n = check_request_ttft(obs.tracer.roots, tol=1e-6)
    assert errs == [] and n == 2
    by_rid = {r.rid: r for r in results}
    for root in obs.tracer.roots:
        if root.name == "request":
            r = by_rid[root.attrs["rid"]]
            assert root.attrs["ttft_s"] == pytest.approx(r.ttft_latency,
                                                         abs=1e-9)


def test_sync_spans_match_the_sync_counter(wall_run):
    cfg, obs, _eng, _results, _out = wall_run
    syncs = [sp for r in obs.tracer.roots for sp in r.walk()
             if sp.name.startswith("sync.")]
    assert len(syncs) == obs.metrics.value("server_host_syncs_total") > 0
    # one prefill, then 3 decodes: each one read a MoE layer + the logits
    n_moe = cfg.n_moe_layers
    assert len(syncs) == 4 * (n_moe + 1)


def test_device_reads_of_a_step_are_the_listed_sync_sites(monkeypatch):
    """Every device->host read of an engine step happens inside a
    ``sync.*`` span, at the sites PERF.md lists: per MoE layer the gate's
    choices ``idx``, once (``sync.top1``), and per forward the logits
    (``sync.logits``).  Spans add none."""
    obs = ObsContext.enabled()
    cfg, eng = _smoke_stack(obs)
    rng = np.random.RandomState(12)
    reads = _ReadLog(monkeypatch, obs.tracer)
    n_moe = cfg.n_moe_layers
    met = obs.metrics
    for _ in range(2):
        eng.submit(rng.randint(0, cfg.vocab_size, (8,)), max_new_tokens=4)
    want = {"prefill": {"sync.top1": n_moe, "sync.logits": 1},
            "decode": {"sync.top1": n_moe, "sync.logits": 1}}
    for kind in ("prefill", "decode", "decode"):
        before = met.value("server_host_syncs_total")
        eng.step()
        sites = reads.take()
        got = {name: sites.count(name) for name in set(sites)}
        assert got == want[kind], (kind, sites)
        assert met.value("server_host_syncs_total") - before == len(sites)
