"""Tests for the static contract checker (repro.analysis).

Covers: pass-1 checks against synthetic bad kernels (misaligned block,
over-budget footprint, uncovered grid, unregistered site), pass-2 lints
against synthetic shard_map bodies (unbound axis, axis literal, dropped
ordering token), the no-finding path on known-good inputs, agreement
between the committed ANALYSIS_BASELINE.json and the live repo, the
call-time VMEM asserts in kernels/dispatch.py matching the analyzer's
estimates, the bench-row annotation, and the retrace detector.
"""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (load_baseline, new_findings, run_all,
                            write_baseline)
from repro.analysis.collectives import analyze_collectives
from repro.analysis.findings import Finding
from repro.analysis.kernels import (CONST, Block, RegistryEntry, ShapeCase,
                                    SiteEval, analyze_kernels,
                                    annotate_bench_rows, build_cases,
                                    grid_dim, iter_pallas_sites)
from repro.analysis.retrace import (RetraceError, no_retrace, supported)
from repro.kernels.dispatch import (combine_rows, combine_vmem_bytes,
                                    dispatch_rows, dispatch_vmem_bytes,
                                    invert_slots)
from repro.kernels.tiling import VMEM_BUDGET_BYTES, block_and_pad

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ pass 1 synthetic --

_SYN_KERNEL = textwrap.dedent('''
    from jax.experimental import pallas as pl

    def bad_misaligned(x):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec((12, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((12, 128), lambda i: (i, 0)),
            out_shape=None)(x)

    def bad_overbudget(x):
        return pl.pallas_call(
            _k, grid=(2,),
            in_specs=[pl.BlockSpec((4096, 2048), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=None)(x)

    def bad_uncovered(x):
        return pl.pallas_call(
            _k, grid=(3,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=None)(x)

    def good_kernel(x):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=None)(x)

    def bad_unit_lane(x):
        return pl.pallas_call(
            _k, grid=(2, 4),
            in_specs=[pl.BlockSpec((512, 1), lambda j, i: (i, j))],
            out_specs=pl.BlockSpec((512, 1), lambda j, i: (i, j)),
            out_shape=None)(x)

    def good_unit_dims(x, y):
        return pl.pallas_call(
            _k, grid=(4,),
            in_specs=[pl.BlockSpec((512, 1), lambda i: (i, 0)),
                      pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((512, 1), lambda i: (i, 0)),
            out_shape=None)(x, y)

    def not_in_registry(x):
        return pl.pallas_call(_k, grid=(1,))(x)
''')


def _syn_eval(name, inputs, outputs, grid):
    def fn(_case=None):
        return [SiteEval("syn.py", name, "syn", grid, inputs, outputs)]
    return RegistryEntry(fn, per_case=False)


_SYN_REGISTRY = {
    ("syn.py", "bad_misaligned"): _syn_eval(
        "bad_misaligned",
        [Block("a", (12, 128), "float32", (grid_dim(0), CONST), (48, 128))],
        [Block("o", (12, 128), "float32", (grid_dim(0), CONST), (48, 128))],
        (4,)),
    ("syn.py", "bad_overbudget"): _syn_eval(
        "bad_overbudget",
        [Block("big", (4096, 2048), "float32", (CONST, CONST),
               (4096, 2048))],
        [Block("o", (8, 128), "float32", (grid_dim(0), CONST), (16, 128))],
        (2,)),
    ("syn.py", "bad_uncovered"): _syn_eval(
        "bad_uncovered",
        [Block("a", (8, 128), "float32", (grid_dim(0), CONST), (32, 128))],
        [Block("o", (8, 128), "float32", (grid_dim(0), CONST), (32, 128))],
        (3,)),
    # a (bt, 1) block on a [T, 2] array: the unit lane dim is neither the
    # full extent nor 128-aligned, which Mosaic refuses
    ("syn.py", "bad_unit_lane"): _syn_eval(
        "bad_unit_lane",
        [Block("idx", (512, 1), "int32", (grid_dim(1), grid_dim(0)),
               (2048, 2))],
        [Block("pos", (512, 1), "int32", (grid_dim(1), grid_dim(0)),
               (2048, 2))],
        (2, 4)),
    # unit dims at full extent, or outside the last two dims, are fine
    ("syn.py", "good_unit_dims"): _syn_eval(
        "good_unit_dims",
        [Block("col", (512, 1), "int32", (grid_dim(0), CONST), (2048, 1)),
         Block("t", (1, 8, 128), "float32", (grid_dim(0), CONST, CONST),
               (4, 8, 128))],
        [Block("o", (512, 1), "int32", (grid_dim(0), CONST), (2048, 1))],
        (4,)),
    ("syn.py", "good_kernel"): _syn_eval(
        "good_kernel",
        [Block("a", (8, 128), "float32", (grid_dim(0), CONST), (32, 128))],
        [Block("o", (8, 128), "float32", (grid_dim(0), CONST), (32, 128))],
        (4,)),
}


@pytest.fixture()
def syn_kernels(tmp_path):
    (tmp_path / "syn.py").write_text(_SYN_KERNEL)
    return analyze_kernels(str(tmp_path), registry=_SYN_REGISTRY,
                           rel_prefix="syn")


def _cats(findings, qualname):
    return sorted({f.category for f in findings if f.qualname == qualname})


def test_misaligned_block_detected(syn_kernels):
    assert _cats(syn_kernels, "bad_misaligned") == ["misaligned-block"]
    f = next(f for f in syn_kernels if f.qualname == "bad_misaligned")
    assert "12" in f.message and "8" in f.message  # size vs sublane tile


def test_overbudget_footprint_detected(syn_kernels):
    fs = [f for f in syn_kernels if f.qualname == "bad_overbudget"]
    assert _cats(syn_kernels, "bad_overbudget") == ["vmem-over-budget"]
    f = fs[0]
    # resident big block once + streamed out twice
    expect = 4096 * 2048 * 4 + 2 * (8 * 128 * 4)
    assert f.data["footprint_bytes"] == expect
    assert f.data["budget_bytes"] == VMEM_BUDGET_BYTES


def test_uncovered_grid_detected(syn_kernels):
    assert _cats(syn_kernels, "bad_uncovered") == ["grid-uncovered"]


def test_good_kernel_no_findings(syn_kernels):
    assert _cats(syn_kernels, "good_kernel") == []


def test_unit_lane_block_on_wider_array_detected(syn_kernels):
    fs = [f for f in syn_kernels if f.qualname == "bad_unit_lane"]
    assert {f.category for f in fs} == {"misaligned-block"}
    assert {f.key.split("[")[-1] for f in fs} == {"dim1]"}


def test_unit_dims_at_full_extent_or_leading_pass(syn_kernels):
    assert _cats(syn_kernels, "good_unit_dims") == []


def test_unregistered_site_detected(syn_kernels):
    assert _cats(syn_kernels, "not_in_registry") == ["unregistered-kernel"]


def test_stale_registry_entry_detected(tmp_path):
    (tmp_path / "syn.py").write_text(_SYN_KERNEL)
    reg = dict(_SYN_REGISTRY)
    reg[("syn.py", "vanished_kernel")] = _syn_eval(
        "vanished_kernel", [], [], (1,))
    fs = analyze_kernels(str(tmp_path), registry=reg, rel_prefix="syn")
    assert _cats(fs, "vanished_kernel") == ["missing-kernel"]


def test_ast_site_enumeration(tmp_path):
    (tmp_path / "syn.py").write_text(_SYN_KERNEL)
    sites = iter_pallas_sites(str(tmp_path), rel_prefix="syn")
    assert {s.qualname for s in sites} == {
        "bad_misaligned", "bad_overbudget", "bad_uncovered", "good_kernel",
        "bad_unit_lane", "good_unit_dims", "not_in_registry"}
    by_name = {s.qualname: s for s in sites}
    assert by_name["good_kernel"].grid_len == 1
    assert by_name["good_kernel"].n_in_specs == 1


# ------------------------------------------------------ pass 2 synthetic --

_SYN_COLLECTIVES = textwrap.dedent('''
    """Docstrings may mention the model axis freely."""
    from jax import lax
    from repro.core.axes import EP_AXIS

    def ok_constant(x):
        return lax.psum(x, EP_AXIS)

    def bad_unbound(x):
        return lax.psum(x, "not_a_mesh_axis")

    def bad_param(x, ax):
        return lax.axis_index(ax)

    def caller(x):
        return bad_param(x, "typoed")

    def bad_literal_spec():
        return ("data", "model")

    def pipelined_expert_ffn(x):
        return x, object()

    def drops_token(x):
        out, _ = pipelined_expert_ffn(x)
        return out

    def keeps_token(x):
        out, tok = pipelined_expert_ffn(x)
        return out, tok
''')


@pytest.fixture()
def syn_collectives(tmp_path):
    (tmp_path / "mod.py").write_text(_SYN_COLLECTIVES)
    return analyze_collectives(str(tmp_path), rel_prefix="syn",
                               producers={"pipelined_expert_ffn": 1})


def test_unbound_axis_detected(syn_collectives):
    keys = {f.key for f in syn_collectives if f.category == "unbound-axis"}
    assert "psum:not_a_mesh_axis" in keys
    # parameterized axis resolved through its in-module call site
    assert "axis_index:typoed" in keys


def test_axis_literal_detected(syn_collectives):
    vals = {f.key.split("@")[0] for f in syn_collectives
            if f.category == "axis-literal"}
    assert vals == {"data", "model"}   # docstring mention exempt


def test_dropped_token_detected(syn_collectives):
    drops = [f for f in syn_collectives
             if f.category == "dropped-ordering-token"]
    assert [f.qualname for f in drops] == ["drops_token"]


def test_bound_axis_and_kept_token_clean(syn_collectives):
    assert not any(f.qualname in ("ok_constant", "keeps_token")
                   for f in syn_collectives)


def test_real_tree_collectives_clean():
    assert analyze_collectives(os.path.join(REPO, "src", "repro")) == []


# --------------------------------------------------- repo vs baseline -----

def test_repo_findings_match_committed_baseline():
    """CI's gate, as a test: the live tree produces exactly the findings
    recorded in ANALYSIS_BASELINE.json — nothing new, nothing stale."""
    findings = run_all(REPO)
    baseline = load_baseline(os.path.join(REPO, "ANALYSIS_BASELINE.json"))
    fresh = new_findings(findings, baseline)
    assert fresh == [], [f.fingerprint for f in fresh]
    current = {f.fingerprint for f in findings}
    stale = baseline - current
    assert stale == set(), sorted(stale)


def test_pr4_ceilings_are_retired():
    """The PR-4 VMEM ceilings (T-resident dispatch source, R-resident
    combine buffer, full-K grouped_matmul blocks) are gone: re-tiling
    removed every untiled-block and vmem-over-budget finding, and every
    registered kernel's static footprint fits the per-core budget at all
    paper shapes, scale 1 included."""
    findings = run_all(REPO)
    cats = {f.category for f in findings}
    assert "untiled-block" not in cats, \
        [f.fingerprint for f in findings if f.category == "untiled-block"]
    assert "vmem-over-budget" not in cats, \
        [f.fingerprint for f in findings
         if f.category == "vmem-over-budget"]
    from repro.analysis.kernels import REGISTRY
    for entry in REGISTRY.values():
        cases = build_cases() if entry.per_case else [None]
        for case in cases:
            for ev in entry.eval_fn(case):
                assert ev.footprint() <= VMEM_BUDGET_BYTES, \
                    (ev.qualname, ev.variant, ev.case, ev.footprint())


def test_injected_bad_kernel_fails_gate(tmp_path):
    """A misaligned synthetic kernel makes the baseline-gated run fail."""
    findings = run_all(REPO)
    base = tmp_path / "baseline.json"
    write_baseline(str(base), findings)
    assert new_findings(findings, load_baseline(str(base))) == []
    injected = findings + [Finding(
        "misaligned-block", "src/repro/kernels/new.py", "new_kernel",
        "a[dim1]", "synthetic")]
    assert len(new_findings(injected, load_baseline(str(base)))) == 1


# ------------------------------------- dispatch call-time VMEM asserts ----

def test_dispatch_assert_matches_analyzer_estimate():
    t, d, r, k = 64, 128, 32, 2
    x = jnp.ones((t, d), jnp.float32)
    rows = jnp.zeros((t, k), jnp.int32)
    src, _ = invert_slots(rows, r)
    br, _ = block_and_pad(r, 1024)
    bx, _ = block_and_pad(t, 512)
    expect = dispatch_vmem_bytes(br, bx, d)
    with pytest.raises(ValueError) as ei:
        dispatch_rows(x, src, vmem_budget=expect - 1)
    assert f"{expect:,} B" in str(ei.value)
    # at exactly the footprint the call goes through
    out = dispatch_rows(x, src, vmem_budget=expect)
    assert out.shape == (r, d)

    buf = jnp.ones((r, d), jnp.float32)
    w = jnp.ones((t, k), jnp.float32)
    bt, _ = block_and_pad(t, 1024)
    brf, _ = block_and_pad(r, 512)
    expect_c = combine_vmem_bytes(bt, brf, d, k)
    with pytest.raises(ValueError) as ei:
        combine_rows(buf, rows, w, vmem_budget=expect_c - 1)
    assert f"{expect_c:,} B" in str(ei.value)
    assert combine_rows(buf, rows, w, vmem_budget=expect_c).shape == (t, d)


def test_registry_estimates_match_call_time_asserts():
    """The analyzer's SiteEval footprints equal the dispatch.py formulas
    at every paper shape (asserted inside the eval fns — just drive them)."""
    from repro.analysis.kernels import (_eval_combine_rows,
                                        _eval_dispatch_rows)
    for case in build_cases():
        ev_d = _eval_dispatch_rows(case)[0]
        br, _ = block_and_pad(case.R, 1024)
        bx, _ = block_and_pad(case.T, 512)
        assert ev_d.footprint() == dispatch_vmem_bytes(br, bx, case.D)
        ev_c = _eval_combine_rows(case)[0]
        bt, _ = block_and_pad(case.T, 1024)
        brf, _ = block_and_pad(case.R, 512)
        assert ev_c.footprint() == combine_vmem_bytes(bt, brf, case.D,
                                                      case.K)


# ------------------------------------------------------ bench annotation --

def test_bench_rows_annotated():
    with open(os.path.join(REPO, "BENCH_kernels.json")) as fh:
        rows = json.load(fh)
    annotate_bench_rows(rows)
    known = [r for r in rows if r["bench"] in
             ("gating", "dispatch_combine", "routing", "grouped_ffn",
              "layer_fwdbwd")]
    assert known
    for r in known:
        assert r["static_vmem_bytes"] > 0
        assert r["vmem_budget_bytes"] == VMEM_BUDGET_BYTES
        assert r["vmem_fits"] == (r["static_vmem_bytes"]
                                  <= r["vmem_budget_bytes"])


# ---------------------------------------------------------- retrace pass --

def test_no_retrace_on_warm_function():
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((16,))
    f(x)
    with no_retrace("warm repeat") as rep:
        f(x)
        f(x)
    if supported():
        assert rep.count == 0 and rep.ok


def test_retrace_detected_on_new_shape():
    if not supported():
        pytest.skip("jax tracing counter unavailable")
    f = jax.jit(lambda x: x - 1)
    f(jnp.ones((4,)))
    with pytest.raises(RetraceError):
        with no_retrace("cold shape"):
            f(jnp.ones((32,)))


def test_retrace_nonstrict_records_without_raising():
    if not supported():
        pytest.skip("jax tracing counter unavailable")
    f = jax.jit(lambda x: x + 2)
    with no_retrace("cold start", strict=False) as rep:
        f(jnp.ones((5,)))
    assert rep.count is not None and rep.count > 0 and not rep.ok


def test_shape_cases_cover_paper_models():
    cases = build_cases()
    names = {c.name for c in cases}
    assert {"transformer-xl-moe/s1", "gpt2-moe/s4",
            "bert2gpt2-moe/s1", "bert-large-moe/s4"} <= names
    for c in cases:
        assert isinstance(c, ShapeCase)
        assert c.R == c.E * c.C and c.C % 8 == 0
