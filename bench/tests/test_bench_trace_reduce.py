"""The trace reduction on a small recorded v5e trace
(``data/probe.xplane.pb.gz``, written by ``data/record_trace.py``: one
serving MoE layer at 8 and at 512 tokens, gpt2-moe widths, and one
training step of a one-layer transformer-xl-moe), and on hand-made
intervals."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as T  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb.gz"


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trace():
    return T.reduce(str(DATA), window_s=0.1, n_devices=1)


def test_busy_is_union_of_op_intervals(trace):
    ops = trace.ops[0]
    assert len(ops) == 5508
    assert trace.busy_s == pytest.approx(0.072496841, rel=1e-9)
    # the union never exceeds the span of the ops, nor the plain sum
    span = (max(o.end_ns for o in ops) - min(o.start_ns for o in ops)) * 1e-9
    assert trace.busy_s <= span
    assert trace.busy_s <= sum(o.dur_ns for o in ops) * 1e-9
    assert trace.idle_share_max() == pytest.approx(1 - 0.72496841, rel=1e-6)


def test_kernels_found_by_signature(trace):
    ffn_s = metric("expert_ffn_roofline.serve")
    ffn_t = metric("expert_ffn_roofline.train")
    disp = metric("dispatch_combine_roofline.serve")
    ops = trace.ops[0]
    # serving: one grouped FFN per layer call over 64 expert slots
    assert sum(ffn_s.is_grouped_ffn(o, 768, 3072) for o in ops) == 2
    assert trace.kernel_seconds(
        lambda o: ffn_s.is_grouped_ffn(o, 768, 3072)) == pytest.approx(
            0.002395529, rel=1e-9)
    # dispatch + combine of the two serving calls
    assert sum(disp.is_dispatch_or_combine(o, 768) for o in ops) == 4
    # training: 4 micro-op chunks forward, again under remat, and the
    # backward's grouped matmuls (5 per chunk)
    assert sum(ffn_t.is_grouped_ffn(o, 1024, 4096) for o in ops) == 8
    assert sum(ffn_t.is_grouped_matmul(o, 1024, 4096) for o in ops) == 20
    # nothing is matched twice, and the gating kernels are not matched
    assert not any(ffn_t.is_grouped_ffn(o, 1024, 4096)
                   and ffn_t.is_grouped_matmul(o, 1024, 4096) for o in ops)
    assert sum(o.is_kernel for o in ops) == 44


def test_breakdown_shape(trace):
    b = trace.breakdown
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        for name, secs in b[key]:
            assert isinstance(name, str) and secs >= 0
    tops = [s for _, s in b["device_ops"]]
    assert tops == sorted(tops, reverse=True)


def op(kind, start, dur):
    return T.Op(kind, kind, "", [], [], float(start), float(dur))


def test_exposed_collective_time():
    ops = {0: [op("fusion", 0, 10), op("all-to-all", 5, 10),
               op("while", 0, 100), op("fusion", 30, 5),
               op("all-to-all", 32, 10)],
           1: [op("all-to-all", 0, 4)]}
    tr = T.Trace(window_s=1e-7, ops=ops)
    exp = tr.exposed_seconds(lambda o: o.kind == "all-to-all")
    # chip 0: [10, 15) and [35, 42) are not covered by compute (the while
    # loop's own interval does not count as compute); chip 1: all of it
    assert exp[0] == pytest.approx(12e-9)
    assert exp[1] == pytest.approx(4e-9)


def test_union_and_subtract():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T.subtract(u, [(1, 6)]) == [(0, 1), (6, 9)]
    assert T.length(u) == 7


def test_parse_op_text():
    text = ('%_lambda_.9 = f32[512,768]{1,0:T(8,128)} custom-call(s32[512,1]'
            '{1,0} %copy.50, f32[512,1]{1,0} %b, bf16[8,768]{1,0} %x.1), '
            'custom_call_target="tpu_custom_call"')
    o = T.parse_op(text, 1.0, 2.0)
    assert o.kind == "custom-call" and o.is_kernel
    assert o.results == [("f32", (512, 768))]
    assert o.operands == [("s32", (512, 1)), ("f32", (512, 1)),
                          ("bf16", (8, 768))]
