"""Device idle time by the host span that covers it (``bench/host_idle.py``),
on hand-made intervals and on the recorded v5e probe trace."""
from __future__ import annotations

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import host_idle as H  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb.gz"


def ops(*intervals):
    return [T.Op("x", "x", "", [], [], float(a), float(b - a))
            for a, b in intervals]


# device busy on [0, 10], [20, 30], [50, 60], [80, 90]: gaps 10-20, 30-50,
# 60-80 (ns); the spans below cover them in different ways
BUSY = ops((0, 10), (20, 30), (50, 60), (80, 90))


@pytest.mark.parametrize("spans, want", [
    # one span over everything: all idle is its own
    ([("step", 0, 90)], {"step": 50}),
    # nested spans: each moment goes to the innermost one open
    ([("step", 0, 90), ("layer", 25, 55), ("sync", 35, 45)],
     {"step": 10 + 20, "layer": 5 + 5, "sync": 10}),
    # gaps no span covers, and a gap only partly covered
    ([("wait", 12, 15), ("step", 30, 40)],
     {"wait": 3, "none": 7 + 10 + 20, "step": 10}),
    # a span that outlasts its parent is cut at the parent's end
    ([("step", 0, 40), ("late", 35, 70)],
     {"step": 10 + 5, "late": 5, "none": 10 + 20}),
    # siblings back to back inside one gap
    ([("a", 60, 70), ("b", 70, 80)], {"a": 10, "b": 10, "none": 30}),
])
def test_idle_goes_to_the_innermost_span(spans, want):
    got = H.idle_by_span(BUSY, spans)
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # every idle moment is counted once
    assert sum(got.values()) == pytest.approx(50e-9)


def test_idle_gaps_merge_overlapping_ops():
    assert H.idle_gaps(ops((0, 10), (5, 12), (12, 15), (20, 25))) == [
        (15.0, 20.0)]
    assert H.idle_gaps([]) == []


def test_probe_trace_idle_is_conserved():
    """On a real trace: the host's own events (runtime dispatch, buffer
    allocation) split the chip's idle gaps without losing or doubling
    any of it, and what they cover lies inside them."""
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    trace = T.reduce(str(DATA), window_s=0.1, n_devices=1)
    gaps = H.idle_gaps(trace.ops[0])
    total = sum(b - a for a, b in gaps) * 1e-9
    got = H.idle_by_span(trace.ops[0], spans)
    assert len(gaps) == 1109
    assert sum(got.values()) == pytest.approx(total, rel=1e-9)
    assert 0.0 < sum(v for k, v in got.items() if k != "none") < total
    for name, secs in got.items():
        if name != "none":
            cover = T.union((a, b) for n, a, b in spans if n == name)
            assert secs <= T.length(cover) * 1e-9 * (1 + 1e-9)
