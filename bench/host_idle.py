"""Device idle time on chip 0, by the host span that covers it.

The program's tracer puts its spans into a device trace as ``repro.*``
host events (``repro.obs.tracer``), on the clock of the device ops.  For
every gap between device ops on chip 0, ``idle_by_span`` finds the
innermost host span open at each moment of the gap and adds that moment
to the span's name: what the host was doing while the chip had nothing to
run.  Time no span covers is put under ``"none"``.

Host spans are ``(name, start_ns, end_ns)``, as ``trace_reduce.Trace.host``
holds them.  Spans of one thread nest; a span that outlasts the span it
opened in is cut at that span's end.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from bench.trace_reduce import union

Interval = Tuple[float, float]


def idle_gaps(ops) -> List[Interval]:
    """The gaps between the union of ``ops``' intervals (ns)."""
    busy = union((o.start_ns, o.end_ns) for o in ops)
    return [(a, b) for (_, a), (b, _) in zip(busy, busy[1:]) if b > a]


def innermost(spans: Iterable[Tuple[str, float, float]]
              ) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` segments, in order: at each moment
    some span is open, the name of the innermost one."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, name), innermost last
    t = 0.0

    def emit(until: float) -> None:
        nonlocal t
        if until > t:
            if stack:
                segs.append((t, until, stack[-1][1]))
            t = until

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        if not stack:
            t = a
        emit(a)
        end = min(b, stack[-1][0]) if stack else b
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return segs


def idle_by_span(ops, spans) -> Dict[str, float]:
    """Seconds of device idle on the chip whose ``ops`` are given, by the
    innermost host span covering them (``"none"`` where none does)."""
    out: Dict[str, float] = {}
    segs = innermost(spans)
    j = 0
    for a, b in idle_gaps(ops):
        cur = a
        while j < len(segs) and segs[j][1] <= cur:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, name = segs[k]
            if s > cur:
                out["none"] = out.get("none", 0.0) + (s - cur)
            lo, hi = max(cur, s), min(b, e)
            out[name] = out.get(name, 0.0) + (hi - lo)
            cur = hi
            if e > b:
                break
            k += 1
        if cur < b:
            out["none"] = out.get("none", 0.0) + (b - cur)
    return {name: ns * 1e-9 for name, ns in out.items()}
