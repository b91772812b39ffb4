"""Device trace (``.xplane.pb``) -> device events, busy and idle time,
per-kernel time, exposed collective time and the ``breakdown``.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per executed HLO instruction; the event's name is the
instruction's text (``%name = <result> <op>(<operands>), ...``), and the
Mosaic kernels the Pallas calls compile to are ``custom-call`` instructions
with ``custom_call_target="tpu_custom_call"``.  The kernels carry no name
of their own, so the per-layer readers find them by signature (operand and
result shapes, see ``Op``).  Host threads are on ``/host:CPU``; the
benchmark's own ``jax.profiler.TraceAnnotation`` spans there (names
starting ``bench.``) say what the host was doing during a device gap.
All timestamps share one clock.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_SHAPE = re.compile(r"\b(pred|s8|s16|s32|s64|u8|u16|u32|u64|bf16|f16|f32|f64|"
                    r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_OP = re.compile(r"^%?([^\s=]+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)$", re.S)
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-reduce-start", "all-reduce-done",
               "all-gather-start", "all-gather-done",
               "collective-permute-start", "collective-permute-done")


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE.finditer(text)]


@dataclass
class Op:
    """One device event: the HLO instruction, its kind, shapes, times."""
    name: str
    kind: str                       # HLO opcode: fusion, custom-call, ...
    target: str                     # custom_call_target, or ""
    results: list                   # [(dtype, dims)]
    operands: list                  # [(dtype, dims)]
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def is_kernel(self) -> bool:
        return self.kind == "custom-call" and self.target == "tpu_custom_call"

    @property
    def is_collective(self) -> bool:
        return self.kind in COLLECTIVES

    @property
    def is_control(self) -> bool:
        """A loop or call whose interval spans the ops of its body."""
        return self.kind in ("while", "conditional", "call")


def parse_op(text: str, start_ns: float, dur_ns: float) -> Op:
    return Op(*_parse_instruction(text), start_ns, dur_ns)


@functools.lru_cache(maxsize=None)
def _parse_instruction(text: str):
    """(name, kind, target, results, operands) of one instruction's text;
    an instruction runs many times in a window, so each is parsed once."""
    m = _OP.match(text)
    if not m:
        return text, text.split("(")[0], "", [], []
    name, result, kind, rest = m.groups()
    depth, i = 1, 0
    while i < len(rest) and depth:
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        i += 1
    args, attrs = rest[:i - 1], rest[i:]
    tm = re.search(r'custom_call_target="([^"]*)"', attrs)
    return (name, kind, tm.group(1) if tm else "", _shapes(result),
            _shapes(args))


def union(intervals) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover) -> List[Tuple[float, float]]:
    """Parts of ``intervals`` (a union) not inside ``cover`` (a union)."""
    out, j = [], 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


@dataclass
class Trace:
    window_s: float
    ops: Dict[int, List[Op]]                     # chip -> ops
    host: List[Tuple[str, float, float]] = field(default_factory=list)
    busy_by_chip: Dict[int, float] = field(default_factory=dict)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.busy_by_chip:
            return 0.0
        return sum(self.busy_by_chip.values()) / len(self.busy_by_chip)

    def idle_share_max(self) -> Optional[float]:
        """Idle share of the most idle chip."""
        if not self.busy_by_chip or self.window_s <= 0:
            return None
        return 1.0 - min(self.busy_by_chip.values()) / self.window_s

    def kernel_seconds(self, pred) -> float:
        """Device seconds of the ops ``pred`` accepts, summed over chips."""
        return sum(op.dur_ns for ops in self.ops.values() for op in ops
                   if pred(op)) * 1e-9

    def exposed_seconds(self, pred) -> Dict[int, float]:
        """Per chip: seconds of the ops ``pred`` accepts during which no
        other (non-collective) operation runs on that chip."""
        out = {}
        for chip, ops in self.ops.items():
            sel = union((o.start_ns, o.end_ns) for o in ops if pred(o))
            comp = union((o.start_ns, o.end_ns) for o in ops
                         if not (pred(o) or o.is_collective or o.is_control))
            out[chip] = length(subtract(sel, comp)) * 1e-9
        return out

    @property
    def breakdown(self) -> dict:
        """Top device ops by time, and the longest idle gaps on chip 0
        named by the benchmark's host annotation that covers them."""
        tot: Dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                if o.is_control:
                    continue
                key = f"{o.kind}:{o.name.split('.')[0]}"
                tot[key] = tot.get(key, 0.0) + o.dur_ns * 1e-9
        n = max(len(self.ops), 1)
        top = sorted(((k, v / n) for k, v in tot.items()),
                     key=lambda kv: -kv[1])[:10]
        gaps = []
        chip0 = self.ops.get(min(self.ops)) if self.ops else []
        busy = union((o.start_ns, o.end_ns) for o in chip0)
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b > a:
                gaps.append((a, b))
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for a, b in gaps[:10]:
            mid = 0.5 * (a + b)
            what = "none"
            best = None
            for name, hs, he in self.host:
                if hs <= mid <= he and (best is None or he - hs < best):
                    what, best = name, he - hs
            named.append([what, (b - a) * 1e-9])
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": named}


def reduce(path: str, window_s: float, n_devices: int) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler`` (or a gzip of
    one, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    host = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [parse_op(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    for chip in range(n_devices):
        ops.setdefault(chip, [])
    tr = Trace(window_s=window_s, ops=ops, host=host)
    for chip, chip_ops in ops.items():
        busy = union((o.start_ns, o.end_ns) for o in chip_ops)
        tr.busy_by_chip[chip] = length(busy) * 1e-9
    return tr
