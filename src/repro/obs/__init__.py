"""Unified tracing + metrics for serving and training (the §3 substrate).

Lina's design is justified by a *measurement* — §3 attributes step time to
all-to-all vs compute before §4/§5 spend that attribution.  ``repro.obs``
is the first-class home for producing the same breakdown here:

  ``tracer``   — nested spans with JSON + Chrome ``trace_event`` export
                 (open in Perfetto) and a no-op disabled fast path;
  ``metrics``  — counters / gauges / fixed-bucket histograms with
                 Prometheus-text and JSON snapshot export;
  ``profiler`` — guarded ``jax.profiler`` trace sessions; while the
                 tracer is enabled its spans appear in such a device
                 trace as ``repro.*`` host events.

``ObsContext`` bundles one tracer + one registry; the serving stack shares
a single context (``MoEServer`` owns one, ``ServingEngine`` inherits or
overrides it), the trainer owns its own.  ``python -m repro.obs validate``
checks an exported trace against the span-tree invariants (CI gates on it).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

from repro.obs import tracer as tracer_mod
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               parse_prometheus)
from repro.obs.profiler import StepProfiler, trace_session
from repro.obs.tracer import (NOOP, Span, Tracer, check_span_tree,
                              to_chrome, to_json, tree_from_chrome)

__all__ = [
    "ObsContext", "Tracer", "Span", "NOOP", "MetricsRegistry", "Counter",
    "Gauge", "Histogram", "parse_prometheus", "to_json", "to_chrome",
    "tree_from_chrome", "check_span_tree", "trace_session", "StepProfiler",
]


@dataclass
class ObsContext:
    """One tracer + one metrics registry, shared across a subsystem stack.
    Metrics are always live (counter bumps are dict lookups — the ledgers
    must be queryable even in production); span recording is opt-in."""
    tracer: Tracer
    metrics: MetricsRegistry

    @classmethod
    def disabled(cls) -> "ObsContext":
        """Tracing off (no-op spans), metrics on — the default wiring."""
        return cls(Tracer(enabled=False), MetricsRegistry())

    @classmethod
    def enabled(cls, clock=None) -> "ObsContext":
        tr = Tracer(enabled=True) if clock is None \
            else Tracer(enabled=True, clock=clock)
        return cls(tr, MetricsRegistry())

    def export(self, out_dir: str) -> dict:
        """Write the standard artifact set under ``out_dir``:
        ``trace.json`` (Chrome trace_event, Perfetto-viewable),
        ``spans.json`` (lossless nested tree the validator consumes),
        ``metrics.prom`` + ``metrics.json`` (registry snapshots).
        Returns {artifact name: path}."""
        os.makedirs(out_dir, exist_ok=True)
        paths = {}
        p = os.path.join(out_dir, "trace.json")
        with open(p, "w") as f:
            json.dump(to_chrome(self.tracer), f)
        paths["trace"] = p
        p = os.path.join(out_dir, "spans.json")
        with open(p, "w") as f:
            json.dump(to_json(self.tracer), f)
        paths["spans"] = p
        p = os.path.join(out_dir, "metrics.prom")
        with open(p, "w") as f:
            f.write(self.metrics.to_prometheus())
        paths["prom"] = p
        p = os.path.join(out_dir, "metrics.json")
        with open(p, "w") as f:
            json.dump(self.metrics.to_json(), f, indent=1)
        paths["metrics"] = p
        return paths
