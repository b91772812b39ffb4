"""Device-time attribution: jax.profiler hooks + overlap-phase replay.

Two pieces:

  * ``trace_session`` / ``StepProfiler`` — optional ``jax.profiler`` trace
    capture around N steps.  Off unless given a log directory; once a trace
    is requested, a failure to start or stop it raises (a run that asked
    for a device trace must not finish without one).  The captured trace
    is where the fwd/bwd device-time split inside a jitted train step
    actually lives; the host-side spans around it (``runtime.trainer``)
    carry the schedule attribution.

  * ``attribute_overlap`` — replays the overlap microbench's measured
    phases (per-variant serial baseline, a2a-only reference, pipelined
    time; ``benchmarks.train_side`` rows / the ``overlap`` key of
    ``BENCH_schedules.json``) into a span tree, so "fraction of the a2a
    hidden" becomes a quantity recomputable FROM THE TRACE
    (``hidden_fraction``) instead of a bench-only number.  The identity
    pinned by tests: for every row,
    ``hidden_fraction(attribute_overlap(...)) == row["a2a_hidden_frac"]``
    within float tolerance, surviving a Chrome-trace export round-trip.
"""
from __future__ import annotations

from typing import List, Optional

from repro.obs.tracer import Span, Tracer

__all__ = ["trace_session", "StepProfiler", "attribute_overlap",
           "hidden_fraction"]


class trace_session:
    """Context manager around ``jax.profiler.start_trace`` /
    ``stop_trace``.  ``active`` reports whether a device trace is being
    captured; errors from starting or stopping it propagate."""

    def __init__(self, logdir: Optional[str], enabled: bool = True):
        self.logdir = logdir
        self.enabled = enabled and logdir is not None
        self.active = False

    def __enter__(self) -> "trace_session":
        if not self.enabled:
            return self
        import jax
        jax.profiler.start_trace(self.logdir)
        self.active = True
        return self

    def __exit__(self, *exc):
        if self.active:
            import jax
            self.active = False
            jax.profiler.stop_trace()
        return False


class StepProfiler:
    """Start a jax.profiler trace at step ``start`` and stop it after
    ``steps`` profiled steps — the usual "skip compile, profile a window"
    shape.  Drive it with ``on_step(step_idx)`` from any loop."""

    def __init__(self, logdir: Optional[str], start: int = 2,
                 steps: int = 3, enabled: bool = True):
        self.start = int(start)
        self.stop_at = int(start) + int(steps)
        self._session = trace_session(logdir, enabled=enabled)
        self._started = False

    @property
    def active(self) -> bool:
        return self._session.active

    def on_step(self, step: int) -> None:
        if not self._started and step >= self.start:
            self._started = True
            self._session.__enter__()
        if self._session.active and step >= self.stop_at:
            self._session.__exit__()

    def close(self) -> None:
        self._session.__exit__()


def attribute_overlap(tracer: Tracer, rows, t0: float = 0.0) -> List:
    """Replay overlap-microbench rows into spans.

    Each row (a dict with ``variant``, ``chunks_requested``,
    ``chunks_chosen``, ``us_per_call``, ``serial_us``, ``a2a_us``,
    ``a2a_hidden_frac`` — the schema of ``BENCH_schedules.json``'s
    ``overlap`` key) becomes one root span with three sequential phase
    children::

        overlap/<variant>-c<requested>
          ├─ serial      (pipeline-off baseline, serial_us)
          ├─ a2a_only    (chunked dispatch+combine with identity expert)
          └─ pipelined   (the overlapped variant, us_per_call)

    Spans are laid out back-to-back from ``t0`` on a microsecond-scaled
    timeline.  Returns the created root spans (empty when disabled)."""
    roots = []
    cursor = float(t0)
    for row in rows:
        ser = float(row["serial_us"]) * 1e-6
        a2a = float(row["a2a_us"]) * 1e-6
        pipe = float(row["us_per_call"]) * 1e-6
        name = (f"overlap/{row['variant']}"
                f"-c{row.get('chunks_requested', '?')}")
        root = tracer.add(name, cursor, cursor + ser + a2a + pipe,
                          **{k: row[k] for k in
                             ("mode", "variant", "chunks_requested",
                              "chunks_chosen", "a2a_hidden_frac")
                             if k in row})
        t = cursor
        root.child("serial", t, t + ser)
        t += ser
        root.child("a2a_only", t, t + a2a)
        t += a2a
        root.child("pipelined", t, t + pipe)
        cursor += ser + a2a + pipe
        roots.append(root)
    return roots


def hidden_fraction(span: Span) -> float:
    """Recompute the overlap efficiency from an attribution span's phase
    children: ``(serial - pipelined) / a2a_only``, clipped to [0, 1] —
    the same formula ``benchmarks.train_side`` measures, but sourced from
    the (possibly Chrome-round-tripped) trace."""
    dur = {}
    for c in span.children:
        dur[c.name] = c.duration
    a2a = dur.get("a2a_only", 0.0)
    if a2a <= 0:
        return 0.0
    frac = (dur.get("serial", 0.0) - dur.get("pipelined", 0.0)) / a2a
    return max(0.0, min(1.0, frac))
