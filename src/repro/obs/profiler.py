"""Device-time capture: ``jax.profiler`` hooks.

``trace_session`` / ``StepProfiler`` — optional ``jax.profiler`` trace
capture around N steps.  Off unless given a log directory; once a trace is
requested, a failure to start or stop it raises (a run that asked for a
device trace must not finish without one).  The captured trace is where
the fwd/bwd device-time split inside a jitted train step actually lives;
the tracer's spans appear in it as ``repro.*`` host events on the same
clock (``repro.obs.tracer``).
"""
from __future__ import annotations

from typing import Optional

__all__ = ["trace_session", "StepProfiler"]


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
