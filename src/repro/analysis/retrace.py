"""Pass 3 — retracing detector (the only runtime pass).

Steady-state serving and training must not re-trace: a new trace means a
new shape/dtype/static-arg reached a jitted function, which on TPU stalls
the serving engine for seconds (the paper's motivation for shape-stable
scheduling).  ``no_retrace()`` wraps a steady-state window and asserts the
jit tracing cache took zero new misses inside it.

Counting uses ``jax._src.test_util.count_jit_tracing_cache_miss`` when
available (it patches ``pjit``'s jaxpr-creation cache); repeat calls with
known shapes hit the C++ fast path and never reach it, so a warmed-up
engine counts exactly zero.  On JAX versions without the hook the detector
degrades to a null counter that reports ``count=None`` and never fails —
gated features must check ``supported()``.
"""
from __future__ import annotations

import contextlib
import dataclasses


class RetraceError(AssertionError):
    pass


@dataclasses.dataclass
class RetraceReport:
    where: str
    allow: int = 0
    count: int | None = None     # None until the window closes / unsupported

    @property
    def ok(self) -> bool:
        return self.count is None or self.count <= self.allow


def _counter_cm():
    try:
        from jax._src import test_util as jtu
        return jtu.count_jit_tracing_cache_miss()
    except (ImportError, AttributeError):
        return None


def supported() -> bool:
    return _counter_cm() is not None


@contextlib.contextmanager
def no_retrace(where: str = "steady-state", *, allow: int = 0,
               strict: bool = True):
    """Context manager asserting zero new jit traces inside the window.

    Yields a RetraceReport; ``report.count`` is filled when the window
    closes.  ``strict=False`` records without raising (the benchmark
    mode); ``allow`` tolerates a known number of first-call traces.
    """
    report = RetraceReport(where=where, allow=allow)
    cm = _counter_cm()
    if cm is None:
        yield report
        return
    with cm as count:
        yield report
    report.count = int(count())
    if strict and not report.ok:
        raise RetraceError(
            f"{report.count} new jit trace(s) during {where} "
            f"(allowed {allow}) — a shape/dtype/static-arg is not "
            f"stable across steady-state steps")
