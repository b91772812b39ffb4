"""What every driver shares: the run's inputs and outputs, the program's
model configuration built from the configuration file, compile counting,
device memory and the traced window."""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

# JAX's monitoring event for building one executable in this process (a
# backend compile or a fetch from the persistent cache)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class RunSpec:
    cell: dict
    cfg: dict
    block: Any                      # bench/blocks/<cfg["block"]>.py
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict                      # bench/peaks.json entry of the chip
    process_age: Callable[[], float]


@dataclass
class Record:
    """What the per-layer readers read (``bench/metrics/*.py``)."""
    cfg: dict
    mix: dict
    peak: dict                      # bench/peaks.json entry of the chip
    window_s: float
    n_chips: int
    counters: dict = field(default_factory=dict)   # deltas over the window
    spans: dict = field(default_factory=dict)      # name -> seconds summed
    steps: list = field(default_factory=list)      # driver's per-step record
    requests: dict = field(default_factory=dict)   # per-request timings
    work: dict = field(default_factory=dict)       # driver's work counts
    trace: Any = None               # trace_reduce.Trace, traced run only


@dataclass
class Result:
    e2e: dict
    record: Record
    attempted: int
    failed: int
    checks: list                    # [{"name", "value", "limit"}]
    memory_peak_bytes: int
    trace: Any = None


def program_config(cfg: dict, block):
    """The program's ModelConfig for a configuration file: its named
    configuration with every field the file states (the block's
    ``MODEL_KEYS`` and ``MOE_KEYS``)."""
    from repro.configs import get_config
    base = get_config(cfg["program_config"])
    mc = dataclasses.replace(base, **{k: cfg[k] for k in block.MODEL_KEYS})
    moe = dataclasses.replace(mc.moe, **{k: cfg[k] for k in block.MOE_KEYS})
    mc = dataclasses.replace(mc, moe=moe)
    for k in block.MODEL_KEYS:
        if getattr(mc, k) != cfg[k]:
            raise ValueError(f"{k}: the program runs {getattr(mc, k)!r}, "
                             f"the configuration states {cfg[k]!r}")
    return mc


@contextlib.contextmanager
def count_compiles():
    """Executables built inside the block: {"n", "seconds"}."""
    from jax import monitoring
    tot = {"n": 0, "seconds": 0.0}

    def on(event, secs, **_):
        if event == COMPILE_EVENT:
            tot["n"] += 1
            tot["seconds"] += secs
    monitoring.register_event_duration_secs_listener(on)
    try:
        yield tot
    finally:
        monitoring.unregister_event_duration_listener(on)


def annotate(on: bool, name: str):
    """A host span in the device trace (traced runs only)."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Window:
    """The measured window; with ``trace`` a device trace of it, reduced
    when it closes (``self.trace``)."""

    def __init__(self, trace: bool, devices):
        self.tracing = trace
        self.devices = devices
        self.trace = None
        self._dir: Optional[str] = None

    def __enter__(self):
        if self.tracing:
            import jax
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            # keep the trace small: the benchmark's own annotations are
            # user-level, the runtime's dispatch events are not needed
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def close(self, t0: float, t1: float) -> None:
        """Stop tracing; the window ran from t0 to t1 (host clock)."""
        if not self.tracing or self._dir is None:
            return
        import glob

        import jax

        from bench import trace_reduce
        c0 = time.perf_counter()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self._dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        c1 = time.perf_counter()
        self.trace = trace_reduce.reduce(files[0], window_s=t1 - t0,
                                         n_devices=len(self.devices))
        print(f"trace: {os.path.getsize(files[0]) / 2**20:.1f} MiB written "
              f"in {c1 - c0:.1f} s, reduced in {time.perf_counter() - c1:.1f}"
              f" s; {sum(map(len, self.trace.ops.values()))} device ops, "
              f"{len(self.trace.host)} benchmark host spans", file=sys.stderr)
        shutil.rmtree(self._dir, ignore_errors=True)
        self._dir = None

    def __exit__(self, *exc):
        if self._dir is not None:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        return False
