"""Device set-up and the guards that keep a chip run honest: the TPU
check, the compile-cache directory rule, the profiler that must not
swallow a failed trace, the benchmark workers that must not fight the
parent for the chip, and the explicit gradient reduce's layout."""
import os

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.launch import device
from repro.launch.sharding import reduce_specs
from repro.models import lm as lm_mod
from repro.obs import StepProfiler, trace_session

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_tpu_refuses_the_cpu_backend():
    with pytest.raises(RuntimeError, match="TPU was required"):
        device.require_tpu()


def test_compile_cache_uses_exported_dir_and_sets_nothing(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = device.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_trace_session_raises_when_the_trace_cannot_start(monkeypatch,
                                                          tmp_path):
    def boom(logdir):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with trace_session(str(tmp_path)):
            pass
    prof = StepProfiler(str(tmp_path), start=0, steps=1)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        prof.on_step(0)


def test_trace_session_raises_when_the_trace_cannot_stop(monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir: None)

    def boom():
        raise RuntimeError("trace lost")
    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    sess = trace_session(str(tmp_path))
    with pytest.raises(RuntimeError, match="trace lost"):
        with sess:
            assert sess.active
    assert not sess.active


def test_trace_session_off_without_logdir(monkeypatch):
    def boom(logdir):
        raise AssertionError("must not start")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with trace_session(None) as sess:
        assert not sess.active


def test_forced_host_workers_refuse_a_tpu_parent(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    from benchmarks import train_side
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        train_side.overlap_rows_subprocess(device_count=4)
    with pytest.raises(RuntimeError, match="holds the TPU"):
        train_side.measured_schedule_ablation(device_count=4)


def test_reduce_specs_keep_expert_shards_and_drop_dp_axes():
    cfg = get_config("gpt2-moe").smoke()
    mesh = AbstractMesh((2, 4), ("data", "model"))
    params = jax.eval_shape(lambda k: lm_mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = reduce_specs(cfg, mesh, params)
    flat = [s for s in jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, P)) if isinstance(s, P)]
    # the reduce runs over `data`: no gradient may be split over it
    assert flat and all("data" not in str(s) for s in flat)
    # expert weights stay split over `model` (4 experts / 4 devices)
    assert specs.stack.moe.wi == P(None, "model", None, None)
    assert specs.stack.moe.wo == P(None, "model", None, None)
