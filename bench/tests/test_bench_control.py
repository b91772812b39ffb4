"""The comparison that decides ``correct`` has to fail: under the control
(the reference in fp8 in the program's place) and under each fault the
cell's timed path can have.  At a size a test run holds, on the CPU,
through the drivers (the TPU check in ``bench/run.py`` is not passed) and
the cells' committed limits."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import blocks, reference, traffic_gen, weights  # noqa: E402
from bench.drivers import serve, train  # noqa: E402
from test_bench_rehearsal import (SERVE_MIX, TRAIN_MIX, result_line,  # noqa: E402
                                  spec_for)

# small, but wide enough that rounding to fp8 shows in the numbers
SMALL = {"name": "small", "program_config": "gpt2-moe", "block": "moe_gelu",
         "n_layers": 2,
         "d_model": 256, "n_heads": 4, "n_kv_heads": 4, "d_ff": 512,
         "vocab_size": 2048, "ffn_type": "gelu", "n_experts": 4, "top_k": 2,
         "serve_top_k": 1, "capacity_factor": 1.25, "aux_loss_weight": 0.01,
         "dtype": "bfloat16", "param_dtype": "float32"}


def limits(cell):
    return json.loads((ROOT / "bench" / "limits" / f"{cell}.json").read_text())


def correct(checks, lim) -> bool:
    return all(v <= lim[n] for n, v in checks.items() if n in lim)


def test_train_control_fails():
    """The fp8 reference in the program's place fails the limits; the
    program (bf16) passes them."""
    cell = "txl.train.1chip"
    lim = limits(cell)
    mix = TRAIN_MIX
    oc = dict(mix["optimizer"], betas=tuple(mix["optimizer"]["betas"]))
    stream = traffic_gen.LMStream(mix, SMALL["vocab_size"], 11)
    batches = [stream.batch(s) for s in range(train.CHECK_STEPS)]

    block = blocks.named(SMALL)

    def readings(**kw):
        w0 = weights.make(block, SMALL, 11, jnp.float32)
        return reference.train_readings(block, w0, batches, SMALL, oc, ep=1,
                                        **kw)
    ref = readings()
    ctrl = train.compare(readings(precision="fp8"), ref)
    assert not correct(ctrl, lim), ctrl
    # the number the control is held by at the cell's size (PERF.md)
    assert ctrl["head_grad_diff"] > lim["head_grad_diff"], ctrl
    spec = spec_for({"name": cell, "chips": 1}, SMALL, mix, lim, 0, seed=11)
    line = result_line(spec, train.run(spec))
    assert line["correct"], line["checks"]


def run_train_with(step_wrapper, monkeypatch):
    """Run the training driver with ``Trainer.step_fn`` wrapped."""
    from repro.runtime import trainer as trainer_mod
    orig_init = trainer_mod.Trainer.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        self.step_fn = step_wrapper(self.step_fn)
    monkeypatch.setattr(trainer_mod.Trainer, "__init__", init)
    cell = "txl.train.1chip"
    spec = spec_for({"name": cell, "chips": 1}, SMALL, TRAIN_MIX,
                    limits(cell), 0, seed=12)
    return result_line(spec, train.run(spec))


def test_train_fault_state_unchanged(monkeypatch):
    def wrap(step):
        def f(params, opt_state, batch):
            _, _, m = step(params, opt_state, batch)
            return params, opt_state, m
        return f
    line = run_train_with(wrap, monkeypatch)
    assert not line["correct"]
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_fault_half_batch(monkeypatch):
    def wrap(step):
        def f(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half)
        return f
    line = run_train_with(wrap, monkeypatch)
    assert not line["correct"], line["checks"]


def test_train_fault_no_exchange():
    """Four CPU devices, the expert layer's all-to-all replaced by the
    identity: each chip computes its own tokens with its own experts.  The
    training driver's expert-parallel path, held to the training limits."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT)!r}); sys.path.insert(0, {str(ROOT / 'bench' / 'tests')!r})
        import jax
        from jax import lax
        import repro.core.microop as microop
        from test_bench_control import SMALL, limits
        from test_bench_rehearsal import TRAIN_MIX, result_line, spec_for
        from bench.drivers import train
        real = lax.all_to_all
        def ident(x, axis_name, split_axis, concat_axis, **kw):
            return x
        microop.lax.all_to_all = ident
        cell = "txl.train.1chip"
        spec = spec_for({{"name": cell, "chips": 4}}, SMALL, TRAIN_MIX,
                        limits(cell), 0, seed=13)
        spec.devices = jax.devices()[:4]
        line = result_line(spec, train.run(spec))
        print(json.dumps(line["checks"]))
        print("CORRECT", line["correct"])
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CORRECT False" in p.stdout, p.stdout[-2000:]


def test_serve_control_and_altered_token(monkeypatch):
    """The program passes the cell's limit on its own requests; the fp8
    control, judged on the same requests and held to the limit it is
    given, fails it; and a program whose engine alters every other token
    where it picks them fails the cell's limit."""
    cell = "gpt2moe.serve.skewed"
    lim = limits(cell)
    assert set(lim) == {"mean_logit_gap"}
    spec = spec_for({"name": cell, "chips": 1}, SMALL, SERVE_MIX, lim, 0,
                    seconds=4.0, seed=14)
    line = result_line(spec, serve.run(spec))
    assert line["correct"], line["checks"]
    # At this size (2 layers, d 256, vocab 2048) the control reads about
    # 0.1 and the program under 0.005; the cell's limit was set between
    # their readings at the cell's size on the chip (PERF.md).  Here the
    # control is held to a limit scaled alike, 0.35 / 7.
    spec.limits = {"mean_logit_gap": lim["mean_logit_gap"] / 7}
    ctrl = result_line(spec, serve.run(spec, control=True))
    assert not ctrl["correct"], ctrl["checks"]
    assert (ctrl["checks"]["mean_logit_gap"]["value"]
            > 10 * line["checks"]["mean_logit_gap"]["value"])
    import repro.runtime.engine as engine_mod

    class AlteringNumpy:
        calls = 0

        def __getattr__(self, name):
            return getattr(np, name)

        def argmax(self, a, *args, **kw):
            AlteringNumpy.calls += 1
            i = np.argmax(a, *args, **kw)
            return (i + 1) % np.shape(a)[-1] if AlteringNumpy.calls % 2 \
                else i
    monkeypatch.setattr(engine_mod, "np", AlteringNumpy())
    spec = spec_for({"name": cell, "chips": 1}, SMALL, SERVE_MIX, lim, 0,
                    seconds=4.0, seed=15)
    line = result_line(spec, serve.run(spec))
    assert not line["correct"], line["checks"]
