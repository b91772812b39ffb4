"""Rehearse a whole benchmark run on the CPU at a tiny size: each driver
for a short window, through the driver functions (the TPU check in
``bench/run.py`` is not passed here), and the result line it would print.
Also: ``bench/run.py`` itself refuses a CPU backend."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout, redirect_stderr
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import blocks, run as bench_run  # noqa: E402
from bench.drivers import serve, train  # noqa: E402
from bench.drivers.common import RunSpec  # noqa: E402
from bench.flops import peaks  # noqa: E402

TINY = {"name": "tiny", "program_config": "gpt2-moe", "block": "moe_gelu",
        "n_layers": 2,
        "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
        "vocab_size": 512, "ffn_type": "gelu", "n_experts": 4, "top_k": 2,
        "serve_top_k": 1, "capacity_factor": 1.25, "aux_loss_weight": 0.01,
        "dtype": "bfloat16", "param_dtype": "float32"}

SERVE_MIX = {"driver": "serve", "rate_rps": 4.0, "prompt_lens": [16, 32],
             "prompt_probs": [0.5, 0.5], "output_lens": [4],
             "output_probs": [1.0], "topics": 4, "pool": 64, "zipf_a": 1.1,
             "kappa": 3.0,
             "engine": {"max_batch_requests": 8, "max_batch_tokens": 9},
             "server": {"n_devices": 4, "max_pack": 2, "path_len": 2,
                        "schedule_policy": "lina"},
             "profile": {"n_batches": 1, "batch": 2, "seq": 32},
             "preroll_s": 2.0, "drain_s": 30, "trace_s": 2.0,
             "min_checked_tokens": 8}

TRAIN_MIX = {"driver": "train", "batch": 2, "seq": 32, "zipf_a": 1.2,
             "markov_p": 0.5, "schedule": "priority+partition",
             "optimizer": {"lr": 3e-4, "betas": [0.9, 0.95], "eps": 1e-8,
                           "weight_decay": 0.1, "grad_clip": 1.0,
                           "warmup_steps": 100, "total_steps": 10000}}

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def spec_for(cell, cfg, mix, limits, trace, seed=2**31 + 7, seconds=1.0,
             block=None):
    # the CPU has no peaks; the v5e's let the readers' arithmetic run
    return RunSpec(cell=cell, cfg=cfg, block=block or blocks.named(cfg),
                   mix=mix, limits=limits, seed=seed, seconds=seconds,
                   trace=trace, devices=jax.devices()[:1],
                   peak=peaks("TPU v5 lite"), process_age=lambda: 1.0)


def result_line(spec, res) -> dict:
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        assert bench_run.report(BENCH, spec, res) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert err.getvalue().strip().splitlines()[-1].startswith("correct ")
    return line


def check_schema(line, bench_cell, trace):
    assert list(line)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench_run.cell_metrics(BENCH, bench_cell,
                                                       section)}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_driver_rehearsal(trace):
    cell = {"name": "gpt2moe.serve.skewed", "chips": 1}
    spec = spec_for(cell, TINY, SERVE_MIX, {"max_logit_gap": 1.0}, trace,
                    seconds=4.0)
    res = serve.run(spec)
    line = result_line(spec, res)
    check_schema(line, cell["name"], trace)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]
    if not trace:
        assert line["metrics"]["serve_tok_s"]["value"] > 0
    else:
        # the trace covers the window's last trace_s seconds
        assert 0 < line["device"]["window_s"] < spec.seconds


@pytest.mark.parametrize("trace", [0, 1])
def test_train_driver_rehearsal(trace):
    cell = {"name": "txl.train.1chip", "chips": 1}
    limits = {"loss_gap": 0.05, "grad_gap": 0.2, "change_gap": 0.2}
    spec = spec_for(cell, TINY, TRAIN_MIX, limits, trace)
    res = train.run(spec)
    line = result_line(spec, res)
    check_schema(line, cell["name"], trace)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]


def test_run_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "txl.train.1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
