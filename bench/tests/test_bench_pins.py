"""Pins of the ``moe_gelu`` block's readings on the CPU, as the benchmark
read them before the layer equations moved into ``bench/blocks/``: the
training driver's rehearsal at its seed, and the reference's serving gaps
and training readings on fixed inputs.  Moving code may not move a digit.

The serving driver's own rehearsal is not pinned: which requests finish
in its window depends on the wall clock, so its gaps differ between runs
of one seed.  Its reference is pinned here on fixed requests instead.
"""
from __future__ import annotations

import io
import re
import sys
from contextlib import redirect_stderr
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import blocks, reference, traffic_gen, weights  # noqa: E402
from bench.drivers import train  # noqa: E402
from test_bench_rehearsal import TINY, TRAIN_MIX, spec_for  # noqa: E402

BLOCK = blocks.named(TINY)
SEED = 2**31 + 7

TRAIN_READINGS = {
    "loss_gap": 0.00045718136851458194,
    "loss_gap_step0": 0.00045718136851458194,
    "grad_gap": 0.014249415229019765,
    "grad_gap_median": 0.0013182663447883756,
    "grad_diff": 0.06019324084439095,
    "grad_diff_median": 0.047054440419879426,
    "head_grad_diff": 0.014916234649717808,
    "change_gap": 0.0035948938938394127,
    "change_gap_median": 0.00038457041161181997,
}

# (mean, max) of the reference's gaps on fixed requests
SERVE_GAPS = {
    "served": (2.8635571002960205, 4.921438694000244),
    "control": (0.1744510531425476, 1.666076898574829),
}

# losses of the three steps and the kept share
REFERENCE_TRAIN = {
    "f32": ([6.6900224685668945, 6.656911373138428, 6.6107072830200195],
            0.9479166666666666, {}),
    "fp8": ([6.679682731628418, 6.6772661209106445, 6.605130195617676],
            0.9453125, {"precision": "fp8"}),
    "half_batch": ([6.772355079650879, 6.674501895904541, 6.822350978851318],
                   0.9479166666666666, {"fault": "half_batch"}),
}


def test_train_rehearsal_readings_pinned():
    limits = {"loss_gap": 0.05, "grad_gap": 0.2, "change_gap": 0.2}
    spec = spec_for({"name": "txl.train.1chip", "chips": 1}, TINY, TRAIN_MIX,
                    limits, 0, seed=SEED)
    err = io.StringIO()
    with redirect_stderr(err):
        res = train.run(spec)
    got = {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^reading (\w+) = (\S+)$", err.getvalue(), re.M)}
    assert got == TRAIN_READINGS
    tokens = res.attempted * TRAIN_MIX["batch"] * TRAIN_MIX["seq"]
    assert res.record.work["model_flops"] / tokens == 814080.0
    assert res.record.work["kept_rows"] / tokens == 1.9609375


def fixed_requests(vocab: int) -> list:
    rng = np.random.default_rng(5)
    return [(rng.integers(0, vocab, p).astype(np.int32),
             rng.integers(0, vocab, s).astype(np.int32))
            for p, s in ((16, 4), (32, 4), (24, 7), (40, 1), (9, 3),
                         (33, 5), (16, 2), (28, 6), (12, 4))]


@pytest.mark.parametrize("which", sorted(SERVE_GAPS))
def test_reference_serve_gaps_pinned(which):
    w = weights.make(BLOCK, TINY, SEED, jnp.bfloat16)
    gaps = np.concatenate(reference.serve_gaps(
        BLOCK, w, fixed_requests(TINY["vocab_size"]), TINY,
        control=which == "control"))
    assert (float(gaps.mean()), float(gaps.max())) == SERVE_GAPS[which]


@pytest.mark.parametrize("which", sorted(REFERENCE_TRAIN))
def test_reference_train_readings_pinned(which):
    losses, kept, kw = REFERENCE_TRAIN[which]
    oc = dict(TRAIN_MIX["optimizer"],
              betas=tuple(TRAIN_MIX["optimizer"]["betas"]))
    stream = traffic_gen.LMStream(TRAIN_MIX, TINY["vocab_size"], 11)
    got = reference.train_readings(
        BLOCK, weights.make(BLOCK, TINY, 11, jnp.float32),
        [stream.batch(s) for s in range(3)], TINY, oc, ep=1, **kw)
    assert got["loss"] == losses
    assert got["kept_share"] == kept
