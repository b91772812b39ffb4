"""Blocks: each configuration names the module of its layer equations, and
``bench/run.py`` refuses one that names none or a missing one.  A second
block (``swiglu_shared_block.py``: SwiGLU experts and a shared expert),
added by a file alone, runs through both drivers on the CPU at a tiny
size and comes out correct; the same block with the shared expert left
out of its reference comes out not correct."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

from bench import blocks, weights  # noqa: E402
from bench.drivers import serve, train  # noqa: E402
from bench.drivers.common import program_config  # noqa: E402
from test_bench_rehearsal import (SERVE_MIX, TINY, TRAIN_MIX,  # noqa: E402
                                  result_line, spec_for)

CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))
SWIGLU_SHARED = dict(TINY, block="swiglu_shared_block", ffn_type="swiglu",
                     shared_expert=True)


def second_block(with_shared: bool):
    """The test block as its own module object; without the shared
    expert, its reference leaves that expert out (the program keeps it)."""
    name = f"swiglu_shared_block_{'with' if with_shared else 'without'}"
    spec = importlib.util.spec_from_file_location(
        name, HERE / "swiglu_shared_block.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not with_shared:
        mod.shared_expert = lambda h, lw, precision: jnp.zeros_like(h)
    return mod


def limits(cell):
    return json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                      .read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=[p.stem for p in CONFIGS])
def test_every_configuration_names_its_block(path):
    cfg = json.loads(path.read_text())
    block = blocks.named(cfg)
    assert block.__name__ == f"bench.blocks.{cfg['block']}"


@pytest.mark.parametrize("cfg", [{"name": "x"}, {"block": "no_such_block"},
                                 {"block": "../reference"}],
                         ids=["none", "missing", "not_a_name"])
def test_named_refuses(cfg):
    with pytest.raises(ValueError, match="block"):
        blocks.named(cfg)


@pytest.mark.parametrize("block", [None, "no_such_block"],
                         ids=["none", "missing"])
def test_run_refuses_configuration(tmp_path, block):
    """bench/run.py, in a checkout whose configuration names no block or
    a missing one, exits non-zero with a message and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src" / "repro").mkdir(parents=True)
    path = tmp_path / "bench" / "configs" / "transformer-xl-moe.json"
    cfg = json.loads(path.read_text())
    cfg.pop("block")
    if block is not None:
        cfg["block"] = block
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        "--workload", "txl.train.1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "block" in p.stderr and "TPU" not in p.stderr, p.stderr


def test_second_block_fills_the_program_once():
    from repro.models import lm as lm_mod
    block = second_block(True)
    struct = jax.eval_shape(partial(
        lm_mod.init_params, program_config(SWIGLU_SHARED, block)),
        jax.random.PRNGKey(0))
    flat = weights.make(block, SWIGLU_SHARED, 3, jnp.float32)
    tree = weights.program_tree(block, struct, flat)
    leaves = weights.named_leaves(block, tree)
    assert sorted(leaves) == sorted(flat)
    assert len(jax.tree.leaves(tree)) == len(flat)
    for name, leaf in leaves.items():
        assert leaf.size == flat[name].size


@pytest.mark.parametrize("with_shared", [True, False],
                         ids=["shared", "shared_left_out"])
def test_second_block_serves(with_shared):
    cell = "gpt2moe.serve.skewed"
    # the cell's limit, scaled to this size as test_bench_control does
    lim = {"mean_logit_gap": limits(cell)["mean_logit_gap"] / 7}
    spec = spec_for({"name": cell, "chips": 1}, SWIGLU_SHARED, SERVE_MIX,
                    lim, 0, seconds=4.0, block=second_block(with_shared))
    line = result_line(spec, serve.run(spec))
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is with_shared, line["checks"]


@pytest.mark.parametrize("with_shared", [True, False],
                         ids=["shared", "shared_left_out"])
def test_second_block_trains(with_shared):
    cell = "txl.train.1chip"
    spec = spec_for({"name": cell, "chips": 1}, SWIGLU_SHARED, TRAIN_MIX,
                    limits(cell), 0, block=second_block(with_shared))
    line = result_line(spec, train.run(spec))
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is with_shared, line["checks"]
