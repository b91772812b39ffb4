"""One module per architecture: everything of the benchmark that depends on
a block's layer equations.  A configuration names its block
(``"block": "<name>"``), and ``bench/blocks/<name>.py`` is it; there is no
default.

A block module gives:

- ``MODEL_KEYS``, ``MOE_KEYS``: the configuration's keys that are fields of
  the program's ``ModelConfig`` and of its ``MoEConfig``.
- ``shapes(cfg)``: the layers' flat weights, name -> (shape, init scale or
  None for ones); the frame every block sits in (token embedding, final
  norm, untied LM head) is ``bench.weights.frame_shapes``.
- ``PROGRAM_NAMES``: program leaf-path suffix -> flat name, for the
  layers' weights.
- ``EXPERT_SHARDED``: the flat weights split over their expert axis
  (axis 1) when the reference runs on more than one chip.
- ``train_layers(x, w, cfg, moe, precision)`` -> (x, each MoE layer's
  auxiliary loss, each MoE layer's kept share) and
  ``serve_layers(x, w, cfg, moe, precision)`` -> x: the plain float32
  forward through every layer, ``moe`` being the shared dispatch of
  ``bench.reference`` (``TrainDispatch`` or ``ServeDispatch``), which the
  block hands its router (routing to ``moe.k`` experts) and its experts.
- ``layer_params(cfg, k)``: the weights a token meets in each layer with
  k routed experts; ``moe_layers(cfg)``: how many layers route to experts;
  ``expert_params(cfg)``: the weights of one expert's matrices;
  ``attention_flops_train(cfg, seq)`` (a training token, forward and
  backward), ``attention_flops_prefill(cfg, s)`` (a prompt of s) and
  ``attention_flops_decode(cfg, pos)`` (one position over pos + 1 cached).
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent


def named(cfg: dict):
    """The block module a configuration names, ``bench/blocks/<block>.py``;
    ValueError where it names none or a missing one."""
    name = cfg.get("block")
    if name is None:
        raise ValueError(f"configuration {cfg.get('name')!r} names no block "
                         f"(\"block\": one of bench/blocks/*.py)")
    if not (isinstance(name, str)
            and re.fullmatch(r"[A-Za-z][A-Za-z0-9_]*", name)
            and (HERE / f"{name}.py").is_file()):
        raise ValueError(f"no block {name!r}: bench/blocks/{name}.py does "
                         f"not exist")
    return importlib.import_module(f"{__name__}.{name}")
