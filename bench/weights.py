"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference is handed
the same flat arrays (regenerated from the seed), and the program gets them
reshaped into its own parameter tree, which is found by field name from
``jax.eval_shape`` of the program's initialiser.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A PRNG key from any non-negative seed, 31 bits at a time."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(cfg: dict) -> dict:
    """Flat weight name -> (shape, init scale or None for ones)."""
    L, d, f, e, v = (cfg["n_layers"], cfg["d_model"], cfg["d_ff"],
                     cfg["n_experts"], cfg["vocab_size"])
    hq = cfg["n_heads"] * (d // cfg["n_heads"])
    hkv = cfg["n_kv_heads"] * (d // cfg["n_heads"])
    return {
        "embed": ((v, d), d ** -0.5),
        "lm_head": ((d, v), d ** -0.5),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, hq), d ** -0.5),
        "wk": ((L, d, hkv), d ** -0.5),
        "wv": ((L, d, hkv), d ** -0.5),
        "wo": ((L, hq, d), hq ** -0.5),
        "router": ((L, d, e), d ** -0.5),
        "wi": ((L, e, d, f), d ** -0.5),
        "wo_e": ((L, e, f, d), f ** -0.5),
    }


def _build(spec: dict, key, dtype) -> dict:
    out = {}
    for i, name in enumerate(sorted(spec)):
        shape, scale = spec[name]
        if scale is None:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32) * scale).astype(dtype)
    return out


def make(cfg: dict, seed: int, dtype, shardings: dict | None = None) -> dict:
    """Flat weights {name: array} in ``dtype``; optional per-name
    shardings place them as they are made."""
    spec = shapes(cfg)
    return jax.jit(lambda key: _build(spec, key, dtype),
                   out_shardings=shardings)(base_key(seed))


def make_program(cfg: dict, seed: int, dtype, struct, shardings=None):
    """The same weights, made directly as the program's parameter tree
    (shape of ``struct``), optionally placed by ``shardings``."""
    spec = shapes(cfg)
    return jax.jit(lambda key: program_tree(struct, _build(spec, key, dtype)),
                   out_shardings=shardings)(base_key(seed))


# program leaf path suffix -> flat name
_PROGRAM_NAMES = {
    ("embed",): "embed", ("lm_head",): "lm_head",
    ("final_norm",): "final_norm", ("ln1",): "ln1", ("ln2",): "ln2",
    ("attn", "wq"): "wq", ("attn", "wk"): "wk", ("attn", "wv"): "wv",
    ("attn", "wo"): "wo", ("moe", "router"): "router",
    ("moe", "wi"): "wi", ("moe", "wo"): "wo_e",
}


def _leaf_name(path) -> str:
    fields = tuple(getattr(p, "name", getattr(p, "key", str(p)))
                   for p in path)
    for n in (2, 1):
        if fields[-n:] in _PROGRAM_NAMES:
            return _PROGRAM_NAMES[fields[-n:]]
    raise KeyError(f"no benchmark weight for program leaf {fields}")


def program_tree(struct, flat: dict):
    """The program's parameter tree (shape of ``struct``) filled from the
    flat weights; every flat weight is used exactly once."""
    used = set()

    def fill(path, leaf):
        name = _leaf_name(path)
        used.add(name)
        arr = flat[name]
        if int(np.prod(leaf.shape)) != int(np.prod(arr.shape)):
            raise ValueError(f"{name}: program {leaf.shape} vs {arr.shape}")
        return arr.reshape(leaf.shape)

    tree = jax.tree_util.tree_map_with_path(fill, struct)
    if used != set(flat):
        raise ValueError(f"weights not used by the program: "
                         f"{sorted(set(flat) - used)}")
    return tree


def named_leaves(tree) -> dict:
    """{flat name: program leaf} for a program tree (params or moments)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[_leaf_name(path)] = leaf
    return out
