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


# the frame every block sits in: token embedding, final norm, untied LM head
def frame_shapes(cfg: dict) -> dict:
    d, v = cfg["d_model"], cfg["vocab_size"]
    return {
        "embed": ((v, d), d ** -0.5),
        "lm_head": ((d, v), d ** -0.5),
        "final_norm": ((d,), None),
    }


FRAME_NAMES = {("embed",): "embed", ("lm_head",): "lm_head",
               ("final_norm",): "final_norm"}


def shapes(block, cfg: dict) -> dict:
    """Flat weight name -> (shape, init scale or None for ones): the
    frame's and the block's layers'."""
    return {**frame_shapes(cfg), **block.shapes(cfg)}


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


def make(block, cfg: dict, seed: int, dtype,
         shardings: dict | None = None) -> dict:
    """Flat weights {name: array} in ``dtype``; optional per-name
    shardings place them as they are made."""
    spec = shapes(block, cfg)
    return jax.jit(lambda key: _build(spec, key, dtype),
                   out_shardings=shardings)(base_key(seed))


def make_program(block, cfg: dict, seed: int, dtype, struct, shardings=None):
    """The same weights, made directly as the program's parameter tree
    (shape of ``struct``), optionally placed by ``shardings``."""
    spec = shapes(block, cfg)
    return jax.jit(lambda key: program_tree(block, struct,
                                            _build(spec, key, dtype)),
                   out_shardings=shardings)(base_key(seed))


def _leaf_name(block, path) -> str:
    """The flat name of a program leaf: the longest suffix of its path
    that the frame or the block names."""
    names = {**FRAME_NAMES, **block.PROGRAM_NAMES}
    fields = tuple(getattr(p, "name", getattr(p, "key", str(p)))
                   for p in path)
    for n in range(len(fields), 0, -1):
        if fields[-n:] in names:
            return names[fields[-n:]]
    raise KeyError(f"no benchmark weight for program leaf {fields}")


def program_tree(block, struct, flat: dict):
    """The program's parameter tree (shape of ``struct``) filled from the
    flat weights; every flat weight is used exactly once."""
    used = []

    def fill(path, leaf):
        name = _leaf_name(block, path)
        used.append(name)
        arr = flat[name]
        if int(np.prod(leaf.shape)) != int(np.prod(arr.shape)):
            raise ValueError(f"{name}: program {leaf.shape} vs {arr.shape}")
        return arr.reshape(leaf.shape)

    tree = jax.tree_util.tree_map_with_path(fill, struct)
    twice = sorted({n for n in used if used.count(n) > 1})
    unused = sorted(set(flat) - set(used))
    if twice or unused:
        raise ValueError(f"flat weights the program uses twice: {twice}; "
                         f"not at all: {unused}")
    return tree


def named_leaves(block, tree) -> dict:
    """{flat name: program leaf} for a program tree (params or moments)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[_leaf_name(block, path)] = leaf
    return out
