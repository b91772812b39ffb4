"""Set-up shared by the entry points that run on the chip: refuse to run on
another backend when the chip was asked for, and keep JAX's persistent
compilation cache at one fixed directory."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (listed in .gitignore): a fixed path, because the cache
# directory is part of what a later run has to find again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    An exported ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def require_tpu() -> list:
    """``jax.devices()`` when they are TPUs; raises otherwise (no fallback
    to another backend)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"a TPU was required but JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return devs
