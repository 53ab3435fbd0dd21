"""JAX's persistent compilation cache, kept where every entry point finds it.

A cold process on the chip compiles every kernel and step program again;
the persistent cache lets a later process (or a later call of the same
command) load them instead. A process finds only what an earlier one left
in the same directory, so it must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads the variable itself), and otherwise one fixed
directory inside the checkout, ``<repo>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's fixed cache directory: src/repro/utils/ -> <repo>/.jax_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and no
    other directory is set here."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
