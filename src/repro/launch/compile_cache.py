"""Where JAX keeps its persistent compilation cache.

A compiled program is keyed, among other things, by the cache directory,
so the directory must not move between runs: a path with a temp name, a
pid or a timestamp in it never hits. Entry points call
:func:`enable_compile_cache` once, before their first compile; importing
this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed in-checkout default (``.gitignore`` lists it)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it
    from the environment and no other path is set. Otherwise the cache
    goes to ``<repo>/.jax_cache``.
    """
    import jax

    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
