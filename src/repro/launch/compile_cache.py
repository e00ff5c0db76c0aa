"""JAX's persistent compilation cache, placed from outside or at a fixed
path inside the checkout.

The cache is keyed on (among other things) its directory, so a directory
that moves between runs never hits. ``enable_compile_cache`` therefore
honours ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (and
then sets no directory of its own), and otherwise uses ``<repo>/.jax_cache``
— the same path on every call and in every process, never derived from a
temporary name, a pid or the time. Entry points call it from ``main()``;
importing this module touches no JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache lives in: ``$JAX_COMPILATION_CACHE_DIR`` if
    set and non-empty, else the fixed in-checkout default."""
    return os.environ.get(ENV_VAR) or str(_DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory. When the environment names the directory,
    JAX already reads it from there and nothing is overridden."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
