"""One persistent compilation cache for every entry point.

A cold chip run otherwise recompiles every program of the model on each
call.  JAX keys cache entries by the cache path too, so the directory is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself, so nothing else is set), else ``<checkout>/.jax_cache`` (listed in
``.gitignore``) — never a temporary path, a process id or a time.  Outside
a checkout (an installed package) the variable must be set.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: repository root: this file is ``<checkout>/src/repro/launch/compile_cache.py``
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    if not (CHECKOUT / "pyproject.toml").is_file():
        # an installed package: its parents are the environment, a path
        # that every run on the machine would share
        raise RuntimeError(
            f"{__name__} is not inside a checkout ({CHECKOUT} has no "
            "pyproject.toml); set JAX_COMPILATION_CACHE_DIR"
        )
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
