"""JAX's persistent compilation cache, for the entry points.

Every geometry tier, window size and scan-tail length is its own
program, so a fresh process pays each compile again. ``enable_compile_cache``
turns the persistent cache on: in ``JAX_COMPILATION_CACHE_DIR`` when that
is set (JAX reads the variable itself, and nothing here overrides it),
otherwise in ``<repo>/.jax_cache``. The path is fixed because it is part
of the cache key: a directory that moves never hits. Only entry points
(``chip_smoke.py``, ``benchmarks/run.py``) call this, each naming the
checkout it sits in, so an installed package never picks a directory of
its own; importing ``repro`` leaves the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_cache"


def enable_compile_cache(repo_root: Path) -> str:
    """Turn the persistent cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``repo_root/.jax_cache``
    (``repo_root`` is the checkout of the calling entry point). Caches
    every program, however quick its compile."""
    path = os.environ.get(ENV)
    if not path:
        path = str(Path(repo_root).resolve() / DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
