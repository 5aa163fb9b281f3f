"""Where JAX keeps its persistent compilation cache.

Each entry point (``chip_smoke.py``, ``python -m repro.experiments.campaign``,
``python -m repro.launch.train``) calls :func:`enable_compile_cache` once,
so processes that compile the same programs find them again.  A directory
named by ``JAX_COMPILATION_CACHE_DIR`` wins and is left alone (JAX reads
that variable itself); otherwise the cache goes to ``<repo>/.jax_cache``.
The path is part of each entry's key, so it is fixed: never built from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
