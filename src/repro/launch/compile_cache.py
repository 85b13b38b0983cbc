"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.solve``,
``repro.launch.mincut_serve``, ``benchmarks.run``) call :func:`enable`
once, before their first compile; library modules never do.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing in
  the code names another directory.
* unset: the cache lives at ``<repo>/.jax_cache`` (gitignored).  The path
  is fixed on purpose: it is part of what a cached entry is found by, so
  a path built from a temporary name, a process id or the time never hits.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
