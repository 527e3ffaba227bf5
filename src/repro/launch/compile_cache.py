"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/api_server.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` before their first
compile, so a second run of the same graphs on the same machine loads them
instead of compiling again.  Nothing calls it at import and tests never do.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself; this
  function sets nothing else.
* unset: the cache lives at ``<repo>/.jax_cache``.  The path is fixed -- never
  a temp name, a pid or the time -- because it is part of what a later run
  must find again.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
