"""Where JAX keeps its persistent compilation cache.

A full-width train step takes tens of seconds to compile, so every
entry point (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
calls :func:`enable_compile_cache` before its first compile.  The cache
key includes the directory, so the directory is fixed: a path that moved
between runs would never hit.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<repo>/.jax_cache`` (gitignored); used only when ``ENV`` is unset
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing is changed here; otherwise the cache goes to the repo's own
    ``.jax_cache``."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
