"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`configure_compile_cache` from ``main``; nothing
calls it at import, so tests and library users keep JAX's own setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (git-ignored). A fixed path: a later run finds the
# entries only if it looks in the same directory.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
