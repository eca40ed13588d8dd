"""Persistent XLA compile cache for the launchers.

Called from a launcher's ``main()`` (``launch/serve.py``, ``chip_smoke.py``),
never at import and never from tests: a process that keeps its compiled
programs across runs starts serving without recompiling every jit.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout (git-ignored): the cache key includes the
# path, so a directory made from a temporary name would never hit
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    it itself) and nothing else is configured; otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
