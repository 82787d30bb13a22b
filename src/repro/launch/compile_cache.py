"""Where JAX keeps its persistent compile cache.

Entry points call ``use_compile_cache()`` before they compile anything:
``chip_smoke.py``, ``python -m repro.launch.train`` and the benchmark
mains. Importing a module never does it, and neither do the tests.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, because the directory is part of what a later run must
    find again (never a temporary, pid- or time-based one)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
