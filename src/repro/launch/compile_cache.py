"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it lives at one fixed path:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself), else ``.jax_cache/`` at the repository root.  Entry points
(``chip_smoke.py``, the examples, the benchmark scripts) call
`use_compile_cache` before their first compile; importing this module
changes nothing, so the test suite's compile census is unaffected.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
