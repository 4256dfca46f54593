"""Where JAX's persistent compilation cache lives.

The directory is part of the cache key, so it must not move between runs:
no temporary name, pid, time or host property goes into it. Whoever runs
the program places the cache by setting ``JAX_COMPILATION_CACHE_DIR`` (jax
reads it itself); then nothing here touches the setting. Left unset, the
cache goes to ``.jax_cache`` at the root of the checkout (git-ignored).

Every entry point calls :func:`configure_compile_cache` before its first
compile: ``polyrl_tpu.train.main``, ``polyrl_tpu.rollout.serve.main``,
``chip_smoke.py``, ``bench.py`` and ``tests/conftest.py``.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Returns the cache directory in effect."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Executables held in the cache directory (0 when it does not exist)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
