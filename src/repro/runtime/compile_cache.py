"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``python -m repro.launch.matserve`` and the
benchmarks).

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where it is set, this module
leaves it alone. Where it is not, the cache goes to one fixed directory
inside the checkout, ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
The directory is part of what lets a later run find an entry again, so it
is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout root is three levels above ``src/repro/runtime/``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    Call it before the first compile of the process: JAX fixes the cache
    location when it first compiles.
    """
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
