"""Where JAX keeps its persistent compilation cache.

A cold process compiles every program again, and the chip-side programs
here take tens of seconds each (``core/solver/ipm_jax``). Entry points
that run on the chip call :func:`use_compile_cache` before their first
compile. The cache is found again only at the same path, so the path is
fixed, never derived from a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

# .jax_cache/ at the root of the checkout (gitignored)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to ``CACHE_DIR``. Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
