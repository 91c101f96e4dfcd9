"""Where the entry points that start serving keep JAX's persistent
compilation cache.

A chip run compiles every step program from cold unless the compiled
executables survive between processes.  The cache key includes the
cache's path, so the path must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), else the fixed ``.jax_cache``
directory at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = REPO_ROOT) -> str:
    """Point JAX's persistent compilation cache at its fixed place and
    return that path.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
