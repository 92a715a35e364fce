"""Where the chip entry points keep JAX's persistent compile cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing
here overrides it.  Otherwise the cache goes to one fixed directory inside
the checkout: the path is part of the cache key, so a directory derived
from a temporary name, a process id or the time would never hit again.
Library code and tests never call this; only the command-line entry points
(``chip_smoke.py``, ``benchmarks/run.py``) do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(default_dir) -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX already honours it);
    else ``default_dir`` (a fixed in-checkout path such as
    ``<repo>/.jax_cache``, listed in ``.gitignore``).
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
