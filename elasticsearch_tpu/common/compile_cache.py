"""The one place that decides where JAX's persistent compilation cache
lives.

Every entry point that compiles serving programs (`rest/server.py main`,
`bench.py`, `chip_smoke.py`) calls `configure_compile_cache()` before JAX
compiles anything. The rule:

- `JAX_COMPILATION_CACHE_DIR` set → JAX reads it itself; nothing here
  sets another directory in code.
- unset → one fixed, git-ignored directory at the root of the checkout.
  The directory is part of the cache key's environment, so it is never
  built from a temporary name, a pid or the time: a path that moves
  never hits.
"""

from __future__ import annotations

import os
from typing import Tuple

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIRNAME = ".jax_compile_cache"


def compile_cache_dir() -> Tuple[str, bool]:
    """(directory, from_env): where the cache goes and whether the
    environment chose it."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env, True
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, CHECKOUT_CACHE_DIRNAME), False


def configure_compile_cache() -> str:
    """Points JAX at the cache directory and returns it. With the
    environment variable set this only reports the path JAX already
    uses."""
    path, from_env = compile_cache_dir()
    if not from_env:
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
