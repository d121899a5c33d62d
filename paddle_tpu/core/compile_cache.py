"""Where compiled executables persist: ONE resolver for the whole tree.

The directory is part of a cache entry's key, so it must be the same
for every process that should share executables (trainer restarts,
`cli serve` replicas on one host, bench rounds) and must be placeable
from outside:

  * `JAX_COMPILATION_CACHE_DIR` set — JAX read it at import and uses
    it; this module assigns nothing.
  * unset — `<checkout>/.jax_cache`, a fixed git-ignored path beside
    the package.

JAX's own write thresholds stay as they are (an executable that
compiled in under a second is not worth a file), so
`xla_compile_counts()` hit/miss counts cover the executables that
were worth persisting.
"""
from __future__ import annotations

import os

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "compile_cache_dir"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The persistent compile cache directory in effect, arming the
    fixed default when the environment names none.  Idempotent; every
    component that compiles (Executor, GenerationServer, the bench
    drivers) calls this instead of configuring a cache of its own."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
