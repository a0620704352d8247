"""JAX's persistent compilation cache, at one fixed place per checkout.

Every process that compiles a train step (the launcher, each worker the job
master spawns or re-execs, ``chip_smoke.py``) calls ``enable_compile_cache``
first, so a re-exec'd worker or a resumed job finds the programs its
predecessor compiled instead of compiling them again. The directory is part
of the cache's key: it never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (git-ignored), used when ``ENV_VAR`` is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def compile_cache_dir() -> str:
    """Where compiled programs are kept: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else the checkout's ``.jax_cache``."""
    return os.environ.get(ENV_VAR) or os.path.normpath(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here. Call before the first compile.
    """
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
