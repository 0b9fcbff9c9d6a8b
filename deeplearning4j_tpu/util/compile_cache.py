"""JAX persistent compilation cache placement.

One helper, called from every process entry point that compiles
(``chip_smoke.py``, ``benchmark/run.py``, the fleet worker, the
``parallel.main`` CLI) — never at package import, so a library user's own
cache configuration is left alone.

The directory comes from OUTSIDE the program when
``JAX_COMPILATION_CACHE_DIR`` is set: jax reads that variable itself, so
nothing is set in code. Otherwise it is ONE fixed path inside the
checkout. The path is part of the cache key, so it must never depend on
a temp dir, a pid or a clock — a directory that moves never hits.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default, listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and
    return that directory. Call before the first compile."""
    env = os.environ.get(_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
