"""Persistent XLA compile cache, placed from outside the program.

Every entry point that compiles (``cli.main``, ``benchmark/run.py``,
``chip_smoke.py``, the tools) calls :func:`configure_compile_cache` once,
before its first jit:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and the
  program sets nothing — the machine decides where compiled code lives.
* unset: the cache is ``<checkout>/.jax_cache``, a fixed path derived from
  this package's location. The path is part of the cache key, so it never
  comes from ``tempfile``, a pid or the clock; ``.gitignore`` lists it.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache; returns the directory."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
