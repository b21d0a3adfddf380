"""Where JAX keeps its persistent compilation cache.

Every entry point (the CLI, ``bench.py``, ``chip_smoke.py``) calls
:func:`use_compile_cache` before it compiles anything. The cache path is
part of the key a later run must find, so it never moves: the
directory ``JAX_COMPILATION_CACHE_DIR`` names, which JAX reads itself,
or else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """The directory compiled programs are cached in. Sets JAX's cache
    directory only when the environment names none."""
    where = os.environ.get(ENV)
    if where:
        return where
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
