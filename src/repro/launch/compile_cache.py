"""JAX's persistent compilation cache, set up by each entry point.

``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py`` call
:func:`setup_compile_cache` before their first compile; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and no directory is set in code.  Otherwise the cache lives
at the fixed path ``<checkout>/.jax_cache`` (listed in ``.gitignore``), so
successive runs from one checkout find each other's programs.
"""
from __future__ import annotations

import os

__all__ = ["setup_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
