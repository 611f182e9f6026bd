"""Where jax keeps its persistent compilation cache.

Entry points that compile (the experiments CLI, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`use_compile_cache` once, before their
first compile; importing this module does nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, and no other
  directory is configured here.
* otherwise: ``<checkout>/.jax_cache``, a fixed path, so a later run in
  the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os

__all__ = ["use_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
