"""The harness places jax's persistent compilation cache in the
checkout; tests keep it off so that they neither read nor write it."""

import pytest


@pytest.fixture(scope="session", autouse=True)
def _no_persistent_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
