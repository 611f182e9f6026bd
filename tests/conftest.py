"""Shared fixtures.  NOTE: no XLA_FLAGS here — unit tests run on the real
single CPU device; anything needing a multi-device mesh spawns a subprocess
(see test_collectives.py) so the dry-run's 512-device forcing never leaks
into this session."""

import numpy as np
import pytest


@pytest.fixture(scope="session", autouse=True)
def _no_persistent_compile_cache():
    """Keep jax's persistent compilation cache off for the whole session,
    even after a CLI entry point has placed it: a test must not read
    programs an earlier run compiled (the ``--cell-timeout-s`` tests
    rely on a cold cell being slower than their timeout)."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def sf5():
    from repro.core.topology import slim_fly
    return slim_fly(5)


@pytest.fixture(scope="session")
def df4():
    from repro.core.topology import dragonfly
    return dragonfly(4)


@pytest.fixture(scope="session")
def rt0():
    from repro.dist.sharding import Runtime
    return Runtime(mesh=None)
