"""The main path's kernels compile for a TPU v5e chip.

Nothing runs: each kernel is lowered and compiled for a *described*
``v5e:2x2`` topology by the TPU compiler installed next to jax, so a
kernel that only works in interpret mode (a block shape off the (8, 128)
tiling, a dynamic lane slice, more VMEM than a kernel may use) fails
here instead of on the chip.  The waterfill shapes are the sf(q=19)
permutation cells': 10,830 flows over 42,599 virtual links.  The
water-filling step is the link-sorted form, plain XLA with no kernel.

The topology is described inside a module-scoped fixture, never at
import, so collecting this module (every xdist worker does) loads no
TPU library; the tests themselves load it in the one process that runs
them.  Without the ``libtpu`` package the module skips; any other
failure to describe the chip fails the tests.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.semiring import semiring_matmul
from repro.kernels.sparse import sparse_semiring_matmul
from repro.kernels.waterfill import waterfill_step

SF19_FLOWS = 10_830          # sf(q=19) permutation: one flow per endpoint
SF19_LINKS = 42_599          # 20,938 fabric + 2 x 10,830 NIC + trash
SF19_FATPATHS_SLOTS = 9      # hop slots + injection + ejection NIC
SF19_ECMP_SLOTS = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("slots,want_util", [
    pytest.param(SF19_FATPATHS_SLOTS, False, id="False"),
    pytest.param(SF19_FATPATHS_SLOTS, True, id="True"),
    pytest.param(SF19_ECMP_SLOTS, False, id="ecmp-False"),
    pytest.param(SF19_ECMP_SLOTS, True, id="ecmp-True")])
def test_waterfill_compiles_at_sf19_permutation(one_chip, slots, want_util):
    """The FatPaths (9 slots) and ECMP (4 slots) cells' step, with and
    without the ECN lane: it compiles, and no kernel call is left."""
    f, e = SF19_FLOWS, SF19_LINKS

    def step(edges, w, desired, cap, active):
        return waterfill_step(edges, w, desired, cap, active=active,
                              backend="pallas", want_util=want_util)

    text = _compiled_text(step, ((f, slots), jnp.int32),
                          ((f,), jnp.float32), ((f,), jnp.float32),
                          ((e,), jnp.float32), ((f,), jnp.bool_),
                          sharding=one_chip)
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("kernel", [semiring_matmul, sparse_semiring_matmul],
                         ids=["semiring", "sparse"])
@pytest.mark.parametrize("semiring", ["count", "bool", "minplus"])
def test_semiring_kernels_compile(one_chip, kernel, semiring):
    # sf(q=19)'s router count: the blocked path engine's size class.
    n = 722
    dtype = jnp.bool_ if semiring == "bool" else jnp.float32

    def matmul(a, b):
        return kernel(a, b, semiring, backend="pallas", interpret=False)

    text = _compiled_text(matmul, ((n, n), dtype), ((n, n), dtype),
                          sharding=one_chip)
    assert "tpu_custom_call" in text
