"""Pallas TPU kernels for the paper's compute hot spots + substrate.

* ``semiring``        — batched semiring matmul engine (Appendix B.1):
                        bool OR/AND, saturating f32 counting, (min, +).
                        The whole path/layer pipeline routes through it.
* ``sparse``          — block-sparse variant of the semiring engine:
                        per-tile occupancy bitmaps skip empty blocks,
                        bit-identical to the dense kernel (empty tiles
                        contribute the additive identity exactly).
* ``waterfill``       — max-min water-filling transport step (§7.1.3):
                        the path-edge scatter, fair-share gather, hop-min
                        and every refinement iteration by link-sorted
                        segments in XLA (no Pallas kernel).
* ``pathcount``       — historical entry point, now the ``"count"``
                        instance of the semiring engine.
* ``gfmm``            — GF(p) modular matmul, Cheung connectivity (App. B.3).
* ``flash_attention`` — online-softmax attention (GQA/window/softcap), the
                        LM substrate's dominant kernel.

``ops`` holds the jit'd public wrappers; ``ref`` the pure-jnp oracles.
Validated with interpret=True on CPU; TPU (Mosaic) is the target.

Backend selection is shared across every kernel here: one
``REPRO_KERNEL_BACKEND`` env var (``pallas`` | ``ref``) overrides the
auto choice (pallas on TPU, the jnp oracle elsewhere, where XLA's native
ops beat an interpreted kernel).  ``REPRO_SEMIRING_BACKEND`` is kept as
a deprecated alias from when the semiring engine was the only dispatcher.
"""

import os
import warnings
from typing import Optional

__all__ = ["kernel_backend", "interpret_default", "flash_attention",
           "gf_matmul", "pathcount_matmul", "semiring_matmul",
           "sparse_semiring_matmul", "tile_occupancy",
           "waterfill_step", "ops", "ref"]

_BACKENDS = ("pallas", "ref")


def kernel_backend() -> str:
    """The backend every kernel dispatcher defaults to: ``pallas`` on
    TPU, ``ref`` (jnp/XLA) elsewhere; ``REPRO_KERNEL_BACKEND=pallas|ref``
    overrides (``REPRO_SEMIRING_BACKEND`` is honoured as a deprecated
    alias)."""
    env = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if env not in _BACKENDS:
        legacy = os.environ.get("REPRO_SEMIRING_BACKEND", "")
        if legacy in _BACKENDS:
            warnings.warn(
                "REPRO_SEMIRING_BACKEND is deprecated; it now selects the "
                "backend for ALL kernels — use REPRO_KERNEL_BACKEND",
                DeprecationWarning, stacklevel=2)
            env = legacy
    if env in _BACKENDS:
        return env
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def interpret_default(flag: Optional[bool]) -> bool:
    """Resolve an ``interpret=`` argument: explicit flag wins, then
    ``REPRO_PALLAS_INTERPRET=0|1``, else compile the Mosaic kernel on TPU
    and interpret elsewhere — the auto backend must never leave a TPU
    silently interpreting."""
    if flag is not None:
        return flag
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if env in ("0", "1"):
        return env == "1"
    import jax
    return jax.default_backend() != "tpu"


from . import ops, ref  # noqa: F401,E402
from .flash_attention import flash_attention  # noqa: F401,E402
from .gfmm import gf_matmul  # noqa: F401,E402
from .pathcount import pathcount_matmul  # noqa: F401,E402
from .semiring import semiring_matmul  # noqa: F401,E402
from .sparse import sparse_semiring_matmul, tile_occupancy  # noqa: F401,E402
from .waterfill import waterfill_step  # noqa: F401,E402
