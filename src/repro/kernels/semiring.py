"""Batched semiring matmul engine (paper Appendix B.1).

One tiled Pallas TPU kernel, parameterised over the three semirings the
path/layer pipeline uses:

* ``"count"``   — (min(·+·, SAT), ×) over f32: saturating walk counting
                  (Theorem 1).  Exact below 2**24; SAT is an absorbing
                  ceiling far above every diversity threshold.
* ``"bool"``    — (OR, AND): reachability products.  Implemented as the
                  count semiring saturated at 1.0 so the MXU still does
                  the work (bool matmul has no MXU path).
* ``"minplus"`` — (min, +) over f32 with +inf as the additive identity:
                  weighted shortest-path relaxation (the ``ksp`` layer
                  scheme).  No MXU mapping exists, so the kernel walks
                  the K tile with a VPU broadcast-min recurrence.

``semiring_matmul`` accepts 2-D operands or stacked (L, N, K) x (L, K, M)
batches — the batched form is what the layer-stack builder feeds it —
and dispatches between the Pallas kernel (TPU, or ``interpret=True`` for
testing) and the pure-jnp oracle in :mod:`repro.kernels.ref` (CPU: XLA's
native matmul is the fast path there).  The grid/tiling follows the
``pathcount`` reduction pattern: K innermost, output block revisited and
combined in place, which is semantics-preserving for all three semirings
because each combine is monotone and absorbing (SAT + x stays SAT;
min(inf, x) = x).

``pathcount_matmul`` in :mod:`repro.kernels.pathcount` is now a thin
wrapper over the ``"count"`` instance of this engine.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_default, kernel_backend, ref

__all__ = ["semiring_matmul", "SEMIRINGS", "SAT", "default_backend"]

SAT = 3.0e38

SEMIRINGS = ("count", "bool", "minplus")

# Additive identity per semiring — also the pad value for both operands
# (pads must be absorbed by the K reduction: 0-blocks add nothing to a
# counting product; +inf blocks never win a min).
_ZERO = {"count": 0.0, "bool": 0.0, "minplus": jnp.inf}


def default_backend() -> str:
    """``pallas`` on TPU, ``ref`` (jnp/XLA) elsewhere; override with
    ``REPRO_KERNEL_BACKEND=pallas|ref`` (the shared kernel-suite switch;
    ``REPRO_SEMIRING_BACKEND`` survives as a deprecated alias)."""
    return kernel_backend()


# -----------------------------------------------------------------------------
# The kernel.
# -----------------------------------------------------------------------------
def combine_tile(acc, a, b, *, semiring: str, sat: float, bk: int):
    """``acc ⊕ (a ⊗ b)`` for one (bm, bk) x (bk, bn) tile pair — the
    combine shared by the dense and block-sparse kernels."""
    if semiring in ("count", "bool"):
        ceil = 1.0 if semiring == "bool" else sat
        prod = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return jnp.minimum(acc + prod, ceil)
    # minplus: VPU broadcast-min over the K tile.  K is walked with
    # STATIC slices: a dynamic column slice ``a[:, k]`` is a dynamic
    # lane slice, which Mosaic cannot lower.
    for k in range(bk):
        acc = jnp.minimum(acc, a[:, k:k + 1] + b[k:k + 1, :])
    return acc


def _semiring_kernel(a_ref, b_ref, o_ref, *, semiring: str, sat: float,
                     bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _ZERO[semiring])

    o_ref[...] = combine_tile(o_ref[...], a_ref[...], b_ref[...],
                              semiring=semiring, sat=sat, bk=bk)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "bm", "bn", "bk", "sat",
                                    "interpret"))
def _pallas_matmul(a, b, *, semiring: str, bm: int, bn: int, bk: int,
                   sat: float, interpret: bool):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    zero = jnp.float32(_ZERO[semiring])
    a_p = jnp.full((mp, kp), zero).at[:m, :k].set(a.astype(jnp.float32))
    b_p = jnp.full((kp, np_), zero).at[:k, :n].set(b.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_semiring_kernel, semiring=semiring, sat=sat,
                          bk=bk),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


# -----------------------------------------------------------------------------
# Public dispatch.
# -----------------------------------------------------------------------------
def semiring_matmul(a: jnp.ndarray, b: jnp.ndarray, semiring: str = "count",
                    *, sat: float = SAT, bm: int = 128, bn: int = 128,
                    bk: int = 128, backend: Optional[str] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Semiring product ``A ⊗ B``; operands may carry one leading batch dim.

    ``bool`` accepts/returns bool arrays; ``count``/``minplus`` work in
    f32.  ``backend=None`` picks :func:`default_backend`.
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; "
                         f"choose from {SEMIRINGS}")
    backend = backend or default_backend()
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose 'pallas' or 'ref'")
    if backend == "ref":
        return ref.semiring_matmul_ref(a, b, semiring, sat=sat)
    fn = functools.partial(_pallas_matmul, semiring=semiring, bm=bm, bn=bn,
                           bk=bk, sat=sat,
                           interpret=interpret_default(interpret))
    if a.ndim == 3 or b.ndim == 3:
        if a.ndim == 2:
            a = jnp.broadcast_to(a[None], (b.shape[0],) + a.shape)
        if b.ndim == 2:
            b = jnp.broadcast_to(b[None], (a.shape[0],) + b.shape)
        out = jax.vmap(fn)(a, b)
    else:
        out = fn(a, b)
    if semiring == "bool":
        return out > 0.5
    return out
