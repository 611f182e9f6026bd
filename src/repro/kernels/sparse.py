"""Block-sparse semiring matmul: the tiled engine with empty tiles skipped.

Adjacency stacks on low-diameter topologies are extremely sparse — a
Slim Fly router talks to k' ~ 3q/2 of its 2q^2 peers, so fewer than 3%
of a padded (N, N) tile grid carries any edge at all.  The dense engine
in :mod:`repro.kernels.semiring` still streams every tile through the
MXU.  This variant takes the same operands plus a per-tile occupancy
bitmap (one int32 per (bm, bk) / (bk, bn) block) and predicates the
whole combine on ``a_occ & b_occ``: a tile pair where either side is
entirely the additive identity is skipped without reading it into the
MXU.

Skipping is *bit-exact*, not approximate: an all-identity block
contributes exactly the additive identity to the K reduction (0-blocks
add nothing to a counting product, +inf blocks never win a min), so the
output of the sparse kernel equals the dense kernel's output bitwise.
That identity-absorption argument is also why the CPU fast path under
the shared ``REPRO_KERNEL_BACKEND`` convention is simply the dense jnp
oracle (:func:`repro.kernels.ref.sparse_semiring_matmul_ref`): XLA's
native matmul is already the fastest way to absorb identity blocks on
CPU, and the frontier-APSP mode in :mod:`repro.core.paths` is where the
CPU-side sparsity win actually lives.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default, kernel_backend, ref
from .semiring import SAT, SEMIRINGS, _ZERO, combine_tile

__all__ = ["sparse_semiring_matmul", "tile_occupancy"]


def tile_occupancy(x: jnp.ndarray, bm: int, bk: int,
                   semiring: str = "count") -> jnp.ndarray:
    """Per-tile occupancy bitmap: ``occ[i, k] != 0`` iff block (i, k) of
    ``x`` holds any non-identity entry.  ``x`` must already be padded to
    tile multiples (the pad value is the additive identity, so pads never
    set a bit)."""
    m, k = x.shape
    assert m % bm == 0 and k % bk == 0, (x.shape, bm, bk)
    tiles = x.reshape(m // bm, bm, k // bk, bk)
    if semiring == "minplus":
        live = tiles < jnp.inf
    else:
        live = tiles != 0
    return live.any(axis=(1, 3)).astype(jnp.int32)


# -----------------------------------------------------------------------------
# The kernel: the dense semiring combine, gated on the occupancy product.
# -----------------------------------------------------------------------------
def _sparse_semiring_kernel(ao_ref, bo_ref, a_ref, b_ref, o_ref, *,
                            semiring: str, sat: float, bk: int, nk: int,
                            nn: int):
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _ZERO[semiring])

    # Occupancy bitmaps are scalar-prefetched into SMEM, flattened
    # row-major: a (1, 1) VMEM block per tile would violate the (8, 128)
    # block tiling.
    occupied = (ao_ref[i * nk + kk] != 0) & (bo_ref[kk * nn + j] != 0)

    @pl.when(occupied)
    def _combine():
        o_ref[...] = combine_tile(o_ref[...], a_ref[...], b_ref[...],
                                  semiring=semiring, sat=sat, bk=bk)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "bm", "bn", "bk", "sat",
                                    "interpret"))
def _pallas_sparse_matmul(a, b, *, semiring: str, bm: int, bn: int, bk: int,
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
    nm, nn, nk = mp // bm, np_ // bn, kp // bk
    a_occ = tile_occupancy(a_p, bm, bk, semiring).reshape(-1)
    b_occ = tile_occupancy(b_p, bk, bn, semiring).reshape(-1)

    out = pl.pallas_call(
        functools.partial(_sparse_semiring_kernel, semiring=semiring,
                          sat=sat, bk=bk, nk=nk, nn=nn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nm, nn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, ao, bo: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk, ao, bo: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda i, j, kk, ao, bo: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a_occ, b_occ, a_p, b_p)
    return out[:m, :n]


# -----------------------------------------------------------------------------
# Public dispatch — mirrors semiring_matmul.
# -----------------------------------------------------------------------------
def sparse_semiring_matmul(a: jnp.ndarray, b: jnp.ndarray,
                           semiring: str = "count", *, sat: float = SAT,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           backend: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Block-sparse semiring product ``A ⊗ B``; bit-identical to
    :func:`repro.kernels.semiring.semiring_matmul` on any input (empty
    tiles contribute exactly the additive identity).  Operands may carry
    one leading batch dim; ``backend=None`` follows the shared
    ``REPRO_KERNEL_BACKEND`` convention."""
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}; "
                         f"choose from {SEMIRINGS}")
    backend = backend or kernel_backend()
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose 'pallas' or 'ref'")
    if backend == "ref":
        return ref.sparse_semiring_matmul_ref(a, b, semiring, sat=sat)
    fn = functools.partial(_pallas_sparse_matmul, semiring=semiring, bm=bm,
                           bn=bn, bk=bk, sat=sat,
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
