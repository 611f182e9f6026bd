"""Public jit'd wrappers around the Pallas kernels.

``interpret=None`` resolves through :func:`repro.kernels.interpret_default`:
compiled Mosaic kernels on TPU, interpret mode elsewhere.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import interpret_default
from .flash_attention import flash_attention
from .gfmm import GF_P_F32, GF_P_INT32, gf_matmul
from .pathcount import SAT, pathcount_matmul

__all__ = ["path_counts_power", "gf_power_sum", "attention", "SAT",
           "GF_P_INT32", "GF_P_F32"]


def path_counts_power(adj: jnp.ndarray, l: int, *,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """A^l walk counts via the pathcount kernel (Theorem 1)."""
    a = adj.astype(jnp.float32)
    out = a
    for _ in range(l - 1):
        out = pathcount_matmul(out, a,
                               interpret=interpret_default(interpret))
    return out


def gf_power_sum(k_mat: jnp.ndarray, l: int, p: int = GF_P_INT32,
                 mode: str = "int32", *,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """sum_{i=0}^{l-1} K^i mod p via Horner (M <- M K + I), the Cheung
    connectivity propagation matrix (Appendix B.3)."""
    e = k_mat.shape[0]
    eye = jnp.eye(e, dtype=jnp.int32)
    m = eye
    for _ in range(l - 1):
        m = gf_matmul(m, k_mat, p=p, mode=mode,
                      interpret=interpret_default(interpret))
        m = (m + eye) % p
    return m


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              softcap: float = 0.0, scale: Optional[float] = None,
              bq: int = 128, bk: int = 128,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention (GQA/causal/window/softcap); see kernel docstring."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale, bq=bq, bk=bk,
                           interpret=interpret_default(interpret))
