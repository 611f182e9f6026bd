"""Fused max-min water-filling transport step (paper §7.1.3).

The flow-level simulator's per-step inner loop is a scatter/gather
ping-pong over virtual links: scatter flow weights to count link
claimants, gather each link's fair share back, take the per-flow min
across hop slots, then repeat ``fair_iters`` times with the provisional
demands to keep every link feasible.  Expressed in jnp that is
``2 * (1 + fair_iters)`` scatter/gather dispatches per simulated step —
the dominant cost of every transport sweep cell after the path engine
(PR 3) moved path derivation out of the scan.

This module fuses the WHOLE step into one tiled Pallas kernel over the
``(F, S)`` path-edge layout (S = hop slots + injection + ejection NIC):

* grid ``(1 + fair_iters, 2, F_tiles)`` — rounds x {scatter, reduce}
  phases x flow tiles, executed sequentially on a TPU core; ALL state
  that crosses rounds or flow tiles (link loads, provisional per-flow
  demands, fair shares) lives in VMEM scratch, because the output
  blocks are revisited at non-consecutive grid iterations and are
  therefore write-only (each visit writes the scratch state; the final
  sweep's write-back is the refined result);
* the scatter phase accumulates per-link claims through a one-hot
  compare against a lane iota, tile by tile over the link axis (the
  standard MXU/VPU scatter-as-matmul layout — no serialized scatter);
* the reduce phase re-reads the accumulated loads, forms fair shares
  (round 0) or feasibility scales (later rounds), gathers them back
  through the same one-hot tiles and takes the masked min across hop
  slots — the trash link (id ``e_tot - 1``) never enters a min;
* round 0 writes the fair-share signal (``share``, the congestion
  feedback) and the provisional demand; later rounds refine the demand
  in place (``sent``).

The jnp oracle (:func:`repro.kernels.ref.waterfill_ref`) is the CPU
fast path — XLA's native scatter beats an interpreted kernel — and the
backend convention matches :mod:`repro.kernels.semiring`:
auto (``pallas`` on TPU, ``ref`` elsewhere), overridable via
``REPRO_KERNEL_BACKEND`` or an explicit ``backend=`` argument.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default, kernel_backend, ref

__all__ = ["waterfill_step"]

# Scoped VMEM the kernel may use.  The (fp, 1) f32 per-flow scratch pads
# every row to 128 lanes, so the ECN lane's third scratch alone overflows
# Mosaic's 16 MiB default at sf(q=19) permutation (F=10,830); a v5e core
# has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 32 * 2**20


def _waterfill_kernel(edges_ref, w_ref, desired_ref, active_ref, cap_ref,
                      *refs, e_tot: int, be: int, n_e_tiles: int, bf: int,
                      want_util: bool, util_round: int):
    # The ECN lane (PR 8) adds one output (per-flow worst link demand
    # utilization, read off the ``util_round`` scatter — the first
    # demand refinement round, whose loads are the provisional demands)
    # and one VMEM scratch; ``want_util`` is a TRACE-TIME flag, so the
    # False program is structurally identical to the pre-lane kernel.
    if want_util:
        (sent_ref, share_ref, util_ref, load_ref, d_ref, adv_ref,
         u_ref) = refs
    else:
        sent_ref, share_ref, load_ref, d_ref, adv_ref = refs
    r = pl.program_id(0)          # water-filling round (0 = fair share)
    p = pl.program_id(1)          # 0 = scatter loads, 1 = reduce per flow
    t = pl.program_id(2)          # flow tile
    # Dynamic-traffic active lane, fused into the step: inactive rows
    # (and -1 walk-padding slots) collapse to the write-only trash link,
    # and their weight/desire is zeroed — the same masking transport
    # callers used to materialise host-side, now free inside the kernel.
    act = active_ref[...] > 0.0                              # (bf, 1) bool
    actf = act.astype(jnp.float32)
    edges = edges_ref[...]                                   # (bf, S) int32
    edges = jnp.where(act & (edges >= 0), edges, e_tot - 1)
    _, s = edges.shape
    # ALL cross-round/cross-tile state lives in VMEM scratch (load_ref:
    # link loads; d_ref/adv_ref: per-flow demand and fair share).  The
    # output blocks are revisited at every (r, p) — NON-consecutive grid
    # iterations — so they are write-only and written on every visit;
    # only the final sweep's values survive the last write-back, which is
    # exactly the refined result.  (Reading an output block back after a
    # non-consecutive revisit is undefined on compiled Mosaic.)
    rows = pl.ds(t * bf, bf)

    @pl.when(p == 0)
    def _scatter():
        @pl.when(t == 0)
        def _reset():
            load_ref[...] = jnp.zeros_like(load_ref)

        # Round 0 claims with the flow weight; later rounds re-scatter the
        # provisional demand scratch (written by round r-1's reduce phase).
        val = jnp.where(r == 0, w_ref[...] * actf, d_ref[rows])  # (bf, 1)

        def etile(ei, _):
            ids = ei * be + jax.lax.broadcasted_iota(jnp.int32, (1, 1, be), 2)
            onehot = edges[:, :, None] == ids                # (bf, S, be)
            contrib = jnp.sum(jnp.where(onehot, val[:, 0:1, None], 0.0),
                              axis=(0, 1))[None, :]          # (1, be)
            load_ref[:, pl.ds(ei * be, be)] = (
                load_ref[:, pl.ds(ei * be, be)] + contrib)
            return 0

        jax.lax.fori_loop(0, n_e_tiles, etile, 0)

    @pl.when(p == 1)
    def _reduce():
        def etile(ei, acc):
            ids = ei * be + jax.lax.broadcasted_iota(jnp.int32, (1, 1, be), 2)
            onehot = edges[:, :, None] == ids                # (bf, S, be)
            cap_t = cap_ref[:, pl.ds(ei * be, be)]           # (1, be)
            load_t = load_ref[:, pl.ds(ei * be, be)]
            per_link = cap_t / jnp.maximum(load_t, 1e-9)     # fair (round 0)
            per_link = jnp.where(r == 0, per_link,
                                 jnp.minimum(1.0, per_link))  # scale (r > 0)
            # Each edge id hits exactly one link tile, so summing the
            # masked broadcasts across tiles IS the gather.
            if want_util:
                acc, acc_u = acc
                # Accumulated every round, but only the ``util_round``
                # value is consumed (u_ref is written under that round).
                per_util = load_t / jnp.maximum(cap_t, 1e-9)
                acc_u = acc_u + jnp.sum(
                    jnp.where(onehot, per_util[0][None, None, :], 0.0),
                    axis=2)
                return (acc + jnp.sum(
                    jnp.where(onehot, per_link[0][None, None, :], 0.0),
                    axis=2), acc_u)
            return acc + jnp.sum(
                jnp.where(onehot, per_link[0][None, None, :], 0.0), axis=2)

        acc0 = jnp.zeros((bf, s), jnp.float32)
        g = jax.lax.fori_loop(0, n_e_tiles, etile,
                              (acc0, acc0) if want_util else acc0)  # (bf, S)
        if want_util:
            g, g_util = g
        live = edges < e_tot - 1                  # trash never enters a min
        m = jnp.min(jnp.where(live, g, jnp.inf), axis=1, keepdims=True)

        @pl.when(r == 0)
        def _round0():
            adv_ref[rows] = m
            d_ref[rows] = jnp.minimum(desired_ref[...] * actf, m)

        @pl.when(r > 0)
        def _refine():
            d_ref[rows] = d_ref[rows] * jnp.where(jnp.isfinite(m), m, 0.0)

        if want_util:
            @pl.when(r == util_round)
            def _util():
                u_ref[rows] = jnp.max(jnp.where(live, g_util, 0.0),
                                      axis=1, keepdims=True)

        sent_ref[...] = d_ref[rows]
        share_ref[...] = adv_ref[rows]
        if want_util:
            util_ref[...] = u_ref[rows]


@functools.partial(jax.jit, static_argnames=("e_tot", "fair_iters", "bf",
                                             "be", "interpret", "want_util"))
def _pallas_waterfill(edges, w, desired, active, cap, *, e_tot: int,
                      fair_iters: int, bf: int, be: int, interpret: bool,
                      want_util: bool = False):
    f, s = edges.shape
    fp = -(-max(f, 1) // bf) * bf
    ep = -(-e_tot // be) * be
    # Flow padding: inactive rows (the kernel's active lane maps their
    # edges to trash and zeroes weight/desire) = an exact no-op on every
    # link sum and every min.  Link padding: capacity 1, no edge id ever
    # points past e_tot - 1.
    edges_p = jnp.full((fp, s), e_tot - 1, jnp.int32).at[:f].set(
        edges.astype(jnp.int32))
    w_p = jnp.zeros((fp, 1), jnp.float32).at[:f, 0].set(
        w.astype(jnp.float32))
    d_p = jnp.zeros((fp, 1), jnp.float32).at[:f, 0].set(
        desired.astype(jnp.float32))
    act_p = jnp.zeros((fp, 1), jnp.float32).at[:f, 0].set(
        active.astype(jnp.float32))
    cap_p = jnp.ones((1, ep), jnp.float32).at[0, :e_tot].set(
        cap.astype(jnp.float32))

    flow_tile = lambda r, p, t: (t, 0)      # noqa: E731
    n_out = 3 if want_util else 2
    out = pl.pallas_call(
        functools.partial(_waterfill_kernel, e_tot=e_tot, be=be,
                          n_e_tiles=ep // be, bf=bf, want_util=want_util,
                          util_round=min(1, fair_iters)),
        grid=(1 + fair_iters, 2, fp // bf),
        in_specs=[
            pl.BlockSpec((bf, s), flow_tile),
            pl.BlockSpec((bf, 1), flow_tile),
            pl.BlockSpec((bf, 1), flow_tile),
            pl.BlockSpec((bf, 1), flow_tile),
            pl.BlockSpec((1, ep), lambda r, p, t: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((bf, 1), flow_tile)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((fp, 1), jnp.float32)] * n_out,
        scratch_shapes=[pltpu.VMEM((1, ep), jnp.float32),
                        pltpu.VMEM((fp, 1), jnp.float32),
                        pltpu.VMEM((fp, 1), jnp.float32)]
        + ([pltpu.VMEM((fp, 1), jnp.float32)] if want_util else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="waterfill",
    )(edges_p, w_p, d_p, act_p, cap_p)
    return tuple(o[:f, 0] for o in out)


def waterfill_step(edges: jnp.ndarray, w: jnp.ndarray, desired: jnp.ndarray,
                   cap: jnp.ndarray, *, active: Optional[jnp.ndarray] = None,
                   fair_iters: int = 2, backend: Optional[str] = None,
                   interpret: Optional[bool] = None, bf: int = 128,
                   be: int = 512,
                   want_util: bool = False) -> Tuple[jnp.ndarray, ...]:
    """One fused water-filling step: ``(sent, share)`` per flow.

    ``edges`` is the (F, S) virtual-link layout (S = hop slots + NIC
    slots; id ``cap.shape[0] - 1`` is the write-only trash slot), ``w``
    the 0/1 flow weights, ``desired`` the requested rates and ``cap``
    the link capacities, all in line-rate units.  ``active`` is the
    optional (F,) dynamic-traffic mask: inactive rows are masked to the
    trash slot INSIDE the step (their share comes back +inf), so callers
    with arrival/departure lanes pass raw path edges (which may contain
    -1 padding) plus the mask instead of materialising a masked edge
    tensor per step.  ``want_util=True`` (the ECN lane) returns
    ``(sent, share, util)`` where ``util`` is each flow's worst link
    demand utilization (first-refinement load over capacity) — the
    trace-time flag compiles an extra output in both backends, and
    False compiles the exact pre-lane program.
    ``backend=None`` picks :func:`repro.kernels.kernel_backend`;
    semantics are defined by :func:`repro.kernels.ref.waterfill_ref`.
    """
    backend = backend or kernel_backend()
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose 'pallas' or 'ref'")
    if backend == "ref":
        return ref.waterfill_ref(edges, w, desired, cap,
                                 fair_iters=fair_iters, active=active,
                                 want_util=want_util)
    act = (jnp.ones(edges.shape[0], jnp.float32) if active is None
           else active.astype(jnp.float32))
    return _pallas_waterfill(edges, w, desired, act, cap,
                             e_tot=int(cap.shape[0]),
                             fair_iters=int(fair_iters), bf=bf, be=be,
                             interpret=interpret_default(interpret),
                             want_util=want_util)
