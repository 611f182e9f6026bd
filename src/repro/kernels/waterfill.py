"""Max-min water-filling transport step (paper §7.1.3).

The flow-level simulator's per-step inner loop is a scatter/gather
ping-pong over virtual links: scatter flow weights to count link
claimants, gather each link's fair share back, take the per-flow min
across hop slots, then repeat ``fair_iters`` times with the provisional
demands to keep every link feasible.  Expressed in jnp that is
``2 * (1 + fair_iters)`` scatter/gather dispatches per simulated step —
the dominant cost of every transport sweep cell after the path engine
(PR 3) moved path derivation out of the scan.

On the TPU the step runs by link-sorted segments
(:func:`_sorted_waterfill`, plain XLA).  It sorts the step's F·S link
ids once, together with one row per link carrying its capacity, so
every link is one contiguous segment.  Scatter passes are segmented
sums over that order (doubling steps over the longest segment); reduce
passes form each link's value at every entry and sort it back to slot
order.  Its work per pass is F·S + E.  It uses sorts where a gather or
a scatter would apply elements one by one on a TPU.  At ``sf(q=19)``
(10,830 flows, 42,599 virtual links) a step takes 1.58 ms on a v5e,
against 4.59 ms for the XLA oracle and 67.2 ms for the fused one-hot
Pallas kernel this form replaced, whose F·S·E compares per pass grew
with the fabric.

The jnp oracle (:func:`repro.kernels.ref.waterfill_ref`) is the CPU
fast path — XLA's native scatter — and the backend convention matches
:mod:`repro.kernels.semiring`: auto (``pallas`` on TPU, ``ref``
elsewhere), overridable via ``REPRO_KERNEL_BACKEND`` or an explicit
``backend=`` argument.  The ``pallas`` backend runs the link-sorted
form.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import kernel_backend, ref

__all__ = ["waterfill_step", "waterfill_path"]


def _sort(key, payload, *, bound: int, stable: bool):
    """``lax.sort`` of the 1-D ``(key, *payload)`` by ``key``, whose
    values lie in ``[0, bound)``.  Under ``vmap`` the batch index is
    folded into the key (row ``b`` sorts ``b·bound + key``), so a batch
    of sorts stays ONE 1-D sort: a TPU sorts a (B, n) array along its
    rows several times slower than the (B·n,) array."""

    @jax.custom_batching.custom_vmap
    def srt(key, payload):
        return jax.lax.sort((key, *payload), num_keys=1, is_stable=stable)

    @srt.def_vmap
    def _rows(axis_size, in_batched, key, payload):
        key, payload = jax.tree.map(
            lambda x, b: x if b else jnp.broadcast_to(
                x, (axis_size,) + x.shape), (key, payload), tuple(in_batched))
        if axis_size * bound >= 2**31:        # the folded key overflows
            out = jax.lax.sort((key, *payload), num_keys=1,
                               is_stable=stable)
            return out, (True,) * len(out)
        n = key.shape[1]
        fold = (jnp.arange(axis_size, dtype=key.dtype) * bound)[:, None]
        out = _sort((key + fold).reshape(-1),
                    tuple(x.reshape(-1) for x in payload),
                    bound=axis_size * bound, stable=stable)
        out = [x.reshape(axis_size, n) for x in out]
        out[0] = out[0] - fold
        return tuple(out), (True,) * len(out)

    return srt(key, tuple(payload))


def _sorted_waterfill(edges, w, desired, active, cap, *, fair_iters: int,
                      want_util: bool):
    """The water-filling step by link-sorted segments (jnp/XLA).

    The step's F·S slots and one row per link (carrying its capacity)
    are sorted once by link, each link's row first, so every link is one
    contiguous segment.  A scatter pass is a segmented sum over that
    order; a reduce pass forms the per-link value at every entry and
    sorts it back to slot order.  No gather and no scatter: on a TPU
    both apply their elements nearly one by one, where a sort moves the
    whole array.  F·S + E work per pass.  Same semantics as
    :func:`repro.kernels.ref.waterfill_ref`, f32 throughout; only the
    summation order of a link's load differs."""
    f, s = edges.shape
    e_tot = cap.shape[0]
    n = f * s
    big = n + e_tot
    trash = e_tot - 1
    act = active > 0.0
    actf = act.astype(jnp.float32)
    ids = jnp.where(act[:, None] & (edges >= 0), edges, trash)
    live = ids < trash                        # trash never enters a min
    pad = jnp.zeros(e_tot, jnp.float32)

    def column(v):                            # per-flow value per slot
        return jnp.concatenate([jnp.broadcast_to(
            v.astype(jnp.float32)[:, None], (f, s)).reshape(n), pad])

    # Sort key 2·link + 1 for a slot, 2·link for the link's own row.
    links = jnp.arange(e_tot, dtype=jnp.int32)
    key0 = jnp.concatenate([2 * ids.reshape(n).astype(jnp.int32) + 1,
                            2 * links])
    here = jnp.arange(big, dtype=jnp.int32)
    key, perm, cap_col, w_k = _sort(
        key0, (here, jnp.concatenate([jnp.zeros(n, jnp.float32),
                                      cap.astype(jnp.float32)]),
               column(w * actf)), bound=2 * e_tot, stable=True)
    inv = _sort(perm, (here,), bound=big, stable=False)[1]

    # Distance of each sorted entry from its link segment's first and
    # last entry.  Doubling steps cover the longest segment but the
    # trash link's (its totals are never read): steps past a segment's
    # length add 0.0 or copy nothing, so the totals do not depend on
    # how far the loop runs.
    link = key // 2
    new = link[1:] != link[:-1]
    first = jnp.concatenate([jnp.ones(1, bool), new])
    last = jnp.concatenate([new, jnp.ones(1, bool)])
    pos = here - jax.lax.cummax(jnp.where(first, here, 0))
    rem = jax.lax.cummin(jnp.where(last, here, big - 1), reverse=True) - here
    longest = jnp.max(jnp.where(link < trash, pos, 0))
    n_steps = 32 - jax.lax.clz(longest)       # bit length of longest
    zeros = jnp.zeros(big, jnp.float32)

    def seg_sum(j, x):                        # segmented inclusive sum
        sh = jnp.left_shift(1, j)
        prev = jax.lax.dynamic_slice(jnp.concatenate([zeros, x]),
                                     (big - sh,), (big,))
        return x + jnp.where(pos >= sh, prev, 0.0)

    def seg_last(j, x):                       # the segment's last, back
        sh = jnp.left_shift(1, j)
        nxt = jax.lax.dynamic_slice(jnp.concatenate([x, zeros]), (sh,),
                                    (big,))
        return jnp.where((rem & sh) != 0, nxt, x)

    def link_total(x):
        """Sorted values -> each entry's link total."""
        x = jax.lax.fori_loop(0, n_steps, seg_sum, x)
        return jax.lax.fori_loop(0, n_steps, seg_last, x)

    def to_slots(*xs):                        # sorted -> (F, S) order
        out = _sort(perm, xs, bound=big, stable=False)[1:]
        return [x[:n].reshape(f, s) for x in out]

    def slot_min(x):
        return jnp.min(jnp.where(live, x, jnp.inf), axis=1)

    def slot_max(x):
        return jnp.max(jnp.where(live, x, 0.0), axis=1)

    # The link's own row holds its capacity, every other entry 0.0: the
    # segmented sum gives each entry its link's capacity exactly.
    cap_k = jax.lax.fori_loop(0, n_steps, seg_sum, cap_col)
    count = link_total(w_k)
    fair, = to_slots(cap_k / jnp.maximum(count, 1e-9))
    share = slot_min(fair)
    util = None
    if want_util and fair_iters == 0:
        util = slot_max(to_slots(count / jnp.maximum(cap_k, 1e-9))[0])
    d = jnp.minimum(desired.astype(jnp.float32) * actf, share)
    for it in range(fair_iters):
        load = link_total(_sort(inv, (column(d),), bound=big,
                                stable=False)[1])
        scale = jnp.minimum(1.0, cap_k / jnp.maximum(load, 1e-9))
        if want_util and it == 0:
            scale, u = to_slots(scale, load / jnp.maximum(cap_k, 1e-9))
            util = slot_max(u)
        else:
            scale, = to_slots(scale)
        m = slot_min(scale)
        d = d * jnp.where(jnp.isfinite(m), m, 0.0)
    return (d, share, util) if want_util else (d, share)


def waterfill_path(backend: Optional[str] = None) -> str:
    """Which implementation :func:`waterfill_step` runs on ``backend``:
    ``"ref"`` (the jnp oracle) or ``"sorted"``
    (:func:`_sorted_waterfill`, the ``pallas`` backend)."""
    backend = backend or kernel_backend()
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown backend {backend!r}; "
                         "choose 'pallas' or 'ref'")
    return "sorted" if backend == "pallas" else "ref"


def waterfill_step(edges: jnp.ndarray, w: jnp.ndarray, desired: jnp.ndarray,
                   cap: jnp.ndarray, *, active: Optional[jnp.ndarray] = None,
                   fair_iters: int = 2, backend: Optional[str] = None,
                   want_util: bool = False) -> Tuple[jnp.ndarray, ...]:
    """One water-filling step: ``(sent, share)`` per flow.

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
    if waterfill_path(backend) == "ref":
        return ref.waterfill_ref(edges, w, desired, cap,
                                 fair_iters=fair_iters, active=active,
                                 want_util=want_util)
    act = (jnp.ones(edges.shape[0], jnp.float32) if active is None
           else active.astype(jnp.float32))
    return _sorted_waterfill(edges, w, desired, act, cap,
                             fair_iters=int(fair_iters), want_util=want_util)
