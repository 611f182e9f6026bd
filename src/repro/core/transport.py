"""Flow-level transport + load-balancing simulator (paper §7, htsim analogue).

A vectorised discrete-time simulator written as a single ``jax.lax.scan``:
all flows advance simultaneously in Δt steps; link sharing is an iterative
max-min water-filling approximation that never oversubscribes a link.

Modelled per paper §3 / §7.1.3:

* **Transport** —
  - ``ndp``  ("purified"): senders start at line rate; per-step rate equals
    the receiver-driven fair share (trimming => no timeouts, headers always
    arrive).
  - ``tcp``: slow start from a small window, AIMD (halve on congestion),
    additive increase otherwise.
  - ``dctcp``: like tcp but gentle multiplicative decrease (ECN-style).
* **Load balancing** —
  - ``ecmp``: flow hashes onto one of ``n_ecmp`` minimal-path forwarding
    tables at start; never re-routes.
  - ``letflow``: flowlet re-routing among the minimal tables only.
  - ``fatpaths``: flowlet re-routing across FatPaths layers (minimal +
    non-minimal); layer choice uniform among layers that can route (s, t)
    (fallback guarantees layer 0 always can).
* **Flowlet elasticity** — the probability that a flowlet gap occurs in a
  step grows as the flow's achieved rate falls:
  ``p_gap = dt/gap * (1 - rate/line + eps)`` — slow (congested) flows
  re-roll paths often, fast flows stick (paper §3.2).

Endpoint NICs are modelled as virtual links (injection + ejection), so
incast (all-to-one) and concentration effects are captured.

Execution structure (PR 5):

* **Fused water-filling step** — the per-step scatter/gather/min inner
  loop is one :func:`repro.kernels.waterfill.waterfill_step` call: on
  TPU the link-sorted form, the jnp oracle on CPU
  (``SimConfig.kernel_backend`` / ``REPRO_KERNEL_BACKEND`` override).
* **PRNG derivation** — per-flow keys ``fold_in(key, flow)`` are hoisted
  out of the step body; step draws come from
  ``uniform(fold_in(flow_key, chunk), (horizon_chunk, 2))[step_in_chunk]``
  so one bulk generation per chunk replaces the per-step vmapped
  ``fold_in`` pair.  Row ``i``'s draws still depend only on
  ``(key, i, step)`` — the padding-safety property the distributed sweep
  engine's bit-identity guarantee rests on — but the draws themselves
  differ from the pre-PR5 stream (and change if ``horizon_chunk``
  changes), so any seed-sensitive baseline re-baselines with this PR.
* **Adaptive horizon** — the scan runs as a ``lax.while_loop`` over
  fixed-size chunks of ``horizon_chunk`` steps that stops as soon as
  every flow is finished or provably stuck (weight 0 forever: no layer
  it can ever pick routes it).  Skipped steps are exact no-ops on every
  result-bearing state component, so early exit returns results
  bit-identical to the full-horizon run; cells whose flows stay active
  (slow but routable) run the full ``n_steps``.

Dynamic traffic (PR 6) — the open-loop lane:

* ``arrs["active_at"]`` is a per-flow activation *step* (int32 operand,
  from :attr:`FlowWorkload.active_step`, built by
  :mod:`repro.core.arrivals`): a flow participates only once
  ``step >= active_at`` AND ``start <= t`` — with ``active_at = 0``
  (the default for every static workload) the predicate reduces bitwise
  to the old closed-loop one, so a dynamic cell whose activations are
  all zero reproduces the static-batch result exactly;
* ``state["depart_step"]`` records the step at which each flow finished
  (-1 while in flight) — the departure half of the unrolled
  flow-slot ring buffer (see :mod:`repro.core.arrivals`);
* the adaptive horizon needs no extra predicate for pending arrivals: a
  not-yet-active flow keeps ``remaining > 0``, and it is counted stuck
  only if no pickable layer can EVER route it — in which case it sends
  nothing after arriving either, so skipping it stays an exact no-op;
* the masking of inactive flows' edges to the trash link moved INTO the
  fused water-filling kernel (the ``active`` lane of
  :func:`repro.kernels.waterfill.waterfill_step`), value-identical to
  the host-side select it replaces — inactive flows still see share
  = +inf (an uncongested network), which the tcp/dctcp ramp relies on.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..kernels import kernel_backend
from ..kernels.waterfill import waterfill_path, waterfill_step
from .layers import LayeredRouting
from .topology import Topology
from .traffic import FlowWorkload

__all__ = ["SimConfig", "SimResult", "simulate", "simulate_seeds",
           "ecmp_routing", "prepare", "pad_prepared", "batch_result",
           "shape_signature"]


@dataclasses.dataclass(frozen=True)
class SimConfig:
    transport: str = "ndp"          # ndp | tcp | dctcp
    balancing: str = "fatpaths"     # ecmp | letflow | fatpaths
    dt: float = 10e-6               # seconds per step
    n_steps: int = 2000
    line_rate: float = 12.5e9       # bytes/s (100 GbE)
    link_latency: float = 1e-6      # per hop (INET-matched fixed delay)
    sw_latency: float = 10e-6       # endpoint software stack latency
    flowlet_gap: float = 50e-6      # LetFlow-style gap timescale
    gap_eps: float = 0.05           # baseline re-roll probability factor
    max_hops: int = 12
    fair_iters: int = 2             # water-filling refinement iterations
    tcp_init: float = 0.05          # initial rate fraction (slow start)
    tcp_ai: float = 0.02            # additive increase per step (frac of line)
    tcp_md: float = 0.5             # multiplicative decrease (tcp)
    dctcp_md: float = 0.85          # gentle decrease (dctcp)
    horizon_chunk: int = 64         # scan chunk size (also the PRNG block)
    adaptive_horizon: bool = True   # stop once all flows are done/stuck
    kernel_backend: str = ""        # "" = auto | "pallas" | "ref"
    # --- loss-recovery lanes (PR 8) -------------------------------------
    # recovery="off" (default) compiles the exact pre-PR-8 program —
    # every recovery lane is trace-time gated, so legacy cells reproduce
    # their results bit-for-bit.  recovery="on" adds: a per-flow stall
    # timer, a retransmission-timeout state machine with exponential
    # backoff (deterministic blackhole escape onto the next usable
    # surviving layer), lost-in-flight rollback on mid-run link death
    # (ndp pays one trimmed-RTT, tcp a full RTO stall + slow-start
    # re-entry, dctcp in between), and link-load ECN marking as the
    # dctcp congestion signal.
    recovery: str = "off"           # off | on
    rto_base: int = 16              # initial retransmission timeout (steps)
    rto_cap: int = 256              # exponential-backoff ceiling (steps)
    ecn_thresh: float = 0.65        # link claim-utilization ECN mark point
    # record=1 additionally materialises per-step aggregate lanes
    # (goodput, stalled-flow count) for the recovery evaluator's
    # time-to-recover curves; off for every batched sweep cell.
    record: int = 0
    seed: int = 0


@dataclasses.dataclass
class SimResult:
    fct: np.ndarray            # (F,) seconds; NaN if unfinished
    delivered: np.ndarray      # (F,) bytes delivered
    size: np.ndarray           # (F,) flow sizes
    finished: np.ndarray       # (F,) bool
    link_util_mean: float
    config: SimConfig
    # (F,) step index at which each flow completed; -1 = still in flight
    # at the horizon (the departure lane of the dynamic-traffic ring).
    depart_step: Optional[np.ndarray] = None
    # Recovery lanes (PR 8; None unless cfg.recovery/record enabled):
    # per-flow retransmitted bytes, and the per-step aggregate goodput
    # (line units) / stalled-flow-count curves the recovery evaluator
    # turns into time-to-recover metrics.
    retrans_bytes: Optional[np.ndarray] = None
    goodput_steps: Optional[np.ndarray] = None
    stalled_steps: Optional[np.ndarray] = None
    # Scan chunks the adaptive horizon executed (execution bookkeeping,
    # never a result: early exit is bit-identical to the full horizon).
    chunks: int = 0

    @property
    def throughput_per_flow(self) -> np.ndarray:
        return np.where(self.finished, self.size / np.maximum(self.fct, 1e-12),
                        np.nan)

    def fct_stats(self) -> Dict[str, float]:
        ok = self.finished
        f = self.fct[ok]
        if len(f) == 0:
            return {"mean": float("nan"), "p50": float("nan"),
                    "p99": float("nan"), "finished": 0.0}
        return {
            "mean": float(f.mean()),
            "p50": float(np.quantile(f, 0.50)),
            "p99": float(np.quantile(f, 0.99)),
            "finished": float(ok.mean()),
        }


def ecmp_routing(topo: Topology, n_tables: int = 8, seed: int = 0,
                 max_len: Optional[int] = None) -> LayeredRouting:
    """Minimal-path-only multi-table routing: n differently tie-broken
    shortest-path tables (flow-hash ECMP / LetFlow substrate).  All n
    tables come out of one batched forwarding program (APSP is shared:
    every table sees the same full-graph distances).  ``build_stats``
    splits the build's wall time as :func:`repro.core.layers.build_layers`
    does."""
    from . import paths as paths_mod

    adj = np.asarray(topo.adj, dtype=bool)
    if max_len is None:
        max_len = max(6, topo.diameter_nominal + 2)
    with tracing.record() as rec:
        engine = paths_mod.path_engine(adj.shape[0])
        nbr = jnp.asarray(paths_mod.neighbor_table(adj))
        stack = jnp.asarray(np.broadcast_to(adj[None],
                                            (n_tables,) + adj.shape))
        with tracing.span("stack.device"):
            dist_j = paths_mod.apsp_batched(jnp.asarray(adj)[None],
                                            max_l=max_len)[0]
            nh = paths_mod._forwarding_program(
                stack, jnp.broadcast_to(dist_j[None], stack.shape), nbr,
                jax.random.PRNGKey(seed), engine)
            nh = np.asarray(jax.block_until_ready(nh)).copy()
        dist = np.asarray(dist_j)
        reach = dist <= max_len
        nh[:, ~reach] = -1
        idx = np.arange(adj.shape[0])
        nh[:, idx, idx] = idx
        plen = np.where(reach, dist, 10_000).astype(np.int16)
        compressed = None
        if paths_mod.representation_for(adj.shape[0]) == "compressed":
            with tracing.span("stack.compress"):
                compressed = paths_mod.CompressedTables.from_dense(nh)
    device_s = rec.seconds("stack.device")
    return LayeredRouting(
        topo=topo, scheme="ecmp", rho=1.0,
        nh=nh, reach=np.stack([reach] * n_tables),
        pathlen=np.stack([plen] * n_tables),
        layer_adj=np.stack([adj] * n_tables),
        build_stats={"total_s": rec.wall_s, "device_s": device_s,
                     "host_s": rec.wall_s - device_s},
        compressed=compressed,
    )


@functools.partial(jax.jit, static_argnames=("max_hops",))
def _path_edge_tensor(nh, eix, src_r, dst_r, max_hops):
    """Walk every layer's table once, ahead of the scan: (L, F, max_hops)
    directed fabric edge ids along each flow's path in each layer (-1
    padding once the destination router is reached) plus an (L, F)
    routed-ok mask.  The per-step scan work then collapses from
    ``max_hops`` sequential gathers to ONE gather by current layer."""

    def one_layer(nh_l):
        def hop(cur, _):
            nxt = nh_l[cur, dst_r]
            at_dst = cur == dst_r
            hole = nxt < 0
            e = jnp.where(at_dst | hole, -1,
                          eix[cur, jnp.where(hole, cur, nxt)])
            return jnp.where(at_dst | hole, cur, nxt), e
        cur, es = jax.lax.scan(hop, src_r, None, length=max_hops)
        return es.T, cur == dst_r                      # (F, H), (F,)

    return jax.vmap(one_layer)(nh)


@functools.partial(jax.jit, static_argnames=("max_hops", "block"))
def _path_edge_tensor_compressed(nh_sets, sel, eix, src_r, dst_r, max_hops,
                                 block):
    """:func:`_path_edge_tensor` off compressed tables: the per-hop
    next-hop gather becomes selector + set-member lookups
    (``nh_sets[l, cur, dst_r // block, sel[l, cur, dst_r]]``) and never
    touches a dense (N, N) table row.  Lookups reconstruct the dense
    entry exactly, so edges/routed are bit-identical to the dense walk."""
    dst_blk = dst_r // block

    def one_layer(args):
        nh_sets_l, sel_l = args

        def hop(cur, _):
            k = sel_l[cur, dst_r].astype(jnp.int32)
            nxt = nh_sets_l[cur, dst_blk, k]
            at_dst = cur == dst_r
            hole = nxt < 0
            e = jnp.where(at_dst | hole, -1,
                          eix[cur, jnp.where(hole, cur, nxt)])
            return jnp.where(at_dst | hole, cur, nxt), e
        cur, es = jax.lax.scan(hop, src_r, None, length=max_hops)
        return es.T, cur == dst_r                      # (F, H), (F,)

    return jax.vmap(one_layer)((nh_sets, sel))


def _virtual_links(topo: Topology, wl: FlowWorkload):
    """(edge-index matrix, fabric edge count, endpoint count) — the
    virtual-link layout shared by :func:`_prepare` and the cheap
    :func:`shape_signature` probe."""
    eix = topo.edge_index_matrix()              # (N, N) -> directed edge id
    n_edges = int((eix >= 0).sum())
    # Empty workloads get one (unused) endpoint slot: max() on an empty
    # array raises, and every downstream shape stays well-formed with
    # n_ep = 1 (a zero-flow cell simulates to an all-empty SimResult).
    if len(wl.src):
        n_ep = int(max(wl.src.max(), wl.dst.max()) + 1)
    else:
        n_ep = 1
    return eix, n_edges, n_ep


def shape_signature(topo: Topology, routing: LayeredRouting,
                    wl: FlowWorkload) -> Tuple[int, int, int]:
    """(n_flows, e_tot, n_layers) for a cell WITHOUT building the scan
    operands — what batch engines bucket on.  Matches the shapes
    :func:`prepare` will realize (the hop depth is the one axis only
    the path walk can determine)."""
    _, n_edges, n_ep = _virtual_links(topo, wl)
    return (len(wl.src), n_edges + 2 * n_ep + 1, int(routing.nh.shape[0]))


@tracing.span("prepare")
def _prepare(topo: Topology, routing: LayeredRouting, wl: FlowWorkload,
             cfg: SimConfig):
    """Static arrays for the scan — including the per-layer path-edge
    tensor, so the scan body never re-derives flow paths."""
    eix, n_edges, n_ep = _virtual_links(topo, wl)
    # virtual links: [0, E) fabric, [E, E+n_ep) injection, [E+n_ep, ..) eject,
    # final slot = trash for -1 scatter.
    e_inj = n_edges
    e_ej = n_edges + n_ep
    e_tot = n_edges + 2 * n_ep + 1
    src_r = jnp.asarray(wl.src_router)
    dst_r = jnp.asarray(wl.dst_router)
    ct = getattr(routing, "compressed", None)
    with tracing.span("prepare.device"):
        if ct is not None:
            edges, routed = _path_edge_tensor_compressed(
                jnp.asarray(ct.nh_sets), jnp.asarray(ct.sel),
                jnp.asarray(eix), src_r, dst_r, cfg.max_hops, ct.block)
        else:
            edges, routed = _path_edge_tensor(jnp.asarray(routing.nh),
                                              jnp.asarray(eix), src_r, dst_r,
                                              cfg.max_hops)
        # Trim the hop axis to the longest realised path: the per-step
        # cost then tracks actual path lengths, not the cfg.max_hops cap
        # (padding is all -1 beyond the longest path by construction).
        hmax = (max(1, int((edges >= 0).sum(axis=2).max())) if edges.size
                else 1)
    edges = edges[:, :, :hmax]
    n_flows = len(wl.src)
    src_e = jnp.asarray(wl.src + e_inj)
    dst_e = jnp.asarray(wl.dst + e_ej)
    n_layers = routing.nh.shape[0]
    # (L, F, H+2): fabric hops + injection + ejection NIC per layer.
    path_edges = jnp.concatenate(
        [edges,
         jnp.broadcast_to(src_e[None, :, None], (n_layers, n_flows, 1)),
         jnp.broadcast_to(dst_e[None, :, None], (n_layers, n_flows, 1))],
        axis=2)
    usable = jnp.asarray(routing.reach)[:, src_r, dst_r].T   # (F, L)
    # Dynamic-traffic activation lane: step index before which the flow
    # does not exist.  Static workloads (active_step=None) get zeros —
    # the activation predicate then reduces bitwise to the closed-loop
    # ``start <= t`` one.
    active_step = getattr(wl, "active_step", None)
    if active_step is None:
        active_at = jnp.zeros(n_flows, dtype=jnp.int32)
    else:
        active_at = jnp.asarray(active_step, dtype=jnp.int32)
    out = dict(
        path_edges=path_edges,                         # (L, F, H+2)
        routed=routed,                                 # (L, F)
        path_hops=(edges >= 0).sum(axis=2).astype(jnp.float32),  # (L, F)
        usable=usable,
        size=jnp.asarray(wl.size, dtype=jnp.float32),
        start=jnp.asarray(wl.start, dtype=jnp.float32),
        active_at=active_at,                           # (F,) int32
        e_tot=e_tot,
        n_layers=n_layers,
    )
    # Mid-run link-death lane (fault injection): per-virtual-link death
    # step, INT32_MAX = never dies.  The key is ABSENT for pristine
    # fabrics — the scan's capacity select is gated at trace time, so a
    # fabric without scheduled failures compiles to a program bitwise
    # identical to one built before this lane existed.
    lds_r = getattr(routing, "link_down_step", None)
    if lds_r is not None:
        lds = np.full(e_tot, np.iinfo(np.int32).max, dtype=np.int32)
        fabric = np.asarray(eix) >= 0
        lds[np.asarray(eix)[fabric]] = np.asarray(lds_r,
                                                  dtype=np.int32)[fabric]
        out["link_down_step"] = jnp.asarray(lds)       # (e_tot,) int32
    # Link-churn lane (PR 10): per-virtual-link sorted (down, up) event
    # intervals plus the re-pick step (up + churn_conv, saturating).
    # Same trace-time contract as link_down_step: the keys are ABSENT
    # for schedule-free fabrics, so those compile the pre-churn program.
    lc_r = getattr(routing, "link_churn", None)
    if lc_r is not None:
        imax = np.iinfo(np.int32).max
        lc_r = np.asarray(lc_r, dtype=np.int32)
        lc = np.full((e_tot,) + lc_r.shape[2:], imax, dtype=np.int32)
        fabric = np.asarray(eix) >= 0
        lc[np.asarray(eix)[fabric]] = lc_r[fabric]
        conv = int(getattr(routing, "churn_conv", 0) or 0)
        pick_at = np.minimum(lc[..., 1].astype(np.int64) + conv, imax)
        out["link_churn"] = jnp.asarray(lc)            # (e_tot, K, 2)
        out["churn_pick_at"] = jnp.asarray(            # (e_tot, K)
            pick_at.astype(np.int32))
    return out


def _flow_uniforms(key, f):
    """(F, 2) U[0,1) draws where row ``i`` depends ONLY on ``(key, i)``.

    A plain ``jax.random.uniform(key, (f,))`` is NOT padding-safe:
    threefry pairs the flat counter array across its two halves, so
    growing ``f`` (batch padding) changes every flow's draw.  Deriving a
    per-flow key via ``fold_in`` makes each row's bits a function of the
    flow index alone — a cell simulated standalone and the same cell
    padded into a larger batch consume identical randomness, which is
    what lets the distributed sweep engine promise bit-identical
    per-cell results (see repro.experiments.dist_sweep)."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(f))
    return jax.vmap(lambda k: jax.random.uniform(k, (2,)))(keys)


def _chunk_uniforms(flow_keys, c, chunk: int):
    """(chunk, F, 2) U[0,1) draws for one scan chunk, generated in one
    bulk pass instead of two vmapped ``fold_in`` sweeps per step.

    Draw ``[s, i]`` depends only on ``(flow_keys[i], c, s)`` — per-flow
    keys keep the padding-safety property of :func:`_flow_uniforms`, and
    the counter offset inside the fixed-size ``(chunk, 2)`` block pins
    each step's bits regardless of how many steps of the chunk actually
    execute (the tail chunk slices this same block)."""
    cks = jax.vmap(jax.random.fold_in, in_axes=(0, None))(flow_keys, c)
    u = jax.vmap(lambda k: jax.random.uniform(k, (chunk, 2)))(cks)
    return jnp.moveaxis(u, 0, 1)


def _pick_layers(u, usable, minimal_only_mask):
    """Uniform choice among usable layers per flow, driven by one
    per-flow uniform ``u`` (layer 0 fallback): pick the r-th usable
    layer with r ~ U{0..n_usable-1}."""
    usable = usable & minimal_only_mask[None, :]       # (F, L)
    c = jnp.cumsum(usable.astype(jnp.int32), axis=1)   # (F, L)
    n = c[:, -1]
    r = jnp.minimum((u * n).astype(jnp.int32), jnp.maximum(n - 1, 0))
    pick = jnp.argmax(c > r[:, None], axis=1).astype(jnp.int32)
    return jnp.where(n > 0, pick, 0)


def _rto_next(rto, delivered, backoff, rto_base: int, rto_cap: int):
    """One step of the retransmission-timeout state machine (vectorised
    over flows): ``backoff`` events (stall-timer expiry, loss on link
    death) double the RTO up to ``rto_cap``; a successful delivery
    resets it to ``rto_base`` and WINS over a same-step backoff.  Pure —
    the scan body and the property tests share this exact function, so
    'backoff is monotone until delivery and resets on delivery' is
    asserted on the code that runs."""
    bumped = jnp.where(backoff, jnp.minimum(rto * 2, rto_cap), rto)
    return jnp.where(delivered, jnp.asarray(rto_base, rto.dtype), bumped)


def _escape_layers(layer, esc_ok):
    """Deterministic blackhole escape: the next layer (cyclically after
    the current one) that is pickable AND routes the flow.  ``esc_ok``
    is the static (F, L) surviving-usable-layer mask; flows with no such
    layer return their current layer (valid=False).  No PRNG draws —
    escape is timeout-driven and independent of the flowlet hazard."""
    n_layers = esc_ok.shape[1]
    order = (layer[:, None] + 1
             + jnp.arange(n_layers, dtype=jnp.int32)[None, :]) % n_layers
    ok = jnp.take_along_axis(esc_ok, order, axis=1)          # (F, L)
    first = jnp.argmax(ok, axis=1)
    esc = jnp.take_along_axis(order, first[:, None], axis=1)[:, 0]
    valid = ok.any(axis=1)
    return jnp.where(valid, esc, layer).astype(jnp.int32), valid


def _churn_state(i, sched, pick_at):
    """Per-link churn predicates at step ``i``: ``dead`` — inside a
    ``(down, up)`` outage interval (capacity 0); ``unpickable`` — inside
    the wider ``(down, up + conv)`` window during which flowlets may not
    (re-)pick the link.  Capacity restores at ``up``, USABILITY at
    ``up + conv`` — the control-plane re-convergence delay.  ``sched``
    is ``(..., K, 2)`` int32 with INT32_MAX sentinels, ``pick_at`` the
    precomputed saturating ``up + conv`` (``(..., K)``).  Pure — the
    scan body and the unit tests share this exact function."""
    down = sched[..., 0]
    dead = jnp.any((down <= i) & (i < sched[..., 1]), axis=-1)
    unpickable = jnp.any((down <= i) & (i < pick_at), axis=-1)
    return dead, unpickable


def _run_scan_impl(arrs, key0, cfg: SimConfig, static: Tuple[int, int, int]):
    e_tot, n_layers, n_steps = static
    f = arrs["size"].shape[0]
    line_bytes = jnp.float32(cfg.line_rate * cfg.dt)   # bytes per step at line

    minimal_only = jnp.ones(n_layers, dtype=bool)
    reroute = cfg.balancing in ("letflow", "fatpaths")
    chunk = max(1, int(cfg.horizon_chunk))
    n_full, rem = divmod(n_steps, chunk)
    # Loss-recovery lanes (PR 8) — ALL trace-time gated: with
    # recovery="off" and record=0 every branch below compiles away and
    # the program is identical to the pre-PR-8 scan (test-asserted
    # bitwise per transport mode).
    recovery_on = str(cfg.recovery).lower() in ("on", "1", "true")
    record_on = bool(int(cfg.record))
    has_lds = "link_down_step" in arrs
    # Churn lanes (PR 10), gated exactly like link_down_step: absent
    # operands compile the identical pre-churn program.  has_death arms
    # the loss-accounting lanes for EITHER kind of mid-run link death.
    has_churn = "link_churn" in arrs
    has_death = has_lds or has_churn
    # Link-load ECN marking replaces the pure share-vs-rate congested
    # bool as the dctcp signal only under recovery (tcp keeps the
    # legacy signal in both modes).
    want_util = recovery_on and cfg.transport == "dctcp"

    k_init, k_scan = jax.random.split(key0)
    layer0 = _pick_layers(_flow_uniforms(k_init, f)[:, 0], arrs["usable"],
                          minimal_only)
    # Per-flow key table, hoisted out of the step body: step randomness
    # is (flow key, chunk, step-in-chunk) — see _chunk_uniforms.
    flow_keys = jax.vmap(lambda i: jax.random.fold_in(k_scan, i))(
        jnp.arange(f))

    if cfg.transport == "ndp":
        rate0 = jnp.ones(f, dtype=jnp.float32)         # line rate start
    else:
        rate0 = jnp.full(f, cfg.tcp_init, dtype=jnp.float32)

    init = dict(
        remaining=arrs["size"],
        layer=layer0,
        rate=rate0,
        hops=jnp.zeros(f, dtype=jnp.float32),
        # Per-flow accumulators (elementwise, exact under flow padding);
        # the utilization ratio is taken on host AFTER stripping padding,
        # so batched and standalone runs report bit-identical metrics.
        sent_acc=jnp.zeros(f, dtype=jnp.float32),
        w_acc=jnp.zeros(f, dtype=jnp.float32),
        # Departure lane: the step at which the flow finished (-1 = in
        # flight).  Result-bearing AND exact under early exit: once all
        # flows are done/stuck no step produces a newly_done, so skipped
        # chunks cannot have written it.
        depart_step=jnp.full(f, -1, dtype=jnp.int32),
    )
    if recovery_on:
        # stall: consecutive ~zero-share steps; rto: current timeout
        # (steps, doubles on backoff up to rto_cap); blocked_until: the
        # step before which a loss-penalised flow may not send;
        # retrans_acc: lost-in-flight line-units that had to be resent.
        init.update(
            stall=jnp.zeros(f, dtype=jnp.int32),
            rto=jnp.full(f, int(cfg.rto_base), dtype=jnp.int32),
            blocked_until=jnp.zeros(f, dtype=jnp.int32),
            retrans_acc=jnp.zeros(f, dtype=jnp.float32),
        )

    cap = jnp.ones(e_tot, dtype=jnp.float32)           # capacities in line units
    frows = jnp.arange(f)
    # One packed (L, F, H+4) record — path edges | routed | hop count —
    # so the step body gathers by current layer ONCE, not three times.
    n_slots = arrs["path_edges"].shape[2]
    packed = jnp.concatenate(
        [arrs["path_edges"].astype(jnp.int32),
         arrs["routed"].astype(jnp.int32)[..., None],
         arrs["path_hops"].astype(jnp.int32)[..., None]], axis=2)

    # Provably-stuck support for the adaptive horizon: a flow whose
    # current layer cannot route it AND that can never re-roll onto a
    # routing layer (re-rolls pick among `usable` layers, falling back
    # to layer 0) has weight 0 on every future step.  Without re-routing
    # the layer is pinned, so the current layer alone decides.
    if reroute:
        pickable = arrs["usable"] & minimal_only[None, :]
        pickable = jnp.where(pickable.any(axis=1, keepdims=True), pickable,
                             (jnp.arange(n_layers) == 0)[None, :])
        pick_routable = jnp.any(pickable & arrs["routed"].T, axis=1)  # (F,)
        # Static escape-candidate mask for the RTO blackhole escape:
        # layers a flow may pick that actually route it.
        esc_ok = pickable & arrs["routed"].T                           # (F, L)
    else:
        pick_routable = jnp.zeros(f, dtype=bool)
        esc_ok = None

    def step(state, xs):
        if reroute:
            i, u = xs
        else:
            i = xs
        t = i.astype(jnp.float32) * cfg.dt
        # Open-loop activation: a flow exists once its activation step
        # has been reached AND its start time has passed.  Static cells
        # have active_at == 0 everywhere, reducing this bitwise to the
        # closed-loop ``start <= t`` predicate.
        started = (arrs["start"] <= t) & (i >= arrs["active_at"])
        done = state["remaining"] <= 0
        active = started & ~done

        # One gather by current layer replaces the per-step table walk:
        # paths were materialised once in _prepare, packed once above.
        g = packed[state["layer"], frows]                       # (F, H+4)
        edges = g[:, :n_slots]
        routed = g[:, n_slots] > 0
        n_hops = g[:, n_slots + 1].astype(jnp.float32)
        if recovery_on:
            # Loss penalties stall the sender: a flow blocked by its
            # transport's loss response (RTO stall for tcp, a fraction
            # of it for dctcp, one trimmed-RTT for ndp) sends nothing
            # until its blocked_until step.
            unblocked = i >= state["blocked_until"]
            send = active & routed & unblocked
        else:
            send = active & routed

        # --- fused max-min water-filling (feasible by construction) -------
        # The active lane masks non-sending rows to the trash link inside
        # the kernel (value-identical to the host-side select it replaced).
        w = send.astype(jnp.float32)
        desired = jnp.minimum(state["rate"], 1.0) * w
        # Mid-run link death: a link's capacity drops to 0 at its
        # scheduled step (fair share 0 in both waterfill backends), so
        # flows on it stall, their slack maxes the flowlet-gap hazard,
        # and the next re-roll lands on a surviving usable layer.  The
        # branch is trace-time: pristine fabrics (no "link_down_step"
        # operand) compile the exact pre-fault program.
        if "link_down_step" in arrs:
            cap_t = jnp.where(i < arrs["link_down_step"], cap, 0.0)
        else:
            cap_t = cap
        # Link churn: capacity 0 inside every (down, up) outage window —
        # and back to line rate at `up` (unlike the one-shot lane, links
        # RETURN).  Re-pick usability is gated separately below.
        if has_churn:
            churn_dead, link_unpick = _churn_state(
                i, arrs["link_churn"], arrs["churn_pick_at"])
            cap_t = jnp.where(churn_dead, 0.0, cap_t)
        with jax.named_scope("waterfill"):
            wf = waterfill_step(edges, w, desired, cap_t, active=send,
                                fair_iters=cfg.fair_iters,
                                backend=cfg.kernel_backend or None,
                                want_util=want_util)
        if want_util:
            sent, share, util = wf
        else:
            sent, share = wf

        # Lost-in-flight accounting on mid-run link death: at the step a
        # path edge dies, a bandwidth-delay-product estimate of the
        # bytes in the pipe (rate x path latency in steps, capped by
        # what was actually sent) is rolled back from sent_acc into
        # remaining — those bytes MUST be retransmitted.  The dying
        # link's capacity is already 0 this step, so the hit flow
        # delivered nothing concurrently.
        if recovery_on and has_death:
            safe_e = jnp.where(edges >= 0, edges, e_tot - 1)     # (F, S)
            died_now = None
            if has_lds:
                lds_g = arrs["link_down_step"][safe_e]
                died_now = jnp.any(lds_g == i, axis=1)
            if has_churn:
                # A churn down-event on the current path this step: the
                # same in-flight loss as a one-shot death (events repeat,
                # so a flapping link charges the pipe on EVERY down).
                ch_d = arrs["link_churn"][..., 0][safe_e]        # (F, S, K)
                c_hit = jnp.any(ch_d == i, axis=(1, 2))
                died_now = c_hit if died_now is None else died_now | c_hit
            hit = active & routed & died_now
            pipe_steps = (n_hops * jnp.float32(cfg.link_latency)
                          + jnp.float32(cfg.sw_latency)) / jnp.float32(cfg.dt)
            lost = jnp.where(
                hit, jnp.minimum(state["sent_acc"],
                                 state["rate"] * pipe_steps), 0.0)
        else:
            hit = None
            lost = 0.0

        delivered = sent * line_bytes
        new_remaining = jnp.maximum(state["remaining"] - delivered * w, 0.0)
        if recovery_on and has_death:
            new_remaining = new_remaining + lost * line_bytes
        newly_done = (new_remaining <= 0) & ~done & started
        # FCT is NOT accumulated in-scan: it is derived on the host from
        # the integer depart/hops lanes (:func:`_to_result`).  A float
        # chain like ``t + dt - start + ...`` is fair game for XLA to
        # regroup, and batched vs standalone compilations regrouped it
        # DIFFERENTLY once ``start`` was nonzero (dynamic traffic) —
        # a 1-ulp engine divergence.  Integer lanes can't regroup.
        hops = jnp.where(newly_done, n_hops, state["hops"])
        depart = jnp.where(newly_done, i.astype(jnp.int32),
                           state["depart_step"])

        # --- transport rate dynamics --------------------------------------
        if cfg.transport == "ndp":
            rate = jnp.ones(f, dtype=jnp.float32)
        elif cfg.transport == "dctcp" and recovery_on:
            # ECN: mark proportionally to the worst link claim
            # utilization on the path — a DCTCP-style graded decrease
            # (full dctcp_md multiplicative decrease at saturation)
            # instead of the binary share-vs-rate signal.  A dead link
            # reports huge utilization, so blackholed flows mark at
            # full strength.
            denom = max(1.0 - float(cfg.ecn_thresh), 1e-6)
            frac = jnp.clip((util - cfg.ecn_thresh) / denom, 0.0, 1.0)
            slow_start = state["rate"] < 0.5
            up = jnp.where(slow_start, state["rate"] * 2.0,
                           state["rate"] + cfg.tcp_ai)
            down = state["rate"] * (1.0 - (1.0 - cfg.dctcp_md) * frac)
            rate = jnp.where(frac > 0, jnp.maximum(down, cfg.tcp_init),
                             jnp.minimum(up, 1.0))
        else:
            congested = share < state["rate"] * 0.98
            md = cfg.tcp_md if cfg.transport == "tcp" else cfg.dctcp_md
            slow_start = state["rate"] < 0.5
            up = jnp.where(slow_start, state["rate"] * 2.0,
                           state["rate"] + cfg.tcp_ai)
            rate = jnp.where(congested, jnp.maximum(share * md, cfg.tcp_init),
                             jnp.minimum(up, 1.0))

        # --- RTO state machine + loss penalties (recovery lanes) ----------
        if recovery_on:
            progress = sent > 1e-6
            # Stall timer: consecutive steps an unblocked, wanting flow
            # got ~zero share (blackholed on a dead edge, starved, or
            # unrouted on its current layer).
            stalled = active & unblocked & ~progress
            stall_new = jnp.where(stalled, state["stall"] + 1, 0)
            expire = stalled & (stall_new >= state["rto"])
            backoff = expire
            blocked = state["blocked_until"]
            if has_death:
                i32 = i.astype(jnp.int32)
                if cfg.transport == "ndp":
                    # Trimming: loss detected in one trimmed-RTT, no
                    # timeout and no backoff (headers always arrive).
                    pen = jnp.int32(1)
                elif cfg.transport == "tcp":
                    # Full RTO stall + slow-start re-entry.
                    pen = state["rto"]
                    rate = jnp.where(hit, jnp.float32(cfg.tcp_init), rate)
                else:
                    # dctcp: a fraction of the RTO + gentle decrease.
                    pen = jnp.maximum(state["rto"] // 4, 1)
                    rate = jnp.where(
                        hit, jnp.maximum(state["rate"] * cfg.dctcp_md,
                                         cfg.tcp_init), rate)
                blocked = jnp.where(hit, i32 + pen, blocked)
                if cfg.transport != "ndp":
                    backoff = backoff | hit
            rto = _rto_next(state["rto"], progress, backoff,
                            int(cfg.rto_base), int(cfg.rto_cap))
            stall_out = jnp.where(expire, 0, stall_new)
            retrans = state["retrans_acc"] + (lost if has_death else 0.0)

        # --- flowlet elasticity + layer re-roll -----------------------------
        if reroute:
            slack = 1.0 - jnp.clip(sent, 0.0, 1.0)
            p_gap = jnp.clip(cfg.dt / cfg.flowlet_gap
                             * (slack + cfg.gap_eps), 0.0, 1.0)
            roll = u[:, 0] < p_gap
            if has_churn:
                # Re-convergence gating: a layer whose path crosses a
                # link inside its (down, up + conv) window is not
                # re-pickable this step — flows already placed on it
                # keep sending once capacity returns at `up`, but new
                # flowlet picks wait out the control-plane delay.  With
                # every candidate gated the flow keeps its layer (no
                # forced fallback onto a dead layer 0).
                pe_safe = jnp.where(arrs["path_edges"] >= 0,
                                    arrs["path_edges"], e_tot - 1)
                layer_live = ~jnp.any(link_unpick[pe_safe], axis=2).T  # (F, L)
                cand = arrs["usable"] & layer_live
                newpick = _pick_layers(u[:, 1], cand, minimal_only)
                roll = roll & cand.any(axis=1)
            else:
                newpick = _pick_layers(u[:, 1], arrs["usable"], minimal_only)
            layer = jnp.where(roll & active, newpick, state["layer"])
        else:
            layer = state["layer"]
        if recovery_on and reroute:
            # Blackhole escape: when the stall timer crosses the RTO the
            # flow DETERMINISTICALLY re-picks the next usable layer that
            # routes it — timeout-driven, independent of the stochastic
            # flowlet hazard, and consuming no PRNG draws (so the
            # hazard's (key, flow, step) stream is untouched).  Without
            # re-routing (ecmp) the layer stays pinned: the
            # never-recovers control.
            esc_layer, esc_valid = _escape_layers(
                state["layer"], esc_ok & layer_live if has_churn else esc_ok)
            layer = jnp.where(expire & esc_valid, esc_layer, layer)

        out = dict(remaining=new_remaining, layer=layer, rate=rate,
                   hops=hops, depart_step=depart, w_acc=state["w_acc"] + w)
        if recovery_on:
            out.update(
                sent_acc=state["sent_acc"] + sent
                - (lost if has_death else 0.0),
                stall=stall_out, rto=rto, blocked_until=blocked,
                retrans_acc=retrans)
        else:
            out["sent_acc"] = state["sent_acc"] + sent
        if record_on:
            # Per-step aggregates for the recovery evaluator's curves.
            # f32 device sums are fine HERE: the record lane only runs
            # on the sequential evaluator path (both engines execute
            # this same unpadded program), never in padded batches.
            ys = dict(
                goodput=jnp.sum(sent * w),
                stalled=jnp.sum((active & (sent <= 1e-6))
                                .astype(jnp.float32)))
        else:
            ys = None
        return out, ys

    # Record buffers ride the while-loop carry OUTSIDE the per-step scan
    # carry (they are written chunk-at-a-time via dynamic_update_slice).
    # bufs0 is None when record=0 — an empty pytree node, so the carry
    # structure (and the compiled program) is unchanged from pre-PR-8.
    if record_on:
        bufs0 = dict(goodput_t=jnp.zeros(n_steps, dtype=jnp.float32),
                     stalled_t=jnp.zeros(n_steps, dtype=jnp.float32))
    else:
        bufs0 = None

    def run_chunk(state, bufs, c, length: int):
        steps_i = c * chunk + jnp.arange(length)
        if reroute:
            # Full (chunk, F, 2) block even for the tail: a step's draws
            # must not depend on how many steps of its chunk execute.
            u = _chunk_uniforms(flow_keys, c, chunk)[:length]
            xs = (steps_i, u)
        else:
            xs = steps_i
        state, ys = jax.lax.scan(step, state, xs)
        if record_on:
            bufs = {
                "goodput_t": jax.lax.dynamic_update_slice(
                    bufs["goodput_t"], ys["goodput"], (c * chunk,)),
                "stalled_t": jax.lax.dynamic_update_slice(
                    bufs["stalled_t"], ys["stalled"], (c * chunk,)),
            }
        return state, bufs

    def exhausted(state):
        # Pending arrivals block early exit for free: a flow whose
        # active_at lies ahead still has remaining > 0, and it only
        # counts as stuck if NO pickable layer can ever route it — in
        # which case it would send nothing after activating either.
        routed_cur = arrs["routed"][state["layer"], frows]
        stuck = ~routed_cur & ~pick_routable
        return jnp.all((state["remaining"] <= 0.0) | stuck)

    # Adaptive horizon: fixed-size chunks under a while_loop.  Once every
    # flow is done or provably stuck, each further step is an exact no-op
    # on every result-bearing state component (remaining/fct/hops/accs;
    # weight-0 flows send nothing and accumulate nothing), so stopping
    # early is bit-identical to running all n_steps.  Only result-inert
    # components keep evolving full-horizon (a done tcp flow's rate ramp,
    # a stuck flow's layer re-rolls) — none of them feed SimResult.
    if n_full:
        def w_cond(carry):
            state, _bufs, c = carry
            go = c < n_full
            if cfg.adaptive_horizon:
                go = go & ~exhausted(state)
            return go

        def w_body(carry):
            state, bufs, c = carry
            state, bufs = run_chunk(state, bufs, c, chunk)
            return state, bufs, c + 1

        state, bufs, c_run = jax.lax.while_loop(w_cond, w_body,
                                                (init, bufs0, jnp.int32(0)))
    else:
        state, bufs, c_run = init, bufs0, jnp.int32(0)
    if rem:
        # The tail rides chunk index n_full unconditionally (running it
        # after an early exit is the same no-op as the skipped chunks).
        state, bufs = run_chunk(state, bufs, n_full, rem)
    # horizon_chunks is execution bookkeeping (how far the while_loop
    # ran), never a result: downstream result assembly ignores it and
    # the sweep engines report it as execution meta only.
    out = dict(state, horizon_chunks=c_run)
    if record_on:
        out.update(bufs)
    return out


_run_scan = functools.partial(jax.jit,
                              static_argnames=("cfg", "static"))(_run_scan_impl)


@functools.partial(jax.jit, static_argnames=("cfg", "static"))
def _run_scan_batch(arrs, keys, cfg: SimConfig,
                    static: Tuple[int, int, int]):
    """One vmapped scan over a batch of PRNG keys (seed sweep)."""
    return jax.vmap(lambda k: _run_scan_impl(arrs, k, cfg, static))(keys)


@tracing.span("finalise")
def _to_result(size: np.ndarray, final, cfg: SimConfig,
               start: Optional[np.ndarray] = None) -> SimResult:
    remaining = np.asarray(final["remaining"])
    # Flow-time-weighted achieved-rate fraction: total line-rate fraction
    # actually sent over total demanded.  Host-side float64 over the
    # (padding-stripped) per-flow accumulators — identical whether the
    # cell ran standalone or inside a padded batch.
    sent = float(np.asarray(final["sent_acc"], dtype=np.float64).sum())
    want = float(np.asarray(final["w_acc"], dtype=np.float64).sum())
    # FCT from the integer depart lane, on host with a FIXED numpy op
    # order (left-to-right, no FMA): completion time (the step after the
    # departing step's clock tick) minus start, plus propagation and
    # software latency over the path taken at completion.  Deriving this
    # from integer state is what makes dynamic cells' FCTs bit-identical
    # between the sequential and distributed engines — see the step
    # body's comment.
    dep = np.asarray(final["depart_step"])
    hops = np.asarray(final["hops"])
    f32 = np.float32
    start32 = (np.zeros(dep.shape, np.float32) if start is None
               else np.asarray(start, np.float32))
    fct = ((dep.astype(np.float32) + f32(1.0)) * f32(cfg.dt) - start32
           + hops * f32(cfg.link_latency) + f32(cfg.sw_latency))
    fct = np.where(dep >= 0, fct, np.float32(np.nan))
    # Recovery/record lanes are optional scan outputs (absent = None).
    line_bytes = f32(cfg.line_rate * cfg.dt)
    ret = final.get("retrans_acc")
    return SimResult(
        fct=fct,
        delivered=size - remaining,
        size=size,
        finished=remaining <= 0,
        link_util_mean=sent / max(want, 1.0),
        config=cfg,
        depart_step=dep,
        retrans_bytes=(None if ret is None
                       else np.asarray(ret) * line_bytes),
        goodput_steps=(None if "goodput_t" not in final
                       else np.asarray(final["goodput_t"])),
        stalled_steps=(None if "stalled_t" not in final
                       else np.asarray(final["stalled_t"])),
        chunks=int(final.get("horizon_chunks", 0)),
    )


def prepare(topo: Topology, routing: LayeredRouting, wl: FlowWorkload,
            cfg: SimConfig):
    """Public prepare step for external batch engines: returns
    ``(arrs, static)`` where ``arrs`` is the dict of scan operands and
    ``static = (e_tot, n_layers, n_steps)`` the static shape triple
    consumed by the scan program.  ``repro.experiments.dist_sweep`` pads
    and stacks many cells' ``arrs`` into one vmapped program."""
    arrs = _prepare(topo, routing, wl, cfg)
    static = (int(arrs["e_tot"]), int(arrs["n_layers"]), int(cfg.n_steps))
    jarrs = {k: v for k, v in arrs.items() if k not in ("e_tot", "n_layers")}
    return jarrs, static


def pad_prepared(arrs, static, *, n_flows: int, n_edges: int,
                 hop_slots: int):
    """Pad one cell's prepared scan operands to a bucket-wide shape so
    heterogeneous cells stack into one batched program, WITHOUT changing
    the simulation of the real flows.

    Exactness argument (each padding axis):

    * flows (F): padded flows have ``start=inf`` and ``active_at`` =
      INT32_MAX (never started, never activated), size 0,
      ``usable``/``routed`` False — their water-filling weight is 0.0, an
      exact no-op on every shared-link sum, and the per-flow randomness
      is ``fold_in``-keyed by flow index so real flows' draws are
      unchanged (:func:`_flow_uniforms`);
    * hop slots (H): pad columns are -1, which the scan maps to the trash
      link and excludes from every min/fair-share reduction;
    * virtual links (e_tot): extra slots have capacity 1 and no flow ever
      indexes them (edge ids are cell-local); only the trash slot moves,
      and it is write-only.

    The layer count L and step count are bucket keys, never padded —
    padding L would change layer-choice draws, padding steps would change
    the dynamics.
    """
    e_tot, n_layers, n_steps = static
    F, H = arrs["size"].shape[0], arrs["path_edges"].shape[2]
    if n_flows < F or n_edges < e_tot or hop_slots < H:
        raise ValueError(f"pad target ({n_flows},{n_edges},{hop_slots}) "
                         f"smaller than cell ({F},{e_tot},{H})")

    def padf(x, fill, axis):
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, n_flows - x.shape[axis])
        return jnp.pad(x, pads, constant_values=fill)

    pe = jnp.pad(arrs["path_edges"], ((0, 0), (0, 0), (0, hop_slots - H)),
                 constant_values=-1)
    out = dict(
        path_edges=padf(pe, -1, 1),
        routed=padf(arrs["routed"], False, 1),
        path_hops=padf(arrs["path_hops"], 0.0, 1),
        usable=padf(arrs["usable"], False, 0),
        size=padf(arrs["size"], 0.0, 0),
        start=padf(arrs["start"], jnp.inf, 0),
        active_at=padf(arrs["active_at"], np.iinfo(np.int32).max, 0),
    )
    if "link_down_step" in arrs:
        # Pad link slots with INT32_MAX (never die); no flow indexes
        # them, so the value only has to keep the select a no-op.
        out["link_down_step"] = jnp.pad(
            arrs["link_down_step"], (0, n_edges - e_tot),
            constant_values=np.iinfo(np.int32).max)
    if "link_churn" in arrs:
        # Churn events pad the same way: sentinel intervals never open,
        # so padded link slots are never dead nor pick-gated.  The event
        # axis K is a bucket key (padded_signature), never padded.
        imax = np.iinfo(np.int32).max
        out["link_churn"] = jnp.pad(
            arrs["link_churn"], ((0, n_edges - e_tot), (0, 0), (0, 0)),
            constant_values=imax)
        out["churn_pick_at"] = jnp.pad(
            arrs["churn_pick_at"], ((0, n_edges - e_tot), (0, 0)),
            constant_values=imax)
    return out, (int(n_edges), n_layers, n_steps)


def batch_result(size: np.ndarray, final, cfg: SimConfig,
                 n_flows: Optional[int] = None,
                 start: Optional[np.ndarray] = None) -> SimResult:
    """One element of a batched scan output -> :class:`SimResult`,
    stripping flow padding (``n_flows`` = the cell's real flow count).
    ``start`` is the cell's (unpadded) flow start times; omit for
    all-start-at-zero workloads."""
    per_flow = ("remaining", "layer", "rate", "hops",
                "sent_acc", "w_acc", "depart_step",
                # recovery lanes (present only when cfg.recovery is on)
                "stall", "rto", "blocked_until", "retrans_acc")
    if n_flows is not None:
        final = {k: (v[:n_flows] if k in per_flow else v)
                 for k, v in final.items()}
        size = size[:n_flows]
        if start is not None:
            start = np.asarray(start)[:n_flows]
    return _to_result(np.asarray(size), final, cfg, start=start)


def static_config(cfg: SimConfig) -> SimConfig:
    """The jit-static form of ``cfg``.  The PRNG key is a scan operand
    and cfg.seed is NOT read inside the program, so the seed is
    normalized out (otherwise every sweep seed recompiles a
    byte-identical scan).  The kernel backend is resolved here, not at
    trace time, so a program compiled for one backend is never reused
    after ``REPRO_KERNEL_BACKEND`` changes."""
    return dataclasses.replace(
        cfg, seed=0, kernel_backend=cfg.kernel_backend or kernel_backend())


def count_waterfill_path(cfg: SimConfig) -> None:
    """Count the water-filling implementation a scan under ``cfg`` (its
    :func:`static_config`) runs, ``waterfill.ref`` or
    ``waterfill.sorted``, once per simulated cell."""
    tracing.count("waterfill." + waterfill_path(cfg.kernel_backend))


def simulate(topo: Topology, routing: LayeredRouting, wl: FlowWorkload,
             cfg: SimConfig) -> SimResult:
    """Run the flow simulator; returns per-flow FCTs and aggregates."""
    jarrs, static = prepare(topo, routing, wl, cfg)
    scfg = static_config(cfg)
    count_waterfill_path(scfg)
    with tracing.span("scan"):
        final = jax.block_until_ready(_run_scan(
            jarrs, jax.random.PRNGKey(cfg.seed), scfg, static))
    return _to_result(np.asarray(jarrs["size"]), final, cfg,
                      start=np.asarray(jarrs["start"]))


def simulate_seeds(topo: Topology, routing: LayeredRouting, wl: FlowWorkload,
                   cfg: SimConfig, seeds) -> list:
    """Seed sweep batched through ONE vmapped scan (no Python loop over
    simulations): same topology/routing/workload, one PRNG stream per
    seed.  Returns a list of :class:`SimResult`, one per seed, identical
    to looping :func:`simulate` with ``cfg.seed`` set to each value."""
    seeds = [int(s) for s in seeds]
    if not seeds:
        return []
    jarrs, static = prepare(topo, routing, wl, cfg)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    scfg = static_config(cfg)
    count_waterfill_path(scfg)
    with tracing.span("scan"):
        finals = jax.block_until_ready(
            _run_scan_batch(jarrs, keys, scfg, static))
    size = np.asarray(jarrs["size"])
    start = np.asarray(jarrs["start"])
    return [
        _to_result(size, {k: v[i] for k, v in finals.items()},
                   dataclasses.replace(cfg, seed=s), start=start)
        for i, s in enumerate(seeds)
    ]
