"""Distributed sweep engine: the whole evaluation matrix as device-
parallel batched programs.

The sequential :meth:`Session.sweep` runs one cell at a time — one
``lax.scan`` dispatch per (cell, sim-seed), wall-clock-bound long before
the paper-scale grid (six topologies x four schemes x many patterns and
seeds, §7).  This engine converts the grid into a handful of batched
device programs:

1. **bucket** — transport cells are grouped by padded shape signature:
   identical :class:`~repro.core.transport.SimConfig` (scheme/transport/
   steps/...), identical layer count L, and the same power-of-two size
   class of flow / virtual-link counts (cells in a bucket pay each
   other's padding, so size classes bound the waste at 2x);
2. **pad** — each cell's prepared scan operands are padded to the bucket
   maxima with *exactness-preserving* padding
   (:func:`repro.core.transport.pad_prepared`): padded flows never
   start, padded hop slots map to the write-only trash link, padded link
   slots are never indexed.  Per-flow randomness is ``fold_in``-keyed by
   flow index, so padding changes no real flow's draws;
3. **vmap** — all of a bucket's (cell, sim-seed) elements run as ONE
   program, ``jax.vmap`` over the stacked operands;
4. **shard_map** — the element axis is sharded over a
   :class:`repro.dist.Runtime` mesh (``--devices N`` forced host devices
   or real accelerators), so an 8-device host advances ~8 cells per
   dispatch.

Because steps 2-4 are all bit-exact transformations of the standalone
simulation, per-cell results are IDENTICAL to the sequential engine and
independent of device count — CI asserts sequential == ``--devices 8``
cell-for-cell (see :func:`repro.experiments.results.compare_results`).

The transport scan's adaptive horizon (PR 5) composes with all of the
above: a batched ``lax.while_loop`` stops each element's chunked scan
as soon as its flows are done or provably stuck, which jax's batching
rule applies per element (finished elements' carries are frozen by
``select``), so early exit stays bit-identical under vmap/shard_map
too.  The executed chunk count is surfaced as ``sweep_chunks`` in each
cell's meta — execution bookkeeping (like ``sweep_bucket``), never part
of the results, and ignored by :func:`compare_results`.

Sweeps are resumable: with a checkpoint directory every finished cell is
committed (atomic per-cell JSON, :class:`repro.ckpt.SweepCheckpoint`)
and a re-run loads completed cells instead of re-simulating them.
Non-transport evaluators (``mat``, ``fabric``) fall back to the
sequential path within the same sweep and share its checkpointing.

Quarantine: a bucket whose compile or execution fails — a Pallas
lowering/runtime error on an exotic shape, say — emits its cells with
empty metrics and a structured ``error`` meta field instead of poisoning
the whole artifact.  There is no retry on another kernel backend: a
failure of the measured path shows up as that failure, never as the
oracle's numbers under the cell's name.  Cells whose simulation state
comes back non-finite (inf/NaN delivered bytes) are quarantined the same
way.  Error cells are NEVER checkpointed, so a later resume re-attempts
exactly them.

Emission is streamed (``callback`` fires as each cell completes,
bucket-by-bucket) but the returned list — and therefore every sweep
artifact — is in canonical grid order, independent of execution order
(:func:`repro.experiments.results.order_results`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..ckpt.sweep import SweepCheckpoint
from ..core import transport as transport_mod
from ..dist.sharding import P, Runtime, host_device_runtime
from .catalog import (EVALUATORS, fct_metrics, sim_meta, transport_meta,
                      transport_plan)
from .results import RunResult, order_results
from .session import ResolvedCell, Session
from .specs import ExperimentSpec

__all__ = ["dist_sweep", "bucket_signature"]


@dataclasses.dataclass
class _Work:
    """One transport cell planned for batched execution.  Only the
    cheap shape signature is computed up front; the heavy scan operands
    (the (L, F, H+2) path tensor) are built per-bucket at dispatch time
    so peak memory scales with one bucket, not the whole grid."""

    spec: ExperimentSpec
    cell: ResolvedCell
    cfg: Any                     # SimConfig (seed = the cell's seed)
    sim_seeds: List[int]
    n_flows: int
    e_tot: int
    n_layers: int
    ev_meta: Dict[str, Any]
    rec: tracing.Record          # the cell's resolve and plan (span "cell")
    size: Any = None             # (F,) float32, filled at dispatch
    start: Any = None            # (F,) float32 flow start times, ditto


def _ceil_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def bucket_signature(cfg, static) -> tuple:
    """The batch-compatibility key for a prepared transport cell: the
    full SimConfig with the seed normalized away (the PRNG key is a scan
    *operand*, not part of the program) plus the layer count L.  L is a
    hard key — padding the layer axis would change layer-choice draws —
    while flows / links / hop depth pad exactly and stay out of the
    key."""
    return (dataclasses.replace(cfg, seed=0), static[1])


def padded_signature(cfg, n_layers: int, n_flows: int, e_tot: int,
                     link_down: bool = False, churn_k: int = 0) -> tuple:
    """The bucketing key actually used to group cells: the compatibility
    key plus the power-of-two size class of the flow count and the
    virtual-link count.  Cells in one bucket batch into one program and
    pay each other's padding, so a 100-flow cell must not share a bucket
    with a 10k-flow cell — size classes bound the waste at 2x while
    still merging near-same-size cells across topologies.  Computed from
    the cheap :func:`repro.core.transport.shape_signature` probe, no
    scan operands needed.  ``link_down`` flags cells with a mid-run
    link-death schedule: their prepared operand tree carries one extra
    leaf (and the scan compiles an extra capacity select), so they must
    not stack with pristine cells.  ``churn_k`` is the churn schedule's
    per-link event-slot count (0 = no schedule): churn cells carry two
    extra (e, K, ...) operands and extra scan lanes, so they never share
    a bucket with pristine cells, and K is an exact stacking dimension —
    not a pow2 class — because event slots are never padded."""
    return (dataclasses.replace(cfg, seed=0), n_layers,
            _ceil_pow2(n_flows), _ceil_pow2(e_tot), bool(link_down),
            int(churn_k))


# The compiled bucket programs live at module scope: a fresh
# ``jax.jit(closure)`` per call would recompile every bucket on every
# sweep (jit caches key on function identity).  ``cfg``/``static`` are
# hashable static args; the ``_sharded_*`` variants additionally
# memoize per Runtime so the shard_map wrapping is built once per mesh.
#
# Two program shapes: ``_*_scan`` batches independent (cell, seed)
# elements — operands per element; ``_*_scan_seeds`` batches cells with
# a NESTED vmap over each cell's sim-seed keys, so a seed sweep shares
# one copy of the cell's operand tensors instead of shipping
# ``n_seeds`` duplicates to the device.  The functions the ``_sharded_*``
# variants wrap keep ``scan`` in their names: they name the XLA module
# (``jit_sweep_scan``), which is how a device trace finds the scan.
@functools.partial(jax.jit, static_argnames=("cfg", "static"))
def _vmapped_scan(stacked, keys, cfg, static):
    return jax.vmap(
        lambda a, k: transport_mod._run_scan_impl(a, k, cfg, static)
    )(stacked, keys)


@functools.partial(jax.jit, static_argnames=("cfg", "static"))
def _vmapped_scan_seeds(stacked, keys, cfg, static):
    return jax.vmap(lambda a, ks: jax.vmap(
        lambda k: transport_mod._run_scan_impl(a, k, cfg, static))(ks)
    )(stacked, keys)


@functools.lru_cache(maxsize=128)
def _sharded_scan(rt: Runtime, cfg, static):
    axis = rt.data_axes[0]

    def sweep_scan(stacked, keys):
        return _vmapped_scan(stacked, keys, cfg, static)

    return jax.jit(rt.shard_map(sweep_scan, in_specs=(P(axis), P(axis)),
                                out_specs=P(axis)))


@functools.lru_cache(maxsize=128)
def _sharded_scan_seeds(rt: Runtime, cfg, static):
    axis = rt.data_axes[0]

    def sweep_scan_seeds(stacked, keys):
        return _vmapped_scan_seeds(stacked, keys, cfg, static)

    return jax.jit(rt.shard_map(sweep_scan_seeds,
                                in_specs=(P(axis), P(axis)),
                                out_specs=P(axis)))


def _dispatch_bucket(works: List[_Work], rt: Runtime, bucket_index: int):
    """Asynchronously launch one bucket's batched program.

    Scheduling policy over the mesh:

    * no mesh                    -> plain vmapped program;
    * elements >= mesh size      -> ``shard_map`` the element axis over
      the whole mesh (intra-bucket data parallelism);
    * elements <  mesh size      -> run the whole (small) bucket on ONE
      device, round-robin by bucket index — different buckets then
      execute concurrently on different devices (inter-bucket
      parallelism), instead of padding a 2-element bucket out to an
      8-device mesh.

    Seed sweeps share operands: when every cell in the bucket has the
    same seed count S > 1 — and the cell axis alone still has enough
    units to feed the mesh — the program is a NESTED vmap: outer over
    cells (operands stacked once), inner over each cell's S PRNG keys,
    so the device sees one copy of each cell's tensors, not S
    duplicates.  Mixed seed counts, or fewer cells than devices, fall
    back to the flat one-element-per-(cell, seed) layout (duplicated
    operands, but every element shardable).

    Returns ``(finals, elements, mode, pads)`` where ``finals`` are
    device arrays still computing — jax dispatch is async, so callers
    may launch further buckets before blocking on this one
    (:func:`_finalize_bucket`) — ``elements`` is the flat (work_idx,
    sim_seed) order matching the flattened batch axis/axes of
    ``finals``, and ``pads`` the realized (F, E, H) pad targets.

    The heavy scan operands are built HERE, bucket by bucket: preparing
    the whole grid up front would hold every cell's (L, F, H+2) path
    tensor live at once.
    """
    cfg0 = transport_mod.static_config(works[0].cfg)
    prepared = []
    for w in works:
        arrs, static = transport_mod.prepare(
            w.cell.topo, w.cell.bundle.routing, w.cell.workload, w.cfg)
        w.size = np.asarray(arrs["size"])
        w.start = np.asarray(arrs["start"])
        prepared.append((arrs, static))
    with tracing.span("sweep.pad"):
        n_flows = max(w.n_flows for w in works)
        n_edges = max(w.e_tot for w in works)
        hop_slots = max(a["path_edges"].shape[2] for a, _ in prepared)
        static_pad = None
        padded_cells = []
        for arrs, static in prepared:
            padded, static_pad = transport_mod.pad_prepared(
                arrs, static, n_flows=n_flows, n_edges=n_edges,
                hop_slots=hop_slots)
            padded_cells.append(padded)
        del prepared

        n_dev = 1 if rt.mesh is None else rt.fsdp_size
        seed_counts = {len(w.sim_seeds) for w in works}
        # Nest only when the OUTER (cell) axis can still feed the mesh:
        # sharding happens over whatever axis the program batches, so a
        # 6-cell x 8-seed bucket on an 8-device mesh must use the flat
        # 48-element layout (duplicated operands, full parallelism), not 6
        # nested units serialized onto one device.
        nest_seeds = (seed_counts == {max(seed_counts)}
                      and max(seed_counts) > 1
                      and (n_dev == 1 or len(works) >= n_dev))
        if nest_seeds:
            # units = cells; keys (C, S, 2); operands one copy per cell.
            units = list(padded_cells)
            key_rows = [[jax.random.PRNGKey(s) for s in w.sim_seeds]
                        for w in works]
            scan, sharded = _vmapped_scan_seeds, _sharded_scan_seeds
        else:
            # units = (cell, seed) elements; operands duplicated per seed.
            units, key_rows = [], []
            for w, padded in zip(works, padded_cells):
                for s in w.sim_seeds:
                    units.append(padded)
                    key_rows.append(jax.random.PRNGKey(s))
            scan, sharded = _vmapped_scan, _sharded_scan
        elements = [(wi, s) for wi, w in enumerate(works)
                    for s in w.sim_seeds]

        n_real = len(units)
        use_shard_map = rt.mesh is not None and n_real >= n_dev
        if use_shard_map:
            while len(units) % n_dev:   # pad the unit axis to the mesh size
                units.append(units[0])
                key_rows.append(key_rows[0])

        # Counted in the bucket's record, which every member cell's
        # meta carries: once per cell.
        transport_mod.count_waterfill_path(cfg0)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *units)
        keys = jnp.asarray(np.stack([np.asarray(k) for k in key_rows]))

    with tracing.span("sweep.dispatch"):
        if rt.mesh is None:
            finals = scan(stacked, keys, cfg0, static_pad)
            mode = "vmap"
        elif use_shard_map:
            finals = sharded(rt, cfg0, static_pad)(stacked, keys)
            mode = f"shard_map[{n_dev}]"
        else:
            dev = rt.mesh.devices.flat[bucket_index % n_dev]
            stacked = jax.device_put(stacked, dev)
            keys = jax.device_put(keys, dev)
            finals = scan(stacked, keys, cfg0, static_pad)
            mode = f"device[{bucket_index % n_dev}]"
    mode += "+seednest" if nest_seeds else ""
    return finals, (elements, nest_seeds), mode, (n_flows, n_edges,
                                                  hop_slots)


@tracing.span("sweep.collect")
def _finalize_bucket(works: List[_Work], finals, elements
                     ) -> Dict[int, list]:
    """Block on one bucket's device results and split them back into
    per-cell, per-seed :class:`SimResult`s (padding stripped).  Nested
    seed batches come back as (C, S, ...) leaves; flattening them
    cell-major matches the flat ``elements`` order exactly."""
    elements, nested = elements
    n_elem = len(elements)

    def flat(v):
        v = np.asarray(v)
        if nested:                                    # (C, S, ...) leaves
            v = v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])
        return v[:n_elem]

    finals = {k: flat(v)
              for k, v in jax.block_until_ready(finals).items()}
    sims: Dict[int, list] = {wi: [] for wi in range(len(works))}
    for i, (wi, s) in enumerate(elements):
        w = works[wi]
        sims[wi].append(transport_mod.batch_result(
            w.size, {k: v[i] for k, v in finals.items()},
            dataclasses.replace(w.cfg, seed=s), n_flows=w.n_flows,
            start=w.start))
    return sims


def dist_sweep(session: Session, cells: List[ExperimentSpec], *,
               devices: Optional[int] = None,
               runtime: Optional[Runtime] = None,
               checkpoint_dir: Optional[str] = None,
               callback: Optional[Callable[[RunResult], None]] = None,
               log: Optional[Callable[[str], None]] = None
               ) -> List[RunResult]:
    """Run ``cells`` through the batched engine (module docstring).

    ``devices=None`` or ``1`` runs the same bucketed/padded programs on
    one device; results are identical for every device count.  The
    returned list is in the order of ``cells`` (canonical grid order).
    """
    rt = runtime if runtime is not None else host_device_runtime(
        devices if devices is not None else 1)
    ckpt = SweepCheckpoint(checkpoint_dir) if checkpoint_dir else None
    say = log if log is not None else (lambda _msg: None)

    def emit(rr: RunResult, done_via_ckpt: bool = False,
             persist: bool = True) -> RunResult:
        # Error/quarantined cells pass persist=False: they must NOT be
        # checkpointed, so a checkpoint resume re-attempts them.
        if ckpt is not None and not done_via_ckpt and persist:
            ckpt.put(rr.cell_id, rr.to_dict())
        if callback is not None:
            callback(rr)
        return rr

    results: List[RunResult] = []
    batched: List[_Work] = []
    n_resumed = 0
    for spec in cells:
        if ckpt is not None:
            prev = ckpt.get(spec.cell_id)
            if prev is not None:
                rr = RunResult.from_dict(prev)
                rr = dataclasses.replace(
                    rr, meta={**rr.meta, "sweep_resumed": True})
                results.append(emit(rr, done_via_ckpt=True))
                n_resumed += 1
                continue
        _, kw = EVALUATORS.resolve(spec.evaluator)
        if spec.evaluator.name != "transport":
            # mat / fabric / custom evaluators: sequential fallback.
            results.append(emit(session.run(spec)))
            continue
        # The cell's own record holds its resolve and plan; its share of
        # the simulation is in its bucket's record.
        with tracing.record(spec.cell_id) as rec, tracing.span("cell"):
            cell = session.resolve(spec)
            cfg, sim_seeds = transport_plan(cell, **kw)
            n_flows, e_tot, n_layers = transport_mod.shape_signature(
                cell.topo, cell.bundle.routing, cell.workload)
            ev_meta = transport_meta(cell, cfg, sim_seeds)
        batched.append(_Work(
            spec=spec, cell=cell, cfg=cfg, sim_seeds=sim_seeds,
            n_flows=n_flows, e_tot=e_tot, n_layers=n_layers,
            ev_meta=ev_meta, rec=rec))
    if n_resumed:
        say(f"# resumed {n_resumed} completed cell(s) from checkpoint")

    buckets: Dict[tuple, List[_Work]] = {}
    for w in batched:
        has_lds = getattr(w.cell.bundle.routing, "link_down_step",
                          None) is not None
        lc = getattr(w.cell.bundle.routing, "link_churn", None)
        buckets.setdefault(
            padded_signature(w.cfg, w.n_layers, w.n_flows, w.e_tot,
                             link_down=has_lds,
                             churn_k=0 if lc is None else int(lc.shape[2])),
            []).append(w)

    # Dispatch ahead of finalize: jax dispatch is async, so small
    # buckets placed on different devices (and shard_mapped big ones)
    # execute concurrently while the host pads/stacks the next buckets.
    # The dispatch window is BOUNDED (a few buckets beyond the mesh
    # size): an unbounded launch-everything-first loop would hold every
    # bucket's stacked device operands live at once, scaling peak
    # memory with the whole grid instead of the window.
    n_dev = max(1, rt.fsdp_size)
    max_in_flight = max(4, 2 * n_dev)
    in_flight: List[tuple] = []
    n_buckets = n_elems = 0

    def emit_error(bi: int, w: _Work, error: Dict[str, Any]):
        # Structured quarantine record: empty metrics, the failure in
        # meta, never checkpointed (a resume re-attempts the cell).
        results.append(emit(session.finish_result(
            w.spec, w.cell, {}, w.ev_meta, w.rec, w.rec.seconds("cell"),
            extra_meta={"sweep_bucket": bi, "error": error}),
            persist=False))

    def quarantine(bi: int, works: List[_Work], e: Exception):
        say(f"# bucket {bi}: batched execution failed "
            f"({type(e).__name__}); quarantining {len(works)} cell(s)")
        for w in works:
            emit_error(bi, w, {"type": "bucket_failure",
                               "exception": type(e).__name__,
                               "message": str(e)[:500]})

    def finalize(bi, works, finals, desc, brec):
        try:
            with brec:
                sims = _finalize_bucket(works, finals, desc)
        except Exception as e:                      # noqa: BLE001
            quarantine(bi, works, e)
            return
        # From the bucket's dispatch to the end of its collect.
        bucket_wall = brec.wall_s
        for wi, w in enumerate(works):
            bad = [r for r in sims[wi]
                   if not (np.all(np.isfinite(r.delivered))
                           and np.isfinite(r.link_util_mean))]
            if bad:
                say(f"# bucket {bi}: non-finite simulation state for "
                    f"{w.spec.cell_id}; quarantining")
                emit_error(bi, w, {"type": "nonfinite",
                                   "seeds_bad": len(bad)})
                continue
            with w.rec:
                metrics = fct_metrics(sims[wi])
            wall = w.rec.seconds("cell") + bucket_wall * (
                len(w.sim_seeds) / max(1, len(desc[0])))
            results.append(emit(session.finish_result(
                w.spec, w.cell, metrics, w.ev_meta, w.rec, wall,
                extra_meta={"sweep_bucket": bi, **sim_meta(sims[wi])},
                shared=(brec,))))

    with tracing.record() as sim_rec:
        for bi, works in enumerate(buckets.values()):
            brec = tracing.record(f"sweep bucket {bi}")
            n_buckets += 1
            try:
                with brec:
                    finals, desc, mode, (nf, ne, nh) = _dispatch_bucket(
                        works, rt, bi)
            except Exception as e:                  # noqa: BLE001
                quarantine(bi, works, e)
                continue
            say(f"# bucket {bi}: {len(works)} cells x seeds = "
                f"{len(desc[0])} programs via {mode}, padded to F={nf} "
                f"E={ne} H={nh}")
            in_flight.append((bi, works, finals, desc, brec))
            n_elems += len(desc[0])
            while len(in_flight) > max_in_flight:
                finalize(*in_flight.pop(0))
        while in_flight:
            finalize(*in_flight.pop(0))
    if n_buckets:
        say(f"# {n_buckets} bucket(s), {n_elems} batched programs, "
            f"simulate wall {sim_rec.wall_s:.2f}s on {n_dev} device(s)")

    return order_results(results, [c.cell_id for c in cells])
