"""Distributed sweep engine: padding exactness, engine identity with the
sequential path, checkpoint resume, stable ordering; the 8-device case
runs in a subprocess (keeps this session single-device)."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.experiments import (ExperimentSpec, RunResult, Session,
                               compare_results, order_results)
from repro.experiments.dist_sweep import bucket_signature, dist_sweep
from repro.experiments.results import EXECUTION_META_KEYS

GRID = dict(topos=["clique(k=6)", "star(n=8)"],
            routings=["ecmp(n=2)", "fatpaths(n_layers=3)"],
            patterns=["uniform"],
            evaluators=["transport(steps=40)"], seeds=[0, 1])


# ---- padding exactness ------------------------------------------------------
def test_pad_prepared_is_bitwise_exact():
    """A cell simulated standalone == the same cell padded (flows, links,
    hop slots) and run inside a vmapped batch — bit for bit, every
    SimResult field.  This is the invariant the whole engine rests on."""
    _assert_padding_exact(None)


def test_pad_prepared_is_bitwise_exact_on_the_tpu_path():
    """The same invariant on the TPU's water-filling (the link-sorted
    form, run by XLA on the CPU): padding adds inactive flows, trash
    slots and idle links, which leave every real link's segment and its
    summation order as they were."""
    _assert_padding_exact("pallas")


def _assert_padding_exact(kernel_backend):
    import jax
    import jax.numpy as jnp

    from repro.core import transport as TP

    s = Session()
    topo = s.topology("clique(k=6)")
    bundle = s.routing("clique(k=6)", "fatpaths(n_layers=3)")
    wl = s.workload("clique(k=6)", "uniform")
    cfg = TP.SimConfig(balancing=bundle.balancing, n_steps=50,
                       kernel_backend=kernel_backend)
    base = TP.simulate(topo, bundle.routing, wl, cfg)

    arrs, static = TP.prepare(topo, bundle.routing, wl, cfg)
    F = arrs["size"].shape[0]
    padded, pstatic = TP.pad_prepared(
        arrs, static, n_flows=F + 13, n_edges=static[0] + 7,
        hop_slots=arrs["path_edges"].shape[2] + 2)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    keys = keys.at[1].set(jax.random.PRNGKey(cfg.seed))   # element 1 = cell
    stacked = {k: jnp.stack([v] * 3) for k, v in padded.items()}
    finals = jax.jit(jax.vmap(
        lambda a, k: TP._run_scan_impl(a, k, cfg, pstatic)))(stacked, keys)
    got = TP.batch_result(np.asarray(arrs["size"]),
                          {k: np.asarray(v)[1] for k, v in finals.items()},
                          cfg, n_flows=F)
    np.testing.assert_array_equal(got.fct, base.fct)
    np.testing.assert_array_equal(got.delivered, base.delivered)
    np.testing.assert_array_equal(got.finished, base.finished)
    assert got.link_util_mean == base.link_util_mean


def test_pad_prepared_rejects_shrinking():
    from repro.core import transport as TP

    s = Session()
    cell = s.resolve(ExperimentSpec.make("clique(k=6)", "ecmp(n=2)",
                                         "uniform", "transport(steps=10)"))
    cfg = TP.SimConfig(balancing="ecmp", n_steps=10)
    arrs, static = TP.prepare(cell.topo, cell.bundle.routing, cell.workload,
                              cfg)
    with pytest.raises(ValueError, match="smaller than cell"):
        TP.pad_prepared(arrs, static, n_flows=1, n_edges=static[0],
                        hop_slots=arrs["path_edges"].shape[2])


def test_bucket_signature_keys_scheme_and_layers():
    from repro.core.transport import SimConfig

    a = SimConfig(balancing="fatpaths", n_steps=40, seed=3)
    b = SimConfig(balancing="fatpaths", n_steps=40, seed=9)
    c = SimConfig(balancing="ecmp", n_steps=40, seed=3)
    assert bucket_signature(a, (10, 5, 40)) == bucket_signature(b, (99, 5, 40))
    assert bucket_signature(a, (10, 5, 40)) != bucket_signature(c, (10, 5, 40))
    assert bucket_signature(a, (10, 5, 40)) != bucket_signature(b, (10, 6, 40))


# ---- engine identity --------------------------------------------------------
def test_dist_sweep_matches_sequential_cell_for_cell():
    seq = Session().sweep(**GRID)
    s = Session()
    cells = s.grid(**GRID)
    dist = dist_sweep(s, cells, devices=1)
    assert [r.cell_id for r in dist] == [c.cell_id for c in cells]
    assert compare_results(seq, dist) == []


def test_dist_sweep_matches_sequential_on_the_tpu_path(monkeypatch):
    """dist_sweep pads each cell to its bucket's flows, links and hop
    slots; on the TPU's water-filling (the link-sorted form, run by XLA
    on the CPU) every cell still equals its sequential run bit for
    bit, and each counts that form once."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    seq = Session().sweep(**GRID)
    s = Session()
    logs = []
    dist = dist_sweep(s, s.grid(**GRID), devices=1, log=logs.append)
    assert compare_results(seq, dist) == []
    assert any(" programs via " in m for m in logs)
    for rr in seq + dist:
        forms = {k: v for k, v in rr.meta["counts"].items()
                 if k.startswith("waterfill.")}
        assert forms == {"waterfill.sorted": 1}, rr.cell_id


def test_dist_sweep_seed_sweep_shares_operands():
    """transport(seeds=S) cells take the nested-vmap path (one operand
    copy per cell, inner vmap over sim-seed keys) — still identical to
    the sequential engine, cell for cell."""
    grid = dict(topos=["clique(k=6)", "star(n=8)"],
                routings=["fatpaths(n_layers=3)", "letflow(n=2)"],
                patterns=["uniform"],
                evaluators=["transport(steps=40,seeds=3)"], seeds=[0])
    seq = Session().sweep(**grid)
    s = Session()
    logs = []
    dist = dist_sweep(s, s.grid(**grid), devices=1, log=logs.append)
    assert compare_results(seq, dist) == []
    assert any("seednest" in m for m in logs)
    assert all(r.meta["n_seeds"] == 3 for r in dist)


def test_dist_sweep_mixed_evaluators_fall_back():
    """mat/fabric cells run sequentially inside the same sweep and keep
    canonical ordering interleaved with batched transport cells."""
    grid = dict(topos=["clique(k=6)"], routings=["fatpaths(n_layers=3)"],
                patterns=["uniform"],
                evaluators=["transport(steps=40)", "mat"], seeds=[0])
    seq = Session().sweep(**grid)
    s = Session()
    dist = dist_sweep(s, s.grid(**grid), devices=1)
    assert compare_results(seq, dist) == []
    assert [r.evaluator for r in dist] == ["transport(steps=40)", "mat"]


def test_sweep_devices_kwarg_routes_to_engine():
    got = Session().sweep(devices=1, **GRID)
    seq = Session().sweep(**GRID)
    assert compare_results(seq, got) == []


# ---- resumable sweeps -------------------------------------------------------
def test_checkpoint_resume_skips_completed_cells(tmp_path):
    ckdir = str(tmp_path / "ck")
    s1 = Session()
    cells = s1.grid(**GRID)
    part = dist_sweep(s1, cells[:3], devices=1, checkpoint_dir=ckdir)
    assert len(part) == 3
    assert len([f for f in os.listdir(ckdir) if f.endswith(".json")]) == 3

    s2 = Session()
    streamed = []
    full = dist_sweep(s2, cells, devices=1, checkpoint_dir=ckdir,
                      callback=lambda rr: streamed.append(rr.cell_id))
    assert len(full) == len(cells) == len(streamed)
    resumed = [r for r in full if r.meta.get("sweep_resumed")]
    assert len(resumed) == 3
    # resumed cells were NOT re-simulated: no artifact builds for them
    fresh = Session().sweep(**GRID)
    assert compare_results(fresh, full) == []
    # the full sweep's results come back in canonical grid order
    assert [r.cell_id for r in full] == [c.cell_id for c in cells]


FAIL_GRID = dict(
    topos=["clique(k=6)"],
    routings=["failures(of=fatpaths(n_layers=3),rate=0.1)",
              "failures(of=fatpaths(n_layers=3),rate=0.3,mode=drop)",
              "failures(of=fatpaths(n_layers=3),rate=0.2,down_step=15)",
              "fatpaths(n_layers=3)"],
    patterns=["uniform"], evaluators=["transport(steps=40)"], seeds=[0])


def _artifact_bytes(results):
    """The sweep artifact as CI would diff it: execution-dependent
    fields (walls, build accounting, batch bookkeeping) stripped."""
    dicts = []
    for r in results:
        d = r.to_dict()
        d.pop("wall_s")
        for k in EXECUTION_META_KEYS:
            d["meta"].pop(k, None)
        dicts.append(d)
    return json.dumps(dicts, indent=1, sort_keys=True).encode()


def test_checkpoint_resume_failure_grid_byte_identical(tmp_path):
    """Interrupting a degraded-fabric sweep mid-grid and resuming yields
    an artifact BYTE-identical to the uninterrupted sweep — failure
    scenarios (static repair, static drop, mid-run death) checkpoint and
    resume like any other cell."""
    ckdir = str(tmp_path / "ck")
    s1 = Session()
    cells = s1.grid(**FAIL_GRID)
    part = dist_sweep(s1, cells[:2], devices=1, checkpoint_dir=ckdir)
    assert len(part) == 2

    s2 = Session()
    full = dist_sweep(s2, cells, devices=1, checkpoint_dir=ckdir)
    assert len([r for r in full if r.meta.get("sweep_resumed")]) == 2

    s3 = Session()
    uninterrupted = dist_sweep(s3, s3.grid(**FAIL_GRID), devices=1)
    assert compare_results(uninterrupted, full) == []
    assert _artifact_bytes(full) == _artifact_bytes(uninterrupted)
    # damage accounting survives the checkpoint round-trip
    for r in full:
        if r.routing.startswith("failures"):
            assert "disconnected_pairs" in r.meta
            assert "dead_layers" in r.meta


def test_checkpoint_ignores_torn_files(tmp_path):
    from repro.ckpt import SweepCheckpoint

    ck = SweepCheckpoint(str(tmp_path))
    ck.put("a/b/c@s0", {"topo": "a"})
    with open(os.path.join(str(tmp_path), "cell_deadbeef.json"), "w") as f:
        f.write('{"cell_id": "x"')          # torn write, no rename
    assert ck.load() == {"a/b/c@s0": {"topo": "a"}}
    assert "a/b/c@s0" in ck and len(ck) == 1
    assert ck.get("missing") is None


# ---- results helpers --------------------------------------------------------
def _rr(cell="t/r/p/e@s0", **over):
    d = dict(topo="t", routing="r", pattern="p", evaluator="e", seed=0,
             metrics={"m": 1.0}, meta={"k": 2, "build_s": 0.5}, wall_s=1.0)
    d.update(over)
    return RunResult(**d)


def test_order_results_restores_canonical_order():
    a, b = _rr(routing="r1"), _rr(routing="r2")
    assert order_results([b, a], [a.cell_id, b.cell_id]) == [a, b]
    with pytest.raises(KeyError, match="no result"):
        order_results([a], [a.cell_id, b.cell_id])
    with pytest.raises(KeyError, match="unplanned"):
        order_results([a, b], [a.cell_id])


def test_compare_results_ignores_execution_meta():
    a = _rr()
    b = dataclasses.replace(a, wall_s=99.0,
                            meta={**a.meta, "build_s": 7.0,
                                  "sweep_bucket": 3, "sweep_resumed": True})
    assert compare_results([a], [b]) == []
    c = dataclasses.replace(a, metrics={"m": 1.0 + 1e-9})
    assert compare_results([a], [c]) != []          # exact by default
    assert compare_results([a], [c], rtol=1e-6) == []
    d = dataclasses.replace(a, meta={**a.meta, "k": 3})
    assert any("meta[k]" in x for x in compare_results([a], [d]))
    e = dataclasses.replace(a, routing="other")
    assert any("cell sets differ" in x for x in compare_results([a], [e]))


# ---- mesh helper ------------------------------------------------------------
def test_host_device_runtime_degrades_and_errors():
    from repro.dist import host_device_runtime

    rt = host_device_runtime(1)
    assert rt.mesh is None
    with pytest.raises(RuntimeError, match="XLA_FLAGS"):
        host_device_runtime(8)          # this session is single-device


# ---- the 8-device case (subprocess: forced host devices) --------------------
# steps=200 > horizon_chunk, so the batched while_loop (adaptive early
# exit) runs UNDER shard_map and must still match the sequential engine.
_PROG = textwrap.dedent("""
    from repro.experiments import Session, compare_results
    from repro.experiments.dist_sweep import dist_sweep
    import jax
    assert jax.device_count() == 8, jax.device_count()
    grid = dict(topos=["clique(k=6)", "star(n=8)"],
                routings=["ecmp(n=2)", "fatpaths(n_layers=3)",
                          "failures(of=fatpaths(n_layers=3),rate=0.2,down_step=60)"],
                patterns=["uniform", "load(level=0.4,window=96)"],
                evaluators=["transport(steps=200)"],
                seeds=[0])
    seq = Session().sweep(**grid)
    s8 = Session()
    d8 = dist_sweep(s8, s8.grid(**grid), devices=8)
    diffs = compare_results(seq, d8)
    assert diffs == [], diffs[:5]
    assert any("offered_gbs" in r.meta for r in d8)  # dynamic cells batched
    assert any("failed_links" in r.meta for r in d8)  # degraded cells batched
    chunks = [r.meta["sweep_chunks"] for r in d8
              if r.pattern.startswith("uniform")
              and not r.routing.startswith("failures")]
    assert all(c < 200 // 64 for c in chunks), chunks   # early exit fired
    print("DIST8_OK")
""")


def test_dist_sweep_8_devices_identical():
    r = subprocess.run(
        [sys.executable, "-c", _PROG],
        capture_output=True, text=True, timeout=600,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "DIST8_OK" in r.stdout, r.stderr[-2000:]
