"""Fused water-filling transport step: Pallas kernel (interpret=True) vs
jnp oracle, feasibility invariants, and the adaptive scan horizon's
early-exit == full-horizon guarantee."""

import dataclasses
import os

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest still run
    from _hypothesis_stub import given, settings, st  # noqa: F401

import jax
import jax.numpy as jnp

from repro.kernels import kernel_backend, ref
from repro.kernels.waterfill import waterfill_step

# Ragged (F, S, E) instances: tile multiples AND odd remainders in both
# the flow and link grid dimensions (kernel tiles are bf=128, be=512).
SHAPES = [(7, 3, 19), (128, 7, 512), (200, 7, 751), (1, 5, 33),
          (130, 9, 513), (256, 4, 1024)]


def _instance(f, s, e, seed, idle_frac=0.25):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, e - 1, (f, s)).astype(np.int32)
    edges[rng.random((f, s)) < 0.3] = e - 1          # trash-padded slots
    w = (rng.random(f) >= idle_frac).astype(np.float32)
    edges[w == 0] = e - 1                            # inert flows: all trash
    desired = rng.random(f).astype(np.float32) * w
    cap = np.ones(e, np.float32)
    return (jnp.asarray(edges), jnp.asarray(w), jnp.asarray(desired),
            jnp.asarray(cap))


@pytest.mark.parametrize("f,s,e", SHAPES)
@pytest.mark.parametrize("fair_iters", [0, 1, 2])
def test_kernel_matches_oracle(f, s, e, fair_iters):
    edges, w, desired, cap = _instance(f, s, e, seed=f * s + e)
    sent, share = waterfill_step(edges, w, desired, cap,
                                 fair_iters=fair_iters, backend="pallas",
                                 interpret=True)
    sent_r, share_r = ref.waterfill_ref(edges, w, desired, cap,
                                        fair_iters=fair_iters)
    np.testing.assert_allclose(np.asarray(sent), np.asarray(sent_r),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(share), np.asarray(share_r),
                               rtol=1e-5)


@pytest.mark.parametrize("f,s,e", [(7, 3, 19), (130, 9, 513)])
def test_active_lane_matches_oracle_and_masking(f, s, e):
    """The dynamic-traffic active lane: kernel == oracle under a mixed
    active mask, inactive rows send nothing and see an uncongested
    network (+inf share), and active=all-True == active=None bitwise.
    Raw -1 walk padding in the edge tensor must be tolerated."""
    edges, w, desired, cap = _instance(f, s, e, seed=e, idle_frac=0.0)
    edges = np.array(edges)               # writable copy
    rng = np.random.default_rng(5)
    edges[rng.random((f, s)) < 0.2] = -1          # raw walk padding
    edges = jnp.asarray(edges)
    active = jnp.asarray(rng.random(f) < 0.6)
    sent_k, share_k = waterfill_step(edges, w, desired, cap,
                                     active=active, backend="pallas",
                                     interpret=True)
    sent_r, share_r = ref.waterfill_ref(edges, w, desired, cap,
                                        active=active)
    np.testing.assert_allclose(np.asarray(sent_k), np.asarray(sent_r),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(share_k), np.asarray(share_r),
                               rtol=1e-5)
    inact = ~np.asarray(active)
    assert (np.asarray(sent_r)[inact] == 0).all()
    assert np.isposinf(np.asarray(share_r)[inact]).all()
    # all-active lane is bitwise the no-lane path (closed-loop reduction)
    e2 = jnp.where(edges >= 0, edges, e - 1)
    s_all, sh_all = ref.waterfill_ref(e2, w, desired, cap,
                                      active=jnp.ones(f, bool))
    s_none, sh_none = ref.waterfill_ref(e2, w, desired, cap)
    np.testing.assert_array_equal(np.asarray(s_all), np.asarray(s_none))
    np.testing.assert_array_equal(np.asarray(sh_all), np.asarray(sh_none))


def _link_load(edges, sent, e):
    load = np.zeros(e)
    np.add.at(load, np.asarray(edges).reshape(-1),
              np.repeat(np.asarray(sent), edges.shape[1]))
    load[e - 1] = 0.0                    # trash slot is write-only
    return load


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_never_oversubscribes(backend):
    """After the refinement iterations no link carries more than its
    capacity — the simulator's feasibility-by-construction invariant."""
    for seed in range(5):
        edges, w, desired, cap = _instance(160, 6, 301, seed=seed,
                                           idle_frac=0.1)
        sent, _ = waterfill_step(edges, w, desired, cap, fair_iters=2,
                                 backend=backend, interpret=True)
        load = _link_load(edges, sent, 301)
        assert (load <= np.asarray(cap) + 1e-4).all(), load.max()
        # and sends never exceed what was asked for
        assert (np.asarray(sent) <= np.asarray(desired) + 1e-6).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 200), st.integers(2, 10), st.integers(3, 400),
       st.integers(0, 10_000))
def test_oversubscription_property(f, s, e, seed):
    edges, w, desired, cap = _instance(f, s, e, seed=seed)
    sent, share = ref.waterfill_ref(edges, w, desired, cap, fair_iters=2)
    load = _link_load(edges, sent, e)
    assert (load <= np.asarray(cap) + 1e-4).all()
    # the fair-share signal is positive wherever a flow actually sends
    sent = np.asarray(sent)
    assert (np.asarray(share)[sent > 0] > 0).all()


def _tiny_cell(balancing="fatpaths", topo_spec="clique(k=6)"):
    from repro.core import transport as TP
    from repro.experiments import Session

    s = Session()
    topo = s.topology(topo_spec)
    scheme = {"fatpaths": "fatpaths(n_layers=3)", "ecmp": "ecmp(n=2)",
              "letflow": "letflow(n=2)"}[balancing]
    bundle = s.routing(topo_spec, scheme)
    wl = s.workload(topo_spec, "uniform")
    return TP, topo, bundle, wl


@pytest.mark.parametrize("transport", ["ndp", "tcp", "dctcp"])
def test_sim_kernel_backend_parity(transport):
    """The full simulator agrees between the fused Pallas step
    (interpret=True on CPU) and the jnp oracle, for every transport."""
    TP, topo, bundle, wl = _tiny_cell()
    mk = lambda be: TP.SimConfig(  # noqa: E731
        transport=transport, balancing=bundle.balancing, n_steps=30,
        kernel_backend=be)
    res_ref = TP.simulate(topo, bundle.routing, wl, mk("ref"))
    res_pl = TP.simulate(topo, bundle.routing, wl, mk("pallas"))
    np.testing.assert_allclose(res_pl.fct, res_ref.fct, rtol=1e-5)
    np.testing.assert_allclose(res_pl.delivered, res_ref.delivered,
                               rtol=1e-4)
    np.testing.assert_array_equal(res_pl.finished, res_ref.finished)


# ---- adaptive horizon -------------------------------------------------------
@pytest.mark.parametrize("balancing", ["fatpaths", "ecmp"])
def test_early_exit_equals_full_horizon(balancing):
    """A cell whose flows all finish early must return results
    bit-identical to the full-horizon run — and must actually exit early
    (fewer than all scan chunks executed)."""
    TP, topo, bundle, wl = _tiny_cell(balancing)
    mk = lambda ad: TP.SimConfig(  # noqa: E731
        balancing=bundle.balancing, n_steps=400, horizon_chunk=32,
        adaptive_horizon=ad)
    jarrs, static = TP.prepare(topo, bundle.routing, wl, mk(True))
    key = jax.random.PRNGKey(3)
    fin_ad = jax.device_get(TP._run_scan(jarrs, key, mk(True), static))
    fin_fl = jax.device_get(TP._run_scan(jarrs, key, mk(False), static))
    assert int(fin_ad["horizon_chunks"]) < int(fin_fl["horizon_chunks"])
    for k in ("remaining", "hops", "sent_acc", "w_acc", "depart_step"):
        np.testing.assert_array_equal(fin_ad[k], fin_fl[k], err_msg=k)
    ra = TP._to_result(np.asarray(jarrs["size"]), fin_ad, mk(True))
    rf = TP._to_result(np.asarray(jarrs["size"]), fin_fl, mk(False))
    np.testing.assert_array_equal(ra.fct, rf.fct)
    assert ra.link_util_mean == rf.link_util_mean
    assert ra.finished.all()


def test_early_exit_on_provably_stuck_flows():
    """Unroutable (weight-0 forever) flows must not pin the horizon: a
    cell whose remaining flows can never route exits early with state
    identical to the full run."""
    TP, topo, bundle, wl = _tiny_cell("fatpaths")
    cfg = TP.SimConfig(balancing="fatpaths", n_steps=320, horizon_chunk=32)
    jarrs, static = TP.prepare(topo, bundle.routing, wl, cfg)
    # Make half the flows unroutable in EVERY layer (routed=False and
    # usable=False => they can only ever pick non-routing layers).
    f = jarrs["size"].shape[0]
    sick = jnp.arange(f) % 2 == 0
    jarrs = dict(jarrs,
                 routed=jarrs["routed"] & ~sick[None, :],
                 usable=jarrs["usable"] & ~sick[:, None])
    key = jax.random.PRNGKey(0)
    cfg_f = dataclasses.replace(cfg, adaptive_horizon=False)
    fin_ad = jax.device_get(TP._run_scan(jarrs, key, cfg, static))
    fin_fl = jax.device_get(TP._run_scan(jarrs, key, cfg_f, static))
    assert int(fin_ad["horizon_chunks"]) < int(fin_fl["horizon_chunks"])
    for k in ("remaining", "hops", "sent_acc", "w_acc", "depart_step"):
        np.testing.assert_array_equal(fin_ad[k], fin_fl[k], err_msg=k)
    # stuck flows really never went anywhere
    assert (fin_ad["remaining"][np.asarray(sick)] ==
            np.asarray(jarrs["size"])[np.asarray(sick)]).all()


def test_active_flows_pin_the_horizon():
    """Slow-but-routable flows (incast) keep the scan running: adaptive
    and full horizons execute the same chunk count."""
    from repro.core import traffic as TR
    TP, topo, bundle, _ = _tiny_cell("fatpaths")
    wl = TR.make_workload(topo, "alltoone", seed=1,
                          flow_size=float(1 << 30))   # never finishes
    cfg = TP.SimConfig(balancing="fatpaths", n_steps=128, horizon_chunk=32)
    jarrs, static = TP.prepare(topo, bundle.routing, wl, cfg)
    fin = jax.device_get(TP._run_scan(jarrs, jax.random.PRNGKey(0), cfg,
                                      static))
    assert int(fin["horizon_chunks"]) == 128 // 32


# ---- backend selection ------------------------------------------------------
def test_kernel_backend_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert kernel_backend() == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    assert kernel_backend() == "ref"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    monkeypatch.delenv("REPRO_SEMIRING_BACKEND", raising=False)
    assert kernel_backend() in ("pallas", "ref")     # auto


def test_env_override_selects_the_scan_program(monkeypatch):
    """The scan's jit-static config carries the resolved backend, so
    flipping REPRO_KERNEL_BACKEND between two cells of one process runs
    a different program instead of reusing the first one."""
    TP, topo, bundle, wl = _tiny_cell()
    cfg = TP.SimConfig(balancing=bundle.balancing, n_steps=34, seed=7)
    assert TP.static_config(dataclasses.replace(
        cfg, kernel_backend="pallas")).kernel_backend == "pallas"
    runs = {}
    for be in ("ref", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", be)
        assert TP.static_config(cfg) == dataclasses.replace(
            cfg, seed=0, kernel_backend=be)
        before = TP._run_scan._cache_size()
        runs[be] = TP.simulate(topo, bundle.routing, wl, cfg)
        assert TP._run_scan._cache_size() == before + 1, be
    np.testing.assert_allclose(runs["pallas"].fct, runs["ref"].fct,
                               rtol=1e-5)


def test_semiring_backend_env_is_deprecated_alias(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setenv("REPRO_SEMIRING_BACKEND", "pallas")
    with pytest.warns(DeprecationWarning, match="REPRO_KERNEL_BACKEND"):
        assert kernel_backend() == "pallas"
    # the explicit new var wins over the alias
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    assert kernel_backend() == "ref"
    # semiring's public default_backend rides the same helper
    from repro.kernels.semiring import default_backend
    assert default_backend() == "ref"


def test_unknown_backend_values_fall_through(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "cuda")
    monkeypatch.delenv("REPRO_SEMIRING_BACKEND", raising=False)
    assert kernel_backend() in ("pallas", "ref")


def test_explicit_unknown_backend_rejected():
    edges, w, desired, cap = _instance(8, 3, 17, seed=0)
    with pytest.raises(ValueError, match="unknown backend"):
        waterfill_step(edges, w, desired, cap, backend="jnp")
