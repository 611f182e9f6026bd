"""Water-filling transport step: the TPU path (the link-sorted form) vs
the jnp oracle, feasibility invariants, and the adaptive scan horizon's
early-exit == full-horizon guarantee."""

import dataclasses
import functools
import os

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest still run
    from _hypothesis_stub import given, settings, st  # noqa: F401

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import kernel_backend, ref
from repro.kernels import waterfill as WF
from repro.kernels.waterfill import waterfill_path, waterfill_step

# Ragged (F, S, E) instances: flow and link counts at and off multiples
# of 128 and 512, odd slot counts, a single flow.
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
                                 fair_iters=fair_iters, backend="pallas")
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
                                     active=active, backend="pallas")
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
                                 backend=backend)
        load = _link_load(edges, sent, 301)
        assert (load <= np.asarray(cap) + 1e-4).all(), load.max()
        # and sends never exceed what was asked for
        assert (np.asarray(sent) <= np.asarray(desired) + 1e-6).all()


def _lane_instance(lane, f=130, s=9, e=513, seed=11):
    """Inputs of one step exercising ``lane``: raw -1 padding under a
    mixed active mask in every case, plus links at capacity 0
    (``dead``, link death and churn) or rows with no live slot at all
    (``all_trash``: active flows whose slots are all -1 or trash)."""
    edges, w, desired, cap = _instance(f, s, e, seed=seed, idle_frac=0.0)
    rng = np.random.default_rng(seed)
    edges = np.array(edges)
    edges[rng.random((f, s)) < 0.2] = -1
    active = rng.random(f) < 0.7
    cap = np.array(cap)
    if lane == "dead":
        cap[:-1][rng.random(e - 1) < 0.15] = 0.0
    if lane == "all_trash":
        edges[::5] = -1
        edges[1::7] = e - 1
        active[::5] = True
    return (jnp.asarray(edges), w, desired, jnp.asarray(cap),
            jnp.asarray(active))


def _assert_step_close(got, want):
    """Kernel-vs-oracle tolerances; +inf shares must match exactly."""
    for g, r in zip(got, want):
        g, r = np.asarray(g), np.asarray(r)
        fin = np.isfinite(r)
        np.testing.assert_array_equal(np.isfinite(g), fin)
        np.testing.assert_array_equal(g[~fin], r[~fin])
        np.testing.assert_allclose(g[fin], r[fin], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("lane", ["want_util", "dead", "all_trash", "vmap"])
@pytest.mark.parametrize("fair_iters", [0, 1, 2])
def test_lanes_match_oracle(lane, fair_iters):
    """The TPU form against the oracle on the scan's lanes: the ECN
    lane's util output, capacity-0 links, rows with no live slot, and a
    vmap-batched call (the scan runs the step under vmap)."""
    want_util = lane in ("want_util", "vmap")
    step = functools.partial(waterfill_step, fair_iters=fair_iters,
                             backend="pallas", want_util=want_util)
    oracle = functools.partial(ref.waterfill_ref, fair_iters=fair_iters,
                               want_util=want_util)
    if lane == "vmap":
        batch = [_lane_instance("dead", seed=k) for k in range(3)]
        args = [jnp.stack(x) for x in zip(*batch)]
        got = jax.vmap(lambda e, w, d, c, a: step(e, w, d, c, active=a))(
            *args)
        for k, inst in enumerate(batch):
            _assert_step_close([g[k] for g in got],
                               oracle(*inst[:4], active=inst[4]))
        return
    edges, w, desired, cap, active = _lane_instance(lane)
    got = step(edges, w, desired, cap, active=active)
    want = oracle(edges, w, desired, cap, active=active)
    _assert_step_close(got, want)
    if lane == "all_trash":
        dark = np.asarray((edges < 0) | (edges == edges.max())).all(axis=1)
        assert dark.any()
        assert np.isposinf(np.asarray(got[1])[dark]).all()
        if fair_iters:              # no link to scale by: sends nothing
            assert (np.asarray(got[0])[dark] == 0).all()
    if lane == "dead":
        # a flow crossing a dead link sends nothing
        e = np.asarray(edges)
        hit = (np.asarray(cap)[e] == 0) & (e >= 0)
        hit = hit.any(axis=1) & np.asarray(active)
        assert hit.any() and (np.asarray(got[0])[hit] == 0).all()


@pytest.mark.parametrize("bound", [64, 2**30], ids=["folded", "rows"])
def test_batched_sort_matches_row_sorts(bound):
    """The sorted form's sort under (nested) vmap: the batch folded into
    the key, or, where the folded key would overflow int32, a sort of
    the rows; either way each row is the row's own stable sort."""
    rng = np.random.default_rng(3)
    key = jnp.asarray(rng.integers(0, 40, (2, 3, 50)).astype(np.int32))
    val = jnp.asarray(rng.random((2, 3, 50)).astype(np.float32))
    sort = functools.partial(WF._sort, bound=bound, stable=True)
    got = jax.vmap(jax.vmap(lambda k, v: sort(k, (v,))))(key, val)
    for i in range(2):
        for j in range(3):
            want = jax.lax.sort((key[i, j], val[i, j]), num_keys=1,
                                is_stable=True)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g[i, j]),
                                              np.asarray(w))


def _traced(f, s, e, backend):
    shapes = [((f, s), jnp.int32), ((f,), jnp.float32),
              ((f,), jnp.float32), ((e,), jnp.float32), ((f,), jnp.bool_)]
    return str(jax.make_jaxpr(
        lambda edges, w, d, cap, act: waterfill_step(
            edges, w, d, cap, active=act, backend=backend))(
        *[jax.ShapeDtypeStruct(sh, dt) for sh, dt in shapes]))


@pytest.mark.parametrize("f,s,e", [
    (200, 8, 751),          # sf(q=5) FatPaths
    (200, 4, 751),          # sf(q=5) ECMP
    (10_830, 9, 42_599),    # sf(q=19) FatPaths
    (10_830, 4, 42_599),    # sf(q=19) ECMP
])
@pytest.mark.parametrize("backend,path", [("pallas", "sorted"),
                                          ("ref", "ref")])
def test_backend_picks_the_form(f, s, e, backend, path):
    """The TPU backend runs the link-sorted form at every size, with no
    kernel call and no scatter; the oracle is XLA's scatter and gather.
    Read off the traced program."""
    assert waterfill_path(backend) == path
    jaxpr = _traced(f, s, e, backend)
    assert "pallas_call" not in jaxpr
    assert (" sort" in jaxpr) == (path == "sorted")
    assert ("scatter" in jaxpr) == (path == "ref")


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
    """The full simulator agrees between the TPU path (the link-sorted
    form) and the jnp oracle, for every transport; each cell counts the
    path it took."""
    TP, topo, bundle, wl = _tiny_cell()
    mk = lambda be: TP.SimConfig(  # noqa: E731
        transport=transport, balancing=bundle.balancing, n_steps=30,
        kernel_backend=be)
    with tracing.record() as rec_ref:
        res_ref = TP.simulate(topo, bundle.routing, wl, mk("ref"))
    with tracing.record() as rec_pl:
        res_pl = TP.simulate(topo, bundle.routing, wl, mk("pallas"))
    assert rec_ref.counts["waterfill.ref"] == 1
    assert rec_pl.counts["waterfill.sorted"] == 1
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
