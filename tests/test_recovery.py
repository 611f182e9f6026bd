"""PR 8 loss-recovery lanes and robustness satellites: recovery=off is
bitwise the pre-PR-8 program, kernel/oracle ECN-util parity, the RTO
state machine's backoff/reset algebra, blackhole-escape acceptance
(FatPaths recovers from a mid-run fault, a layer-pinned scheme never
does), recovery cells through both sweep engines, sweep watchdog,
checkpoint schema versioning, and dist_sweep bucket quarantine."""

import dataclasses
import json
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest still run
    from _hypothesis_stub import given, settings, st  # noqa: F401

import jax.numpy as jnp

from repro.ckpt.sweep import SCHEMA, SchemaMismatch, SweepCheckpoint
from repro.core import transport as TP
from repro.experiments import Session, compare_results
from repro.experiments import dist_sweep as ds
from repro.experiments.__main__ import main
from repro.kernels import ref
from repro.kernels.waterfill import waterfill_step


# ---- recovery=off reproduces the golden cells bit-for-bit -------------------
# Golden metrics (all clique(k=6) / uniform / seed 0), derived under the
# installed jax's default PRNG (partitionable threefry; values recorded
# under the legacy stream of jax < 0.5 differ).  Every recovery
# lane is trace-time gated, so recovery="off" (the default) must keep
# compiling the recovery-free program — equality below is ==, not
# allclose.
_FAIL = "failures(of=fatpaths(n_layers=3),rate=0.2,down_step=10)"
GOLDEN = {
    ("fatpaths(n_layers=3)", "transport(steps=40,transport=ndp)"): {
        "fct_mean_us": 208.57142639160156, "fct_p50_us": 181.0,
        "fct_p99_us": 271.0, "finished": 1.0,
        "link_util": 0.4365461923989905, "tput_gbs": 5.403144836425781},
    ("fatpaths(n_layers=3)", "transport(steps=40,transport=tcp)"): {
        "fct_mean_us": 270.71429443359375, "fct_p50_us": 240.99998474121094,
        "fct_p99_us": 340.9999694824219, "finished": 1.0,
        "link_util": 0.33516957189488256, "tput_gbs": 4.097670078277588},
    ("fatpaths(n_layers=3)", "transport(steps=40,transport=dctcp)"): {
        "fct_mean_us": 248.3333282470703, "fct_p50_us": 221.0,
        "fct_p99_us": 310.9999694824219, "finished": 1.0,
        "link_util": 0.3654848135103553, "tput_gbs": 4.429266452789307},
    (_FAIL, "transport(steps=60,transport=ndp)"): {
        "fct_mean_us": 213.63157653808594, "fct_p50_us": 181.0,
        "fct_p99_us": 418.0001525878906, "finished": 0.9047619047619048,
        "link_util": 0.3399339959172919, "tput_gbs": 5.3988423347473145},
    (_FAIL, "transport(steps=60,transport=tcp)"): {
        "fct_mean_us": 282.0526428222656, "fct_p50_us": 240.99998474121094,
        "fct_p99_us": 586.10009765625, "finished": 0.9047619047619048,
        "link_util": 0.26885039449676756, "tput_gbs": 4.058754920959473},
    (_FAIL, "transport(steps=60,transport=dctcp)"): {
        "fct_mean_us": 254.68421936035156, "fct_p50_us": 221.0,
        "fct_p99_us": 471.7001953125, "finished": 0.9047619047619048,
        "link_util": 0.2927901859348879, "tput_gbs": 4.4044976234436035},
}


@pytest.mark.parametrize("routing,evaluator", sorted(GOLDEN))
def test_recovery_off_reproduces_pr7_bitwise(routing, evaluator):
    rr = Session().run("clique(k=6)", routing, "uniform", evaluator, seed=0)
    want = GOLDEN[(routing, evaluator)]
    assert set(rr.metrics) == set(want)         # no retrans_mb when off
    for k, v in want.items():
        assert rr.metrics[k] == v, (k, rr.metrics[k], v)


# ---- ECN util lane: kernel == oracle ----------------------------------------
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


@pytest.mark.parametrize("f,s,e",
                         [(7, 3, 19), (130, 9, 513), (1, 5, 33),
                          (256, 4, 1024)])
@pytest.mark.parametrize("fair_iters", [0, 1, 2])
def test_want_util_kernel_matches_oracle(f, s, e, fair_iters):
    """The want_util lane agrees between backends over ragged shapes
    (multi-tile flow and link grids) and does not perturb (sent, share):
    the flag only ADDS an output."""
    edges, w, desired, cap = _instance(f, s, e, seed=f + s + e)
    sent, share, util = waterfill_step(
        edges, w, desired, cap, fair_iters=fair_iters, backend="pallas",
        want_util=True)
    sent_r, share_r, util_r = ref.waterfill_ref(
        edges, w, desired, cap, fair_iters=fair_iters, want_util=True)
    np.testing.assert_allclose(np.asarray(sent), np.asarray(sent_r),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(share), np.asarray(share_r),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(util), np.asarray(util_r),
                               rtol=1e-5, atol=1e-7)
    # util is a demand utilization: finite, >= 0, 0 for all-trash rows
    u = np.asarray(util_r)
    assert np.isfinite(u).all() and (u >= 0).all()
    assert (u[np.asarray(w) == 0] == 0).all()
    # the lane must not change the base outputs (bitwise, per backend)
    s0, sh0 = waterfill_step(edges, w, desired, cap,
                             fair_iters=fair_iters, backend="pallas")
    np.testing.assert_array_equal(np.asarray(sent), np.asarray(s0))
    np.testing.assert_array_equal(np.asarray(share), np.asarray(sh0))
    s1, sh1 = ref.waterfill_ref(edges, w, desired, cap,
                                fair_iters=fair_iters)
    np.testing.assert_array_equal(np.asarray(sent_r), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(share_r), np.asarray(sh1))


def test_want_util_with_active_mask():
    """ECN util composes with the dynamic-traffic active lane: inactive
    rows report util 0 and the backends agree (shares go +inf for
    inactive rows, so compare them under a finite mask)."""
    f, s, e = 130, 5, 40
    edges, w, desired, cap = _instance(f, s, e, seed=3, idle_frac=0.0)
    rng = np.random.default_rng(9)
    active = jnp.asarray(rng.random(f) < 0.6)
    sent, share, util = waterfill_step(
        edges, w, desired, cap, active=active, backend="pallas",
        want_util=True)
    sent_r, share_r, util_r = ref.waterfill_ref(
        edges, w, desired, cap, active=active, want_util=True)
    np.testing.assert_allclose(np.asarray(sent), np.asarray(sent_r),
                               rtol=1e-5, atol=1e-7)
    fin = np.isfinite(np.asarray(share_r))
    np.testing.assert_array_equal(fin, np.isfinite(np.asarray(share)))
    np.testing.assert_allclose(np.asarray(share)[fin],
                               np.asarray(share_r)[fin], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(util), np.asarray(util_r),
                               rtol=1e-5, atol=1e-7)
    assert (np.asarray(util_r)[~np.asarray(active)] == 0).all()


# ---- RTO state machine algebra ----------------------------------------------
@settings(max_examples=40, deadline=None)
@given(st.integers(1, 64), st.integers(1, 512), st.integers(0, 2 ** 31 - 1))
def test_rto_backoff_monotone_capped_and_reset(rto_base, cap_extra, seed):
    """_rto_next over random event sequences: backoff is monotone
    non-decreasing until a delivery, never exceeds rto_cap, delivery
    resets to rto_base and WINS over a same-step backoff, and no event
    leaves the timeout untouched."""
    rto_cap = rto_base + cap_extra
    rng = np.random.default_rng(seed)
    n = 16
    rto = jnp.full((n,), rto_base, jnp.int32)
    for _ in range(12):
        delivered = jnp.asarray(rng.random(n) < 0.3)
        backoff = jnp.asarray(rng.random(n) < 0.5)
        nxt = np.asarray(TP._rto_next(rto, delivered, backoff,
                                      rto_base, rto_cap))
        cur, d, b = np.asarray(rto), np.asarray(delivered), np.asarray(backoff)
        assert (nxt[d] == rto_base).all()                     # delivery wins
        assert (nxt[~d & b] >= cur[~d & b]).all()             # monotone
        assert (nxt[~d & b] == np.minimum(cur[~d & b] * 2, rto_cap)).all()
        assert (nxt[~d & ~b] == cur[~d & ~b]).all()           # inert
        assert (nxt <= rto_cap).all() and (nxt >= rto_base).all()
        rto = jnp.asarray(nxt)
    # sustained backoff saturates at the cap
    for _ in range(12):
        rto = TP._rto_next(rto, jnp.zeros(n, bool), jnp.ones(n, bool),
                           rto_base, rto_cap)
    assert (np.asarray(rto) == rto_cap).all()


def test_escape_layers_is_deterministic_and_cyclic():
    """Blackhole escape picks the NEXT usable surviving layer cyclically
    after the current one, no PRNG; flows with no escape keep their
    layer and report valid=False."""
    esc_ok = jnp.asarray([[True, False, True, False],
                          [False, False, False, False],
                          [False, True, True, True]])
    layer = jnp.asarray([0, 1, 2], jnp.int32)
    esc, valid = TP._escape_layers(layer, esc_ok)
    np.testing.assert_array_equal(np.asarray(esc), [2, 1, 3])
    np.testing.assert_array_equal(np.asarray(valid), [True, False, True])
    # cyclic wrap: from the last usable layer back to the first
    esc2, _ = TP._escape_layers(jnp.asarray([2, 0, 3], jnp.int32), esc_ok)
    np.testing.assert_array_equal(np.asarray(esc2), [0, 0, 1])


# ---- time-to-recover acceptance ---------------------------------------------
_BLACKHOLE = "failures(of={},rate=0.1,down_step=100)"
_BIGPERM = "permutation(flow_size=1000000000.0)"
_RECOV = "recovery(steps=400,eps=0.02,seeds=4{})"
_DRAWS = range(16)


def test_recovery_fatpaths_recovers_pinned_ecmp_does_not():
    """A mid-run blackhole under never-finishing permutation traffic,
    over 16 failure draws (four sim seeds each, curves averaged).  ECMP
    pins every flow to its hash layer: in every draw where the fault
    strands flows, they stay dark (the stalled count never falls from
    its peak) and the cell never re-enters the pre-fault band
    (recovered=0, TTR=NaN).  FatPaths' RTO escape moves stalled flows
    onto other layers, so its stalled count falls in most such draws.

    Whether goodput then re-enters the 2% band depends on how often
    flowlets re-roll: a re-roll may land on a layer that crosses the
    dead link again (one-shot death does not update the usable-layer
    mask).  At the default 50 us flowlet gap FatPaths re-enters the band
    in none of the 14 draws that bite; with rare re-rolls (1 ms gap) it
    does in 7 of them, and in 5 to 7 at any gap from 1 to 20 ms, while
    ECMP re-enters in none (sweep in PERF.md).  Both settings run; the
    recovery claim is asserted at the 1 ms gap."""
    s = Session()
    for gap in ("", ",flowlet_gap=0.001"):
        def runs(scheme):
            return [s.run("clique(k=6)", _BLACKHOLE.format(scheme),
                          _BIGPERM, _RECOV.format(gap), seed=d)
                    for d in _DRAWS]

        fp, ec = runs("fatpaths(n_layers=9)"), runs("ecmp(n=4)")
        bit = [(f, e) for f, e in zip(fp, ec)
               if e.metrics["stalled_peak"] > 0]
        assert len(bit) >= len(_DRAWS) * 3 / 4, gap
        for f, e in bit:
            assert e.metrics["recovered"] == 0.0
            assert np.isnan(e.metrics["ttr_steps"])
            assert e.meta["stalled_curve"][-1] == e.metrics["stalled_peak"]
            assert f.metrics["stalled_peak"] > 0
            assert f.metrics["dip_frac"] > 0        # the fault actually bit
            assert f.metrics["retrans_mb"] > 0      # blackholed bytes resent
        escaped = [f.meta["stalled_curve"][-1] < f.metrics["stalled_peak"]
                   for f, _ in bit]
        assert sum(escaped) >= len(bit) * 3 / 4, gap
        recovered = [f for f, _ in bit if f.metrics["recovered"] == 1.0]
        for f in recovered:
            assert 0 < f.metrics["ttr_steps"] < 300
        for f in fp:
            assert f.metrics["plateau_goodput"] > 0
            # trajectory meta: downsampled curves of identical length
            assert (len(f.meta["curve_steps"])
                    == len(f.meta["goodput_curve"])
                    == len(f.meta["stalled_curve"]))
            assert f.meta["rto_base"] == 16 and f.meta["rto_cap"] == 256
    assert len(recovered) >= len(bit) / 4


def test_recovery_without_fault_is_trivially_recovered():
    rr = Session().run("clique(k=6)", "fatpaths(n_layers=3)", _BIGPERM,
                       "recovery(steps=120)", seed=0)
    assert rr.metrics["recovered"] == 1.0
    assert rr.metrics["ttr_steps"] == 0.0
    assert rr.metrics["dip_frac"] == 0.0


# ---- both sweep engines, failures x recovery grid ---------------------------
# steps=80 > horizon_chunk and recovery=on cells bucket separately from
# legacy cells (the SimConfig is part of the signature); every cell must
# come back identical to the sequential engine, diff-exact.
_PROG = textwrap.dedent("""
    from repro.experiments import Session, compare_results
    from repro.experiments.dist_sweep import dist_sweep
    import jax
    assert jax.device_count() == 8, jax.device_count()
    grid = dict(topos=["clique(k=6)"],
                routings=["fatpaths(n_layers=3)",
                          "failures(of=fatpaths(n_layers=3),rate=0.2,down_step=20)"],
                patterns=["uniform"],
                evaluators=["transport(steps=80,recovery=on)",
                            "transport(steps=80,recovery=on,transport=dctcp)",
                            "transport(steps=80)"],
                seeds=[0, 1])
    seq = Session().sweep(**grid)
    s8 = Session()
    d8 = dist_sweep(s8, s8.grid(**grid), devices=8)
    diffs = compare_results(seq, d8)
    assert diffs == [], diffs[:5]
    rec = [r for r in d8 if "recovery=on" in r.evaluator]
    assert len(rec) == 8
    assert all("retrans_mb" in r.metrics for r in rec)
    off = [r for r in d8 if "recovery" not in r.evaluator]
    assert all("retrans_mb" not in r.metrics for r in off)
    print("RECOV8_OK")
""")


def test_recovery_grid_8_devices_identical():
    r = subprocess.run(
        [sys.executable, "-c", _PROG],
        capture_output=True, text=True, timeout=600,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "RECOV8_OK" in r.stdout, r.stderr[-2000:]


# ---- satellite: checkpoint schema versioning --------------------------------
def test_sweep_checkpoint_rejects_stale_schema(tmp_path):
    ck = SweepCheckpoint(str(tmp_path))
    ck.put("cell_a", {"topo": "t"})
    stale = tmp_path / "cell_0000000000000000beef.json"
    stale.write_text(json.dumps(
        {"cell_id": "cell_b", "schema": SCHEMA - 1, "result": {}}))
    with pytest.raises(SchemaMismatch,
                       match=re.escape(str(tmp_path))):
        SweepCheckpoint(str(tmp_path)).load()
    # torn/foreign files are still just skipped, not fatal
    stale.write_text('{"cell_id": "cell_b"')
    assert SweepCheckpoint(str(tmp_path)).load() == {"cell_a": {"topo": "t"}}


# ---- satellite: dist_sweep graceful degradation -----------------------------
_GRID = dict(topos=["clique(k=6)"], routings=["ecmp(n=2)"],
             patterns=["uniform"], evaluators=["transport(steps=40)"],
             seeds=[0, 1])


def test_dist_sweep_quarantines_a_twice_failed_bucket(monkeypatch):
    """A failed bucket is attempted once: its cells are quarantined with
    the failure in meta, and nothing is retried."""
    calls = []

    def boom(works, finals, desc):
        calls.append([w.cfg.kernel_backend for w in works])
        raise RuntimeError("synthetic bucket failure")

    monkeypatch.setattr(ds, "_finalize_bucket", boom)
    s = Session()
    out = ds.dist_sweep(s, s.grid(**_GRID), devices=1)
    assert len(out) == 2 and len(calls) == 1    # one attempt, no retry
    for rr in out:
        assert rr.metrics == {}
        err = rr.meta["error"]
        assert err == {"type": "bucket_failure",
                       "exception": "RuntimeError",
                       "message": "synthetic bucket failure"}


def test_dist_sweep_ref_retry_recovers_identically(monkeypatch):
    """No fallback hides the measured path: a bucket that fails to
    dispatch is quarantined as it failed — never re-run on the ``ref``
    kernel backend under the cell's name — while the other buckets of
    the sweep complete identically to the sequential engine."""
    real = ds._dispatch_bucket
    backends = []

    def flaky(works, rt, bucket_index):
        backends.append({w.cfg.kernel_backend for w in works})
        if any(w.cfg.balancing == "ecmp" for w in works):
            raise RuntimeError("synthetic lowering failure")
        return real(works, rt, bucket_index)

    monkeypatch.setattr(ds, "_dispatch_bucket", flaky)
    grid = dict(_GRID, routings=["ecmp(n=2)", "fatpaths(n_layers=3)"])
    s = Session()
    out = ds.dist_sweep(s, s.grid(**grid), devices=1)
    assert backends == [{""}, {""}]             # two buckets, one try each
    bad = [rr for rr in out if "error" in rr.meta]
    good = [rr for rr in out if "error" not in rr.meta]
    assert [rr.routing for rr in bad] == ["ecmp(n=2)"] * 2
    assert all(rr.metrics == {} for rr in bad)
    assert all(rr.meta["error"]["message"] == "synthetic lowering failure"
               for rr in bad)
    seq = Session().sweep(**dict(grid, routings=["fatpaths(n_layers=3)"]))
    diffs = compare_results(seq, good)
    assert diffs == [], diffs[:5]


def test_dist_sweep_quarantines_nonfinite_cells(monkeypatch):
    real = ds._finalize_bucket

    def poison(works, finals, desc):
        sims = real(works, finals, desc)
        sims[0] = [dataclasses.replace(
            r, delivered=np.where(np.arange(len(r.delivered)) == 0,
                                  np.nan, r.delivered))
            for r in sims[0]]
        return sims

    monkeypatch.setattr(ds, "_finalize_bucket", poison)
    s = Session()
    out = ds.dist_sweep(s, s.grid(**_GRID), devices=1)
    assert len(out) == 2
    bad = [rr for rr in out if "error" in rr.meta]
    assert len(bad) == 1
    assert bad[0].meta["error"] == {"type": "nonfinite", "seeds_bad": 1}
    assert bad[0].metrics == {}
    good = [rr for rr in out if "error" not in rr.meta]
    assert good and good[0].metrics["finished"] > 0


def test_dist_sweep_error_cells_are_not_checkpointed(tmp_path, monkeypatch):
    monkeypatch.setattr(
        ds, "_finalize_bucket",
        lambda *a: (_ for _ in ()).throw(RuntimeError("synthetic")))
    s = Session()
    cells = s.grid(**_GRID)
    out = ds.dist_sweep(s, cells, devices=1, checkpoint_dir=str(tmp_path))
    assert all("error" in rr.meta for rr in out)
    assert len(SweepCheckpoint(str(tmp_path))) == 0
    # a resume with the fault gone re-attempts and completes every cell
    monkeypatch.undo()
    out2 = ds.dist_sweep(Session(), cells, devices=1,
                         checkpoint_dir=str(tmp_path))
    assert all("error" not in rr.meta for rr in out2)
    assert len(SweepCheckpoint(str(tmp_path))) == 2


# ---- satellite: --cell-timeout-s watchdog -----------------------------------
_CLI = ["sweep", "--topos", "clique(k=4)", "--schemes", "ecmp(n=2)",
        "--patterns", "uniform"]


def test_cell_timeout_marks_cell_and_exits_1(capsys, tmp_path):
    out_json = str(tmp_path / "wd.json")
    rc = main([*_CLI, "--evaluators", "transport(steps=2000,seeds=4)",
               "--cell-timeout-s", "0.01", "--json", out_json])
    assert rc == 1                              # nothing succeeded
    assert "failed-with-timeout" in capsys.readouterr().out
    rows = json.load(open(out_json))
    assert len(rows) == 1 and rows[0]["metrics"] == {}
    assert rows[0]["meta"]["error"] == {"type": "timeout",
                                        "timeout_s": 0.01}


def test_cell_timeout_passing_cells_exit_0(capsys, tmp_path):
    out_json = str(tmp_path / "wd.json")
    rc = main([*_CLI, "--evaluators", "transport(steps=40)",
               "--cell-timeout-s", "600", "--json", out_json])
    assert rc == 0
    assert "1 succeeded, 0 timed out" in capsys.readouterr().out
    rows = json.load(open(out_json))
    assert rows[0]["metrics"]["finished"] > 0


def test_cell_timeout_rejects_devices(capsys):
    rc = main([*_CLI, "--evaluators", "transport(steps=40)",
               "--cell-timeout-s", "5", "--devices", "2"])
    assert rc == 2
    assert "drop --devices" in capsys.readouterr().err


def test_cell_timeout_resume_reattempts_timed_out_cells(capsys, tmp_path):
    ck = str(tmp_path / "ck")
    ev = ["--evaluators", "transport(steps=2000,seeds=4)"]
    assert main([*_CLI, *ev, "--cell-timeout-s", "0.01",
                 "--checkpoint", ck]) == 1
    assert len(SweepCheckpoint(ck)) == 0        # timeouts never committed
    assert main([*_CLI, *ev, "--cell-timeout-s", "600",
                 "--checkpoint", ck]) == 0
    assert len(SweepCheckpoint(ck)) == 1
    out = capsys.readouterr().out
    assert "1 succeeded, 0 timed out" in out
