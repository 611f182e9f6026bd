"""Host spans and counters (``repro.tracing``) and what the experiment
engines record with them."""

import glob
import os
import time

import jax
import pytest

from repro import tracing
from repro.experiments import Session
from repro.experiments.dist_sweep import dist_sweep
from repro.experiments.results import EXECUTION_META_KEYS, compare_results

CELL = ("sf(q=5)", "permutation", "transport(steps=128)")
FATPATHS = "fatpaths(n_layers=9,rho=0.6)"

# The two sf(q=5) cells' metrics and result meta, as the code before the
# spans computed them (seed 0, CPU): recording spans changes no result.
BEFORE = {
    FATPATHS: {
        "metrics": {"fct_mean_us": 213.0699920654297, "fct_p50_us": 212.0,
                    "fct_p99_us": 361.9999694824219, "finished": 1.0,
                    "link_util": 0.4324134013831823,
                    "tput_gbs": 5.585652828216553},
        "meta": {"balancing": "fatpaths", "delivered_bytes": 209715200.0,
                 "n_endpoints": 200, "n_flows": 200, "n_routers": 50,
                 "n_seeds": 1, "table_exact": 90000, "table_prefix": 22500,
                 "transport": "ndp"}},
    "ecmp": {
        "metrics": {"fct_mean_us": 198.6799774169922, "fct_p50_us": 182.0,
                    "fct_p99_us": 351.9999694824219, "finished": 1.0,
                    "link_util": 0.46267059623222156,
                    "tput_gbs": 6.0010986328125},
        "meta": {"balancing": "ecmp", "delivered_bytes": 209715200.0,
                 "n_endpoints": 200, "n_flows": 200, "n_routers": 50,
                 "n_seeds": 1, "table_exact": 80000, "table_prefix": 20000,
                 "transport": "ndp"}},
}


@pytest.fixture
def clock(monkeypatch):
    """A fake ``time_ns``: reads return ``now[0]``, tests advance it."""
    now = [0]
    monkeypatch.setattr(tracing, "_clock", lambda: now[0])
    return now


def test_spans_nest_with_inclusive_and_self_time(clock):
    with tracing.record("c") as rec:
        with tracing.span("outer") as outer:
            clock[0] += 100
            with tracing.span("inner") as inner:
                clock[0] += 300
                tracing.count("hits")
            with tracing.span("inner"):
                clock[0] += 100
                tracing.count("hits", 2)
            clock[0] += 500
    assert inner.parent is outer and outer.parent is None
    assert rec.seconds("outer") == pytest.approx(1000e-9)
    assert rec.seconds("inner") == pytest.approx(400e-9)
    assert rec.self_seconds("outer") == pytest.approx(600e-9)
    assert rec.self_seconds("inner") == pytest.approx(400e-9)
    assert rec.wall_s == pytest.approx(1000e-9)
    spans, counts = tracing.totals(rec)
    assert spans["inner"] == [pytest.approx(400e-9), 2]
    assert spans["outer"] == [pytest.approx(1000e-9), 1]
    assert counts == {"hits": 3}


def test_records_see_only_their_own_spans(clock):
    with tracing.span("before"):
        clock[0] += 1
    with tracing.record("a") as a:
        with tracing.span("x"):
            clock[0] += 10
        tracing.count("n")
    with tracing.record("b") as b:
        with tracing.span("y"):
            clock[0] += 20
    assert [s.name for s in a.spans] == ["x"]
    assert [s.name for s in b.spans] == ["y"]
    assert a.counts == {"n": 1} and not b.counts
    # Entered again (a sweep bucket's collect after its dispatch): the
    # record grows, and its wall runs from first entry to last exit.
    clock[0] += 1000
    with a:
        with tracing.span("z"):
            clock[0] += 5
    assert [s.name for s in a.spans] == ["x", "z"]
    assert a.wall_s == pytest.approx((10 + 20 + 1000 + 5) * 1e-9)


def test_span_decorates_functions(clock):
    @tracing.span("work")
    def work(n):
        clock[0] += n
        return n

    with tracing.record() as rec:
        assert work(7) == 7 and work(3) == 3
    assert tracing.totals(rec)[0] == {"work": [pytest.approx(10e-9), 2]}


@pytest.fixture(scope="module")
def fatpaths_cell():
    return Session().run(CELL[0], FATPATHS, *CELL[1:], seed=0)


def test_session_cell_carries_its_spans(fatpaths_cell):
    meta = fatpaths_cell.meta
    for name in ("cell", "resolve", "topology", "stack", "stack.device",
                 "workload", "prepare", "prepare.device", "scan",
                 "finalise"):
        assert name in meta["spans"], name
    spans = meta["spans"]
    assert spans["cell"][1] == 1
    assert spans["finalise"][1] == 2        # one sim seed + the metrics
    assert spans["resolve"][0] <= spans["cell"][0]
    assert spans["stack.device"][0] <= spans["stack"][0]
    assert meta["build_s"] == sum(spans[k][0] for k in
                                  ("topology", "stack", "workload"))
    assert meta["build_device_s"] == spans["stack.device"][0]
    assert fatpaths_cell.wall_s == spans["cell"][0]
    assert meta["counts"]["stack_build"] == meta["cache_builds"] == 1
    assert "spans" in EXECUTION_META_KEYS and "counts" in EXECUTION_META_KEYS


@pytest.mark.parametrize("routing", sorted(BEFORE))
def test_spans_change_no_result(routing, fatpaths_cell):
    rr = (fatpaths_cell if routing == FATPATHS
          else Session().run(CELL[0], routing, *CELL[1:], seed=0))
    want = BEFORE[routing]
    assert rr.metrics == want["metrics"]
    assert {k: v for k, v in rr.meta.items()
            if k not in EXECUTION_META_KEYS} == want["meta"]


def test_sequential_and_dist_sweep_still_agree():
    s = Session()
    seq = s.sweep([CELL[0]], ["ecmp", FATPATHS], [CELL[1]], [CELL[2]],
                  seeds=[1])
    bat = dist_sweep(Session(), s.grid([CELL[0]], ["ecmp", FATPATHS],
                                       [CELL[1]], [CELL[2]], seeds=[1]))
    assert compare_results(seq, bat) == []
    for rr in bat:
        spans = rr.meta["spans"]
        for name in ("cell", "resolve", "stack", "prepare", "sweep.pad",
                     "sweep.dispatch", "sweep.collect", "finalise"):
            assert name in spans, name
        assert rr.meta["build_s"] == sum(spans[k][0] for k in
                                         ("topology", "stack", "workload")
                                         if k in spans)
        assert rr.wall_s >= spans["cell"][0]


def test_cells_count_their_waterfill_form(fatpaths_cell):
    """Each simulated cell counts the water-filling form its scan ran,
    once, alone and in a dist_sweep bucket (here the CPU's oracle)."""
    assert fatpaths_cell.meta["counts"]["waterfill.ref"] == 1
    s = Session()
    bat = dist_sweep(s, s.grid([CELL[0]], ["ecmp", FATPATHS], [CELL[1]],
                               [CELL[2]], seeds=[2]))
    for rr in bat:
        forms = {k: v for k, v in rr.meta["counts"].items()
                 if k.startswith("waterfill.")}
        assert forms == {"waterfill.ref": 1}, rr.cell_id


def test_profile_holds_the_spans_on_the_same_clock(tmp_path, fatpaths_cell):
    from jax.profiler import ProfileData

    t0 = time.time_ns()
    with jax.profiler.trace(str(tmp_path)), tracing.record() as rec:
        rr = Session().run(CELL[0], "ecmp", *CELL[1:], seed=0)
    t1 = time.time_ns()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    # Event times are offsets from the session's start, which the
    # profile records on the time_ns clock.
    env = data.find_plane_with_name("Task Environment")
    base = dict(env.stats)["profile_start_time"]
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("repro.cell", "repro.resolve"):
                    found[ev.name] = (base + ev.start_ns,
                                      base + ev.end_ns, dict(ev.stats))
    (c0, c1, c_stats), (r0, r1, r_stats) = (found["repro.cell"],
                                            found["repro.resolve"])
    assert t0 <= c0 <= r0 < r1 <= c1 <= t1
    assert c_stats["cell"] == r_stats["cell"] == rr.cell_id
    mine = {s.name: s for s in rec.spans}
    assert abs(c0 - mine["cell"].start_ns) < 1e6
    assert abs(r0 - mine["resolve"].start_ns) < 1e6
