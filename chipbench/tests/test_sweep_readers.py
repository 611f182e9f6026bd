"""The readers of the four-chip grid cell ``sf_small_grid.sweep4``,
against counts by hand.

The grid runs once at its own size on the CPU, through ``run.measure``
with four forced host devices, in a subprocess (this session keeps one
device); what the readers see of it (each iteration's cells, prepared
shapes, bucket lines and the cells' meta) comes back as JSON.  The
readers of the device trace are given a synthetic trace of the bucket
programs on four chips (times in ns, as in ``test_trace_reduce.py``)."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import roofline, run as run_mod
from chipbench import trace as tr

CELL = "sf_small_grid.sweep4"
SEED = 1
META_KEYS = ("spans", "sweep_bucket", "sweep_chunks", "n_seeds",
             "balancing", "n_flows")

_GRID = r'''
import json
import sys
import jax
jax.config.update("jax_enable_compilation_cache", False)
sys.path.insert(0, {root!r})
from chipbench import check, run

bench = json.load(open(run.ROOT + "/BENCHMARK.json"))
cell = next(w for w in bench["workloads"] if w["name"] == {cell!r})
config = json.load(open(run.HERE + "/configs/" + cell["config"] + ".json"))
traffic = json.load(open(run.HERE + "/traffic/" + cell["traffic"] + ".json"))
seen = {{}}
measure_window = run._measure

def keep(bench, r, *a, **k):
    seen["run"] = r
    return measure_window(bench, r, *a, **k)

run._measure = keep
res = run.measure(bench, cell, config, traffic, check.load_limits(cell["name"]),
                  {seed}, 0.0, False, require_accelerator=False)
r = seen["run"]
print("GRID " + json.dumps({{
    "correct": res["correct"], "metrics": res["metrics"],
    "window_s": r.window_s,
    "iterations": [{{
        "cells": it["cells"], "logs": it["logs"],
        "prepared": it["prepared"],
        "metas": [{{k: x.meta[k] for k in {keys!r} if k in x.meta}}
                  for x in it["results"]]}} for it in r.iterations]}}))
'''


def _reader(name):
    return run_mod.load_module(
        os.path.join(run_mod.HERE, "metrics", f"{name}.py")).read


@pytest.fixture(scope="module")
def grid():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _GRID.format(root=run_mod.ROOT, cell=CELL, seed=SEED,
                        keys=META_KEYS)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=run_mod.ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("GRID ")][-1]
    return json.loads(line[len("GRID "):])


def _run(grid, trace=None):
    bench = json.load(open(os.path.join(run_mod.ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = json.load(open(os.path.join(run_mod.HERE, "configs",
                                         f"{cell['config']}.json")))
    peaks = json.load(open(os.path.join(run_mod.HERE, "peaks.json")))
    r = run_mod.Run(CELL, config, {}, 4,
                    peaks["devices"]["TPU v5 lite"])
    r.window_s = grid["window_s"]
    r.iterations = [
        {"cells": it["cells"], "logs": it["logs"],
         "prepared": [(tuple(s), e) for s, e in it["prepared"]],
         "results": [types.SimpleNamespace(meta=m) for m in it["metas"]]}
        for it in grid["iterations"]]
    r.trace = trace
    return r


def _metas(grid):
    return [m for it in grid["iterations"] for m in it["metas"]]


def _bucket_trace(module="jit_sweep_scan"):
    # Window 0..10,000.  Each chip runs the four bucket programs, one cell
    # a chip, and a table program that is not the scan; chip 3's last
    # bucket runs past the window's end and is clipped.
    chips = []
    for c in range(4):
        mods = [(f"{module}({b})", 1000 * b + 100, 1000 * b + 600 + 10 * c)
                for b in range(4)]
        mods.append(("jit__forwarding_program(2)", 5000, 5500))
        chips.append(mods)
    chips[3][3] = (f"{module}(3)", 3100, 10400)
    ops = [[(n, a, b) for n, a, b in mods] for mods in chips]
    return tr.Trace(modules=chips, ops=ops,
                    spans=[("iteration", 0, 10000)], window=(0, 10000))


SCAN_NS = sum(500 + 10 * c for c in range(3) for _ in range(4)) + (
    3 * 530 + (10000 - 3100))


def test_grid_runs_as_four_sharded_buckets(grid):
    assert grid["correct"]
    (it,) = grid["iterations"]
    assert it["cells"] == 16 and len(it["prepared"]) == 16
    buckets = [x for x in it["logs"] if " programs via " in x]
    assert len(buckets) == 4
    assert all("4 programs via shard_map[4]" in x for x in buckets)
    assert grid["metrics"]["cell_s"]["value"] == pytest.approx(
        grid["window_s"] / 16)


def test_resolve_host_is_each_cells_own_span(grid):
    by_hand = [m["spans"]["cell"][0] - m["spans"]["stack.device"][0]
               for m in _metas(grid)]
    assert len(by_hand) == 16
    assert _reader("sweep_resolve_host_ms.sweep")(_run(grid)) == \
        pytest.approx(sum(by_hand) / 16 * 1e3)


def test_bucket_host_counts_each_bucket_once(grid):
    metas = _metas(grid)
    once, per_member = {}, 0.0
    for m in metas:
        s = m["spans"]
        host = (s["prepare"][0] - s["prepare.device"][0]
                + s["sweep.pad"][0] + s["sweep.dispatch"][0])
        once[m["sweep_bucket"]] = host
        per_member += host
    assert sorted(once) == [0, 1, 2, 3]
    got = _reader("sweep_host_ms.sweep")(_run(grid))
    assert got == pytest.approx(sum(once.values()) / 16 * 1e3)
    # four members a bucket: counting every member's copy reads 4 times
    assert got == pytest.approx(per_member / 4 / 16 * 1e3)
    # each bucket ran its four prepares once
    assert all(m["spans"]["prepare"][1] == 4 for m in metas)


def test_pad_share_from_the_bucket_lines(grid):
    (it,) = grid["iterations"]
    padded = 0
    for line in it["logs"]:
        if " programs via " in line:
            f = int(line.split(" F=")[1].split()[0])
            h = int(line.split(" H=")[1].split()[0])
            padded += 4 * f * h
    real = sum(f * s for (_, f, s), _ in it["prepared"])
    assert _reader("sweep_pad_pct")(_run(grid)) == pytest.approx(
        (1 - real / padded) * 100)


def test_scan_time_is_summed_over_the_chips(grid):
    assert _reader("sweep_scan_ms.sweep")(_run(grid, _bucket_trace())) == \
        pytest.approx(SCAN_NS / 1e9 / 16 * 1e3)


def test_scan_roofline_counts_the_real_shapes(grid):
    (it,) = grid["iterations"]
    metas = it["metas"]
    # the FatPaths sf(q=5) cells' own hop slots differ within their bucket
    fat5 = [s for (n_layers, f, s), _ in it["prepared"]
            if n_layers == 9 and f == 200]
    assert len(fat5) == 4 and len(set(fat5)) > 1, fat5
    # prepared in bucket order, grid order within a bucket
    order = sorted(range(16), key=lambda i: (metas[i]["sweep_bucket"], i))
    pads = {}
    for line in it["logs"]:
        if " programs via " in line:
            b = int(line.split("# bucket ")[1].split(":")[0])
            pads[b] = int(line.split(" H=")[1].split()[0])
    real = padded = 0
    for i, ((n_layers, f, s), e) in zip(order, it["prepared"]):
        m = metas[i]
        assert (m["n_flows"], m["n_seeds"]) == (f, 1)
        steps = m["sweep_chunks"] * 64
        reroute = m["balancing"] != "ecmp"
        assert reroute == (n_layers == 9)
        real += steps * roofline.scan_step_bytes(f, s, e, n_layers, 2,
                                                 reroute)
        padded += steps * roofline.scan_step_bytes(
            f, pads[m["sweep_bucket"]], e, n_layers, 2, reroute)
    assert real < padded
    got = _reader("sweep_scan_roofline_pct.sweep")(
        _run(grid, _bucket_trace()))
    assert got == pytest.approx(real / 819e9 / (SCAN_NS / 1e9) * 100)


@pytest.mark.parametrize("name", ["sweep_scan_ms.sweep",
                                  "sweep_scan_roofline_pct.sweep",
                                  "idle_pct.sweep"])
def test_trace_readers_read_nothing_without_a_trace(grid, name):
    assert _reader(name)(_run(grid)) is None
    empty = tr.Trace(modules=[[]] * 4, ops=[[]] * 4,
                     spans=[("iteration", 0, 10000)], window=(0, 10000))
    assert _reader(name)(_run(grid, empty)) is None


@pytest.mark.parametrize("name", ["sweep_scan_ms.sweep",
                                  "sweep_scan_roofline_pct.sweep"])
def test_scan_readers_do_not_read_unnamed_bucket_programs(grid, name):
    """Before the bucket programs were named, a sharded bucket compiled to
    ``jit_body``: nothing named ``scan`` ran, and the readers say so."""
    assert _reader(name)(_run(grid, _bucket_trace("jit_body"))) is None


def test_idle_share_is_averaged_over_the_chips(grid):
    trace = _bucket_trace()
    busy = [tr.union_ns(ops, 0, 10000) for ops in trace.ops]
    assert _reader("idle_pct.sweep")(_run(grid, trace)) == pytest.approx(
        (1 - sum(busy) / 4 / 10000) * 100)
