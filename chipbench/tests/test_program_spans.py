"""The readers of the program's spans and of the water-filling sorts'
device time, on a synthetic run (times in ns, as in
``test_trace_reduce.py``)."""

import os
import types

import pytest

from chipbench import run as run_mod
from chipbench import trace as tr


def _reader(name):
    return run_mod.load_module(
        os.path.join(run_mod.HERE, "metrics", f"{name}.py")).read


def _result(spans=None, chunks=1):
    meta = {"sweep_chunks": chunks, "n_seeds": 1}
    if spans is not None:
        meta["spans"] = spans
    return types.SimpleNamespace(meta=meta)


def _run(results, trace=None):
    r = run_mod.Run("cell", {"reference": {"transport": {
        "chunk": 64, "steps": 100}}}, {}, 1, None)
    r.iterations = [{"cells": 1, "results": [res]} for res in results]
    r.trace = trace
    return r


SPANS = [
    {"cell": [10.0, 1], "resolve": [0.6, 1], "stack.device": [0.2, 1],
     "prepare": [0.05, 1], "prepare.device": [0.03, 1],
     "finalise": [0.004, 2]},
    # a cell whose stack came from the cache: no stack.device span
    {"cell": [9.0, 1], "resolve": [0.2, 1],
     "prepare": [0.04, 1], "prepare.device": [0.03, 1],
     "finalise": [0.002, 2]},
]


def test_host_readers_average_the_spans_over_cells():
    run = _run([_result(s) for s in SPANS])
    assert _reader("resolve_host_s.cell")(run) == pytest.approx(
        ((0.6 - 0.2) + 0.2) / 2)
    assert _reader("prepare_host_ms.cell")(run) == pytest.approx(
        ((0.05 - 0.03) + (0.04 - 0.03)) / 2 * 1e3)
    assert _reader("finalise_ms.cell")(run) == pytest.approx(
        (0.004 + 0.002) / 2 * 1e3)


@pytest.mark.parametrize("name", ["resolve_host_s.cell",
                                  "prepare_host_ms.cell",
                                  "finalise_ms.cell"])
def test_host_readers_read_nothing_without_spans(name):
    assert _reader(name)(_run([_result(), _result()])) is None
    assert _reader(name)(_run([_result({"cell": [1.0, 1]})])) is None


SORT = ("%sort.12 = (s32[98304]{0}, s32[98304]{0}) sort(s32[98304]{0} "
        "%bitcast.4, s32[98304]{0} %iota.2), dimensions={0}, is_stable=true")


def _trace(with_sorts=True):
    # Window 0..1000.  Chip 0: a scan module 0-600 holding two sorts
    # (100-300, 400-500) and an op that reads a sort's output (300-400);
    # a sort outside any module (620-640) and one in a path-edge module
    # (700-750).  Chip 1: a scan module 850-1200 holding a sort 900-1100,
    # clipped to 900-1000.  Event names are the HLO instructions' text.
    name = SORT if with_sorts else "%custom-call.9 = f32[8] custom-call()"
    reader = "%fusion.3 = s32[98304]{0} fusion(s32[98304]{0} %sort.12)"
    mods0 = [("jit__run_scan_batch(5)", 0, 600),
             ("jit__path_edge_tensor_compressed(3)", 700, 800)]
    ops0 = [(name, 100, 300), (reader, 300, 400), (name, 400, 500),
            (name, 620, 640), (name, 700, 750)]
    mods1 = [("jit__run_scan_batch(5)", 850, 1200)]
    ops1 = [(name, 900, 1100)]
    return tr.Trace(modules=[mods0, mods1], ops=[ops0, ops1],
                    spans=[("iteration", 0, 1000)], window=(0, 1000))


def test_scan_sorts_per_executed_step():
    # two cells of 1 full chunk + the 100 % 64 = 36-step tail each; only
    # the sorts inside a scan module count, clipped to the window
    run = _run([_result(chunks=1), _result(chunks=1)], _trace())
    steps = 2 * (64 + 36)
    assert _reader("scan_sort_ms_per_step.cell")(run) == \
        pytest.approx((200 + 100 + 100) / 1e6 / steps)


def test_scan_sorts_read_nothing_without_their_events():
    read = _reader("scan_sort_ms_per_step.cell")
    assert read(_run([_result()], None)) is None
    assert read(_run([_result()], _trace(with_sorts=False))) is None
    no_chunks = _result()
    del no_chunks.meta["sweep_chunks"]
    assert read(_run([no_chunks], _trace())) is None
