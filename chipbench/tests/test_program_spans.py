"""The readers of the program's spans and of the water-filling kernel's
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


def _trace(with_kernel=True):
    # Window 0..1000.  Chip 0: the kernel 100-300 and 400-500, an op that
    # reads its output 300-400; chip 1: the kernel 900-1100, clipped to
    # 900-1000.  Event names are the HLO instructions' text.
    name = ("%waterfill.9 = (f32[10880,1]) custom-call(s32[10880,4] %pad.61)"
            if with_kernel else "%custom-call.9 = f32[8] custom-call()")
    reader = "%fusion.3 = f32[10830] fusion(f32[10880,1] %waterfill.9)"
    ops0 = [(name, 100, 300), (reader, 300, 400), (name, 400, 500)]
    ops1 = [(name, 900, 1100)]
    return tr.Trace(modules=[[], []], ops=[ops0, ops1],
                    spans=[("iteration", 0, 1000)], window=(0, 1000))


def test_waterfill_kernel_per_executed_step():
    # two cells of 1 full chunk + the 100 % 64 = 36-step tail each
    run = _run([_result(chunks=1), _result(chunks=1)], _trace())
    steps = 2 * (64 + 36)
    assert _reader("waterfill_kernel_ms_per_step.cell")(run) == \
        pytest.approx((200 + 100 + 100) / 1e6 / steps)


def test_waterfill_kernel_reads_nothing_without_its_events():
    read = _reader("waterfill_kernel_ms_per_step.cell")
    assert read(_run([_result()], None)) is None
    assert read(_run([_result()], _trace(with_kernel=False))) is None
    no_chunks = _result()
    del no_chunks.meta["sweep_chunks"]
    assert read(_run([no_chunks], _trace())) is None
