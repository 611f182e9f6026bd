"""The reader of ``cell_s``: the window's seconds over all the cells it
completed, every iteration counted."""

import os

import pytest

from chipbench import run as run_mod


def _reader(name):
    return run_mod.load_module(
        os.path.join(run_mod.HERE, "metrics", f"{name}.py")).read


def _run(walls, cells=1, window_s=None):
    r = run_mod.Run("cell", {}, {}, 1, None)
    r.iterations = [{"cells": cells, "wall_s": w} for w in walls]
    r.window_s = sum(walls) if window_s is None else window_s
    return r


def test_cell_s_is_the_window_over_its_cells():
    run = _run([0.7, 0.7, 1.6], cells=2, window_s=3.1)
    assert _reader("cell_s")(run) == pytest.approx(3.1 / 6)
    assert _reader("cell_s")(_run([])) is None


@pytest.mark.parametrize("walls,cells,window_s,expect", [
    ([0.70, 0.70, 0.70, 0.70], 1, None, 0.70),     # one cell an iteration
    ([0.70, 0.70, 0.70, 1.30], 1, None, 0.85),     # a slow one counts whole
    ([1.40, 1.40, 1.40], 2, None, 0.70),           # cells per iteration
    ([0.70, 0.70], 1, 1.50, 0.75),                 # time between iterations
], ids=["steady", "slow-iteration", "per-cell", "between-iterations"])
def test_cell_s_counts_every_iteration(walls, cells, window_s, expect):
    assert _reader("cell_s")(_run(walls, cells, window_s)) == \
        pytest.approx(expect)
