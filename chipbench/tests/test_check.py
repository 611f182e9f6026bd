"""The check on the CPU: the program passes it, the control fails it.

The control is the plain reference computed with its water-filling in
bfloat16, the precision below the configuration's float32, put in the
program's place."""

import json
import os

import pytest

from chipbench import check, control, run
from chipbench.tests._small import measure

SEEDS = (2147483659, 3000000019)


@pytest.mark.parametrize("routing", [0, 1], ids=["ecmp", "fatpaths"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_the_check(seed, routing):
    result = measure(seed, routing)
    assert result["correct"], result["checks"]
    assert result["checks"]["tables_mismatched"]["value"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_control_fails_the_check(seed):
    """At the grid cell's own size (16 cells), as on the chip."""
    cfg = json.load(open(os.path.join(run.HERE, "configs",
                                      "sf_small_grid.json")))
    traffic = json.load(open(os.path.join(run.HERE, "traffic",
                                          "sweep4.json")))
    limits = check.load_limits("sf_small_grid.sweep4")
    ok, table = check.verdict(control.readings(cfg, traffic, seed), limits)
    assert not ok, table
