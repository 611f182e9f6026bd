"""A cell at a size the CPU tests can hold: the grid configuration's
sf(q=5) fabric, one routing, one cell seed, the session entry."""

import copy
import json
import os

from chipbench import check, run


def small_cell(routing: int = 0, entry: str = "session", seeds: int = 1,
               chips: int = 1):
    """(bench, cell, config, traffic, limits): sf(q=5) x the grid's
    routing ``routing`` (0 ecmp, 1 fatpaths), or both when ``routing`` is
    None, under the sweep cell's traffic and limits."""
    cfg = json.load(open(os.path.join(run.HERE, "configs",
                                      "sf_small_grid.json")))
    cfg = copy.deepcopy(cfg)
    cfg["spec"]["topologies"] = cfg["spec"]["topologies"][:1]
    cfg["reference"]["topologies"] = cfg["reference"]["topologies"][:1]
    if routing is not None:
        cfg["spec"]["routings"] = [cfg["spec"]["routings"][routing]]
        cfg["reference"]["routings"] = [cfg["reference"]["routings"][routing]]
    traffic = json.load(open(os.path.join(run.HERE, "traffic",
                                          "sweep4.json")))
    traffic.update(entry=entry, cell_seeds=seeds)
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = {"name": "sf_small_grid.sweep4", "config": "sf_small_grid",
            "traffic": "sweep4", "chips": chips}
    return bench, cell, cfg, traffic, check.load_limits(cell["name"])


def measure(seed: int, routing: int = 0, entry: str = "session",
            seeds: int = 1, chips: int = 1, traced: bool = False):
    bench, cell, cfg, traffic, limits = small_cell(routing, entry, seeds,
                                                   chips)
    return run.measure(bench, cell, cfg, traffic, limits, seed, 0.0, traced,
                       require_accelerator=False)
