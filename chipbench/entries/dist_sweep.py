"""One grid per iteration: a fresh ``Session()`` and ``dist_sweep`` over
the cell's chips (``shard_map`` over the element axis where a bucket has
at least as many elements as chips)."""

from __future__ import annotations

from . import check_cells, grid


class Entry:
    def __init__(self, config, traffic, seed, chips, hooks):
        self.config, self.hooks, self.chips = config, hooks, chips
        self.specs = grid(config, traffic, seed)

    def iteration(self):
        from repro.experiments import Session
        from repro.experiments.dist_sweep import dist_sweep

        self.hooks.reset()
        self.session = Session()
        logs = []
        self.results = dist_sweep(self.session, self.specs,
                                  devices=self.chips, log=logs.append)
        return {"cells": len(self.results), "results": self.results,
                "logs": logs}

    def cells(self):
        return check_cells(self.session, self.specs, self.results,
                           self.hooks, self.config)
