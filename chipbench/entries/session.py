"""One cell per iteration: a fresh ``Session()`` and ``Session.run``.

This is the user's path from spec to ``RunResult``: topology, stack
build, ``_prepare``, the transport scan and finalisation, with no
artifact carried over from an earlier iteration."""

from __future__ import annotations

from . import check_cells, grid


class Entry:
    def __init__(self, config, traffic, seed, chips, hooks):
        self.config, self.hooks = config, hooks
        self.specs = grid(config, traffic, seed)
        if len(self.specs) != 1:
            raise ValueError("the session entry runs one cell; "
                             f"{len(self.specs)} were configured")

    def iteration(self):
        from repro.experiments import Session

        self.hooks.reset()
        self.session = Session()
        self.results = [self.session.run(self.specs[0])]
        return {"cells": 1, "results": self.results, "logs": []}

    def cells(self):
        return check_cells(self.session, self.specs, self.results,
                           self.hooks, self.config)
