"""Entries: how a traffic mix drives the program, one module each.

A traffic mix names its entry (``"entry"`` in ``traffic/<name>.json``);
``entries/<entry>.py`` defines ``Entry(config, traffic, seed, chips,
hooks)`` with ``iteration()`` (one whole unit of the window, returning
its record) and ``cells()`` (what the last iteration produced, per cell,
for the check).  Both entries here build their cells with
``Session.grid`` from the configuration's topologies and routings, the
mix's pattern and ``cell_seeds`` seeds counted up from ``--seed``.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = ["grid", "check_cells"]


def grid(config: Dict, traffic: Dict, seed: int) -> List:
    from repro.experiments import Session

    spec = config["spec"]
    return Session().grid(spec["topologies"], spec["routings"],
                          [traffic["pattern"]], [spec["evaluator"]],
                          [seed + i for i in range(int(traffic["cell_seeds"]))])


def check_cells(session, specs: List, results: List, hooks,
                config: Dict) -> List[Dict]:
    """Per cell: the reference parameters and what the timed path made
    of it (tables from the session that ran it, the captured per-flow
    simulation, the RunResult's metrics)."""
    from .. import hooks as hooks_mod

    ref = config["reference"]
    n_routings = len(config["spec"]["routings"])
    per_topo = len(specs) // len(config["spec"]["topologies"])
    per_routing = per_topo // n_routings
    out = []
    for i, (spec, rr) in enumerate(zip(specs, results)):
        ti = i // per_topo
        ri = (i % per_topo) // per_routing
        routing = session.routing(spec.topo, spec.routing, seed=spec.seed)
        lr = routing.routing
        tables = [lr.nh]
        if getattr(lr, "compressed", None) is not None:
            tables.append(lr.compressed.dense())
        out.append({
            "name": rr.cell_id,
            "topology": ref["topologies"][ti], "routing": ref["routings"][ri],
            "seed": spec.seed, "tables": tables, "metrics": rr.metrics,
            "sim": hooks_mod.find_sim(hooks.sims, routing.balancing,
                                      int(rr.meta.get("n_flows", -1)),
                                      spec.seed)})
    return out
