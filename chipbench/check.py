"""The comparison that decides a run's ``correct``.

Each cell the window ran is compared with the plain reference
(``chipbench/reference.py``) on what the timed path itself produced: the
forwarding tables held by the window's last Session, the per-flow end
state of the last iteration's simulation, and the evaluator metrics of
its ``RunResult``.  Four numbers come out, each the worst over the
cells of the run, and each is held to the limit that
``chipbench/limits/<workload>.json`` sets for the workload:

* ``tables_mismatched`` — (layer, router, destination) next-hop entries
  that differ from the reference's, in the dense tables and in the
  compressed form the path walk reads;
* ``depart_pct`` — percent of flows whose completion step (-1 while in
  flight at the horizon) differs from the reference's;
* ``delivered_pct`` — the largest gap in one flow's delivered bytes, in
  percent of its size;
* ``metrics_pct`` — the largest relative gap, in percent, over the
  evaluator metrics ``fct_p50_us``, ``fct_p99_us``, ``fct_mean_us``,
  ``tput_gbs``, ``link_util`` and ``finished``.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["NUMBERS", "EVALUATOR_METRICS", "compare_cell", "worst",
           "load_limits", "verdict"]

NUMBERS = ("tables_mismatched", "depart_pct", "delivered_pct", "metrics_pct")
EVALUATOR_METRICS = ("fct_p50_us", "fct_p99_us", "fct_mean_us", "tput_gbs",
                     "link_util", "finished")

_HERE = os.path.dirname(os.path.abspath(__file__))


def _rel_gap(a: float, b: float) -> float:
    if (math.isnan(a) and math.isnan(b)) or a == b:
        return 0.0
    if math.isnan(a) or math.isnan(b):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)


def compare_cell(tables: List[np.ndarray], depart: np.ndarray,
                 delivered: np.ndarray, metrics: Dict[str, float],
                 ref: Dict) -> Dict[str, float]:
    """The four numbers for one cell.  ``tables`` are the program's
    (L, N, N) next-hop stacks (dense and, where it keeps one, the dense
    expansion of its compressed form); ``ref`` is what
    :func:`chipbench.reference.run_cell` returned."""
    mism = 0
    for nh in tables:
        nh = np.asarray(nh)
        if nh.shape != ref["nh"].shape:
            mism = max(mism, int(ref["nh"].size))
        else:
            mism = max(mism, int((nh != ref["nh"]).sum()))
    depart = np.asarray(depart)
    delivered = np.asarray(delivered, np.float64)
    if depart.shape != ref["depart"].shape:
        depart_pct, delivered_pct = 100.0, 100.0
    else:
        depart_pct = float((depart != ref["depart"]).mean() * 100.0)
        delivered_pct = float(np.max(np.abs(
            delivered - np.asarray(ref["delivered"], np.float64)))
            / float(ref["flow_size"]) * 100.0)
    gaps = [_rel_gap(float(metrics.get(k, math.nan)),
                     float(ref["metrics"][k])) for k in EVALUATOR_METRICS]
    return {"tables_mismatched": float(mism), "depart_pct": depart_pct,
            "delivered_pct": delivered_pct,
            "metrics_pct": float(max(gaps) * 100.0)}


def worst(per_cell: List[Dict[str, float]]) -> Dict[str, float]:
    return {k: max(c[k] for c in per_cell) for k in NUMBERS}


def load_limits(workload: str, root: Optional[str] = None) -> Dict[str, float]:
    path = os.path.join(root or _HERE, "limits", f"{workload}.json")
    with open(path) as f:
        limits = json.load(f)["limits"]
    missing = [k for k in NUMBERS if k not in limits]
    if missing:
        raise KeyError(f"{path} sets no limit for {missing}")
    return {k: float(limits[k]) for k in NUMBERS}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}): correct when every number
    is finite and at most its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    ok = all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
             for k in NUMBERS)
    return ok, table
