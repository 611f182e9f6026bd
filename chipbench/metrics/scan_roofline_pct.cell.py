"""scan_roofline_pct.cell: the least time a simulated step can take —
the bytes ``chipbench.roofline.scan_step_bytes`` counts from the cell's
shapes, over the chip's peak HBM bandwidth — in percent of the measured
``scan_ms_per_step.cell``.  Bound by bytes: the step's operations per
byte are far below the chip's peak ratio."""

import os

from chipbench import roofline
from chipbench.run import load_module


def read(run):
    if run.peaks is None or not run.iterations:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    ms = load_module(os.path.join(here, "scan_ms_per_step.cell.py")).read(run)
    prepared = run.iterations[-1]["prepared"]
    if ms is None or len(prepared) != 1:
        return None
    (n_layers, n_flows, hop_slots), e_tot = prepared[0]
    cfg = run.config["reference"]
    reroute = cfg["routings"][0]["balancing"] != "ecmp"
    step_bytes = roofline.scan_step_bytes(
        n_flows, hop_slots, e_tot, n_layers,
        int(cfg["transport"]["fair_iters"]), reroute)
    least_s = step_bytes / float(run.peaks["hbm_bytes_per_s"])
    return least_s / (ms / 1e3) * 100.0
