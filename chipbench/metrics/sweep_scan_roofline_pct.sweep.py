"""sweep_scan_roofline_pct.sweep: the least time the window's grid cells
need in the scan, in percent of the chip-seconds the sweep's bucket
programs took (modules named ``scan``, summed over the chips used).

The least time is, over every real cell of the window, the bytes
``chipbench.roofline.scan_step_bytes`` counts at the cell's own unpadded
shapes (layers L, flows F, hop slots H and virtual links of its
``transport.prepare``, with its own routing's re-roll term) times the
steps it executed, over the chip's peak HBM bandwidth.  Padding and
chips left idle therefore lower the share.  Executed steps are counted
as ``scan_ms_per_step.cell`` counts them.

``dist_sweep`` prepares cells bucket by bucket, in grid order within a
bucket, so the iteration's prepared shapes line up with its results
ordered by ``meta["sweep_bucket"]`` and then grid position."""

from chipbench import roofline
from chipbench import trace as trace_mod


def least_seconds(run):
    cfg = run.config["reference"]["transport"]
    chunk, steps = int(cfg["chunk"]), int(cfg["steps"])
    fair_iters = int(cfg["fair_iters"])
    total = 0
    for rec in run.iterations:
        results = rec["results"]
        if len(rec["prepared"]) != len(results) or any(
                k not in r.meta for r in results
                for k in ("sweep_bucket", "sweep_chunks", "balancing")):
            return None
        order = sorted(range(len(results)),
                       key=lambda i: (results[i].meta["sweep_bucket"], i))
        for i, ((n_layers, n_flows, hop_slots), e_tot) in zip(
                order, rec["prepared"]):
            meta = results[i].meta
            executed = ((int(meta["sweep_chunks"]) * chunk + steps % chunk)
                        * int(meta.get("n_seeds", 1)))
            total += executed * roofline.scan_step_bytes(
                n_flows, hop_slots, e_tot, n_layers, fair_iters,
                meta["balancing"] != "ecmp")
    return total / float(run.peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None or run.peaks is None or not run.iterations:
        return None
    secs = trace_mod.module_seconds(run.trace, r"scan")
    least = least_seconds(run)
    if secs is None or least is None:
        return None
    return least / secs * 100.0
