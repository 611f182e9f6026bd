"""sweep_scan_ms.sweep: device milliseconds per grid cell of the sweep's
bucket programs (XLA modules whose name holds ``scan``: ``dist_sweep``'s
``sweep_scan`` and ``sweep_scan_seeds``), summed over the chips used and
divided by the traced window's grid cells."""

from chipbench import trace as trace_mod


def read(run):
    if run.trace is None:
        return None
    secs = trace_mod.module_seconds(run.trace, r"scan")
    cells = sum(rec["cells"] for rec in run.iterations)
    if secs is None or not cells:
        return None
    return secs / cells * 1e3
