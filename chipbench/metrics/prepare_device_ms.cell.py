"""prepare_device_ms.cell: device milliseconds per cell of the path-edge
programs ``_prepare`` runs before the scan (XLA modules whose name holds
``path_edge``)."""

from chipbench import trace as trace_mod


def read(run):
    if run.trace is None or not run.iterations:
        return None
    secs = trace_mod.module_seconds(run.trace, r"path_edge")
    if secs is None:
        return None
    cells = sum(rec["cells"] for rec in run.iterations)
    return secs / cells * 1e3
