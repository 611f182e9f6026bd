"""finalise_ms.cell: host milliseconds per cell of the program's
``finalise`` spans: ``_to_result`` for each sim seed (per-flow FCTs from
the scan's end state) and the FCT metrics."""

from chipbench.spans import mean_per_cell


def read(run):
    secs = mean_per_cell(run, "finalise")
    return None if secs is None else secs * 1e3
