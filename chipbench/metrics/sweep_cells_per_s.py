"""sweep_cells_per_s: grid cells completed by whole ``dist_sweep`` calls,
over the window's seconds."""


def read(run):
    cells = sum(rec["cells"] for rec in run.iterations)
    return cells / run.window_s if run.window_s > 0 else None
