"""sweep_resolve_host_ms.sweep: host milliseconds per grid cell of the
path engine inside ``dist_sweep``: each cell's own ``cell`` span (its
``Session.resolve`` and transport plan) less its ``stack.device`` span
(the table program from dispatch to ``block_until_ready``), averaged
over the window's grid cells."""

from chipbench.spans import mean_per_cell


def read(run):
    secs = mean_per_cell(run, "cell", less=("stack.device",))
    return None if secs is None else secs * 1e3
