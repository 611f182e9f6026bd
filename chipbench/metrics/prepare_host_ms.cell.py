"""prepare_host_ms.cell: host milliseconds of ``transport._prepare`` per
cell, the program's ``prepare`` span less its ``prepare.device`` span
(the path-edge program through the ``hmax`` sync): operand dispatch and
the numpy schedule placement."""

from chipbench.spans import mean_per_cell


def read(run):
    secs = mean_per_cell(run, "prepare", less=("prepare.device",))
    return None if secs is None else secs * 1e3
