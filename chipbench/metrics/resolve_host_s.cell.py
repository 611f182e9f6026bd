"""resolve_host_s.cell: host seconds of ``Session.resolve`` per cell, the
program's ``resolve`` span less its ``stack.device`` span (the table
program from dispatch to ``block_until_ready``): the topology, the layer
sampling, the table conversion and compression, and the workload draw."""

from chipbench.spans import mean_per_cell


def read(run):
    return mean_per_cell(run, "resolve", less=("stack.device",))
