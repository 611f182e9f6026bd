"""idle_pct.cell: percent of the traced window in which no operation ran
on the chip (one minus the union of operation intervals over the
window)."""

from chipbench import trace as trace_mod


def read(run):
    return trace_mod.idle_pct(run.trace)
