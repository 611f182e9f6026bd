"""idle_pct.sweep: percent of the traced window in which no operation
ran, per chip, averaged over the chips of the sweep (one minus the
union of operation intervals over the window)."""

from chipbench import trace as trace_mod


def read(run):
    return trace_mod.idle_pct(run.trace)
