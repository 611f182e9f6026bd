"""waterfill_kernel_ms_per_step.cell: device milliseconds of the
water-filling kernel per simulated step.  The kernel's time is that of
the operations on the ``XLA Ops`` line whose own HLO instruction name
holds ``waterfill`` (the program names its Pallas call so; an event's
name is the instruction's text, ``%waterfill.9 = ... custom-call(...)``,
and only the part before `` = `` names the operation), clipped to the
window and summed over the chips used; the steps are those
``scan_ms_per_step.cell`` counts.  ``scan_ms_per_step.cell`` less this
is the per-step cost of the scan outside the kernel."""

import os

from chipbench.run import load_module


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    clipped = [min(b, hi) - max(a, lo) for ops in run.trace.ops
               for name, a, b in ops
               if "waterfill" in name.split(" = ", 1)[0]]
    ns = sum(d for d in clipped if d > 0)
    if not ns:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    steps = load_module(os.path.join(here, "scan_ms_per_step.cell.py")
                        ).executed_steps(run)
    if not steps:
        return None
    return ns / 1e6 / steps
