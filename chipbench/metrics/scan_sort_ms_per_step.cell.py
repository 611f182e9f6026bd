"""scan_sort_ms_per_step.cell: device milliseconds per simulated step of
the sorts of the link-sorted water-filling (``_sorted_waterfill`` in
``kernels/waterfill.py``).  On a v5e each sort is an operation of its
own on the ``XLA Ops`` line, an HLO ``sort`` instruction named
``sort.<n>`` (``%sort.186 = (s32[85919]{...}, s32[85919]{...},
f32[85919]{...}, f32[85919]{...}) sort(...)``), not wrapped in a fusion;
six of them run a step in both cells.  The reader takes the operations
whose instruction name starts with ``sort`` and that start inside a
module named ``scan`` on the same chip, clips them to the window, sums
them over the chips used and divides by the steps
``scan_ms_per_step.cell`` counts.  The ``while`` operations that hold
the sorts are not counted."""

import os

from chipbench import trace as trace_mod
from chipbench.run import load_module

SORT = r"^%?sort\b"


def read(run):
    if run.trace is None:
        return None
    secs = trace_mod.op_seconds_in_modules(run.trace, SORT, r"scan")
    here = os.path.dirname(os.path.abspath(__file__))
    steps = load_module(os.path.join(here, "scan_ms_per_step.cell.py")
                        ).executed_steps(run)
    if secs is None or not steps:
        return None
    return secs / steps * 1e3
