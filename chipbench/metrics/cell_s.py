"""cell_s: the window's seconds over the whole ``Session.run`` cells it
completed, each on a fresh Session: the mean wall a user waits from spec
to ``RunResult``, taken over all the work and all the time of the window
(which starts after set-up and ends with the last whole iteration)."""


def read(run):
    cells = sum(rec["cells"] for rec in run.iterations)
    return run.window_s / cells if cells else None
