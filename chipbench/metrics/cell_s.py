"""cell_s: window seconds per whole ``Session.run`` cell, the wall a user
waits from spec to ``RunResult`` (a fresh Session each time)."""


def read(run):
    cells = sum(rec["cells"] for rec in run.iterations)
    return run.window_s / cells if cells else None
