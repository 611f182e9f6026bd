"""setup_s: seconds from the start of the command to the start of the
window — import, compile cache, device check and one whole warm-up
iteration, which compiles or loads every program the window runs."""


def read(run):
    return run.setup_s
