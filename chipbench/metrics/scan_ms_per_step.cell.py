"""scan_ms_per_step.cell: device milliseconds of the transport scan
program (XLA modules whose name holds ``scan``) per simulated step it
executed.  Executed steps per simulation are the adaptive horizon's full
chunks (``sweep_chunks``) times the chunk length, plus the tail of
``steps % chunk`` steps that always runs, times the sim seeds."""

from chipbench import trace as trace_mod


def executed_steps(run):
    cfg = run.config["reference"]["transport"]
    chunk, steps = int(cfg["chunk"]), int(cfg["steps"])
    total = 0
    for rec in run.iterations:
        for r in rec["results"]:
            if "sweep_chunks" not in r.meta:
                return None
            total += ((int(r.meta["sweep_chunks"]) * chunk + steps % chunk)
                      * int(r.meta.get("n_seeds", 1)))
    return total


def read(run):
    if run.trace is None:
        return None
    secs = trace_mod.module_seconds(run.trace, r"scan")
    steps = executed_steps(run)
    if secs is None or not steps:
        return None
    return secs / steps * 1e3
