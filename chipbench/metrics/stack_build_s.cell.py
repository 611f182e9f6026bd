"""stack_build_s.cell: seconds the path engine spent building a cell's
artifacts (topology, forwarding tables or layer stack, workload), the
program's own ``RunResult.meta["build_s"]``, averaged over the traced
window's cells."""


def read(run):
    builds = [r.meta["build_s"] for rec in run.iterations
              for r in rec["results"] if "build_s" in r.meta]
    return sum(builds) / len(builds) if builds else None
