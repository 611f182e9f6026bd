"""The check's control, at a cell's own size.

    python3 -m chipbench.control --workload <cell> --seeds <n> [<n> ...]

For each seed and each of the cell's cells, the plain reference is
computed twice, with its water-filling in float32 (the configuration's
precision) and in bfloat16, and the bfloat16 outputs are compared with
the float32 ones as the program's are in a run.  The numbers printed
are the control's readings; a limit has to lie below the smallest of
them.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys

from . import check, reference
from .run import HERE, ROOT, _json


def readings(config, traffic, seed):
    import ml_dtypes

    ref = config["reference"]
    seeds = [seed + i for i in range(int(traffic["cell_seeds"]))]
    per_cell = []
    for topo in ref["topologies"]:
        fab = reference.fabric(topo)
        for routing in ref["routings"]:
            for s in seeds:
                args = (topo, routing, ref["transport"], traffic["reference"],
                        s)
                tables = reference.routing_tables(fab, routing, s)
                exact = reference.run_cell(*args, tables=tables)
                low = reference.run_cell(*args, tables=tables,
                                         wf_dtype=ml_dtypes.bfloat16)
                per_cell.append(check.compare_cell(
                    [low["nh"]], low["depart"], low["delivered"],
                    low["metrics"], exact))
    return check.worst(per_cell)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cells = {w["name"]: w for w in _json(ROOT, "BENCHMARK.json")["workloads"]}
    cell = cells[args.workload]
    config = _json(HERE, "configs", f"{cell['config']}.json")
    traffic = _json(HERE, "traffic", f"{cell['traffic']}.json")
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings(config, traffic, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
