"""Benchmark aggregator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] \\
      [--json PATH]

Emits ``name,us_per_call,derived`` CSV lines per benchmark; ``--json``
additionally dumps ``{name: {us_per_call, derived, derived_raw}}`` so
the perf trajectory tracks quality (throughput, FCT, collisions)
alongside speed.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
import traceback

MODULES = ["bench_diversity", "bench_collisions", "bench_layers",
           "bench_transport", "bench_throughput", "bench_kernels",
           "bench_sparse", "bench_fabric", "bench_sweep", "bench_failures"]

# k=v pairs whose value is a number (optionally with a trailing unit,
# e.g. "tput=2.74GB/s"), a bool, or nan/inf.  Keys are anchored at a
# word boundary from the left (start or whitespace) so digit-led names
# like "1ring_ms" parse whole and range values ("links=9->27") don't
# spawn phantom keys.
_DERIVED_RE = re.compile(
    r"(?:^|(?<=\s))([A-Za-z0-9_][\w.%'/-]*)="
    r"([-+]?(?:\d+\.?\d*|\.\d+)(?:[eE][-+]?\d+)?|True|False|nan|inf)")


def parse_derived(derived: str) -> dict:
    """Best-effort numeric parse of a derived-metrics string."""
    out = {}
    for key, val in _DERIVED_RE.findall(derived):
        if val in ("True", "False"):
            out[key] = val == "True"
        else:
            out[key] = float(val)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="",
                    help="dump {name: {us_per_call, derived}} to this path")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        print(f"# === {mod_name} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main(quick=args.quick)
        except Exception as e:
            failures.append((mod_name, repr(e)))
            traceback.print_exc()
        print(f"# {mod_name} done in {time.time() - t0:.1f}s", flush=True)
    if args.json:
        from benchmarks.common import ROWS
        out = {name: {"us_per_call": us,
                      "derived": parse_derived(derived),
                      "derived_raw": derived}
               for name, us, derived in ROWS}
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {len(ROWS)} rows to {args.json}", flush=True)
    if failures:
        for f in failures:
            print("FAILED:", f, file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
