"""The benchmark's one command.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout.  Everything a cell is comes from data
found by name: the cell in ``BENCHMARK.json``, its configuration in
``chipbench/configs/<config>.json``, its traffic mix in
``chipbench/traffic/<traffic>.json`` (which names the entry,
``chipbench/entries/<entry>.py``), each metric's reader in
``chipbench/metrics/<metric>.py``, the limits of the check in
``chipbench/limits/<cell>.json`` and the chip's peaks in
``chipbench/peaks.json``.

Set-up (counted in ``setup_s``, from the start of this module to the
start of the window): import, the compile cache, the device check, and
one whole warm-up iteration through the same entry and seed as the
window, which compiles every program the window runs.  The window then
starts whole iterations until ``--seconds`` have passed and ends with
the last one; it records each iteration's wall, and every iteration
counts in the metrics (``cell_s`` is the window's seconds over all its
cells).  With ``--trace 1`` the window is profiled, each iteration and
each layer call is wrapped in a ``chipbench.*`` span, and the per-layer
metrics are read from the trace and the iterations' records; without
it the end-to-end metrics are printed.  After the window the last
iteration's outputs are compared with the plain reference
(``chipbench/check.py``).

Earlier lines of standard output describe each iteration; the last line
is the JSON result.  The last lines of standard error are the numbers
the check compared, each beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, the command exits 1 and prints no
result.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

from . import check, hooks as hooks_mod, reference  # noqa: E402
from . import trace as trace_mod  # noqa: E402

__all__ = ["main", "Run", "load_module", "applicable"]


def _json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a metric's reader by path: names like ``idle_pct.cell`` are
    not identifiers."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applicable(entries: List[Dict], workload: str) -> List[Dict]:
    """Metrics of BENCHMARK.json that this cell reports."""
    return [m for m in entries if workload in m.get("workloads", [workload])]


class Run:
    """What a metric reader sees: the cell, its set-up and window, each
    iteration's record, the reduced trace (traced runs) and the peaks of
    the chip it ran on."""

    def __init__(self, workload, config, traffic, chips, peaks):
        self.workload, self.config, self.traffic = workload, config, traffic
        self.chips, self.peaks = chips, peaks
        self.setup_s = 0.0
        self.window_s = 0.0
        self.iterations: List[Dict] = []
        self.trace: Optional[trace_mod.Trace] = None


class _CompileCounter:
    """Counts backend compiles and persistent-cache loads while active."""

    def __init__(self):
        import jax.monitoring as mon
        self.active = False
        self.compiles = 0
        self.cache_loads = 0

        def on_duration(event, _secs, **_kw):
            if self.active and event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.cache_loads += 1

        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def close(self) -> None:
        import jax.monitoring as mon
        on_duration, on_event = self._listeners
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)


def _devices(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    if require_accelerator and (devs[0].platform != "tpu"
                                or len(devs) < chips):
        print(f"chipbench: this cell needs {chips} TPU chip(s); jax found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        raise SystemExit(1)
    return devs[:chips]


def _peaks(kind: str, require_accelerator: bool) -> Optional[Dict]:
    table = _json(HERE, "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if require_accelerator:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return None


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _compare(entry, config: Dict, traffic: Dict) -> Dict[str, float]:
    per_cell = []
    for cell in entry.cells():
        ref = reference.run_cell(cell["topology"], cell["routing"],
                                 config["reference"]["transport"],
                                 traffic["reference"], cell["seed"])
        sim = cell["sim"]
        if sim is None:
            nums = {k: float("inf") for k in check.NUMBERS}
        else:
            nums = check.compare_cell(cell["tables"], sim.depart_step,
                                      sim.delivered, cell["metrics"], ref)
        print(json.dumps({"checked": cell["name"], **nums}), flush=True)
        per_cell.append(nums)
    return check.worst(per_cell)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = _json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        ap.error(f"unknown workload {args.workload!r}; "
                 f"choose from {sorted(cells)}")
    cell = cells[args.workload]
    measure(bench, cell, _json(HERE, "configs", f"{cell['config']}.json"),
            _json(HERE, "traffic", f"{cell['traffic']}.json"),
            check.load_limits(args.workload), args.seed, args.seconds,
            bool(args.trace))
    return 0


def measure(bench: Dict, cell: Dict, config: Dict, traffic: Dict,
            limits: Dict[str, float], seed: int, seconds: float,
            traced: bool, require_accelerator: bool = True) -> Dict:
    """Set up, run the window and the check of one cell; print the
    iteration lines, the checks and the result line; return the result.
    ``require_accelerator=False`` lets the CPU tests drive everything
    but the device check."""
    chips = int(cell["chips"])
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    devices = _devices(chips, require_accelerator)
    peaks = _peaks(devices[0].device_kind, require_accelerator)
    run = Run(cell["name"], config, traffic, chips, peaks)
    hooks = hooks_mod.Hooks(spans=traced)
    try:
        result = _measure(bench, run, hooks, devices, limits, seed, seconds,
                          traced)
    finally:
        hooks.remove()
    return result


def _measure(bench, run, hooks, devices, limits, seed, seconds, traced):
    import jax

    entry = importlib.import_module(
        f"chipbench.entries.{run.traffic['entry']}").Entry(
        run.config, run.traffic, seed, run.chips, hooks)
    counter = _CompileCounter()

    entry.iteration()                                   # warm-up
    run.setup_s = time.perf_counter() - _T0

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced \
        else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = True
    t_win = time.perf_counter()
    while True:
        t_it = time.perf_counter()
        if trace_dir:
            with jax.profiler.TraceAnnotation("chipbench.iteration"):
                rec = entry.iteration()
        else:
            rec = entry.iteration()
        rec["wall_s"] = time.perf_counter() - t_it
        rec["prepared"] = list(hooks.prepared)
        run.iterations.append(rec)
        if time.perf_counter() - t_win >= seconds:
            break
    run.window_s = time.perf_counter() - t_win
    counter.active = False
    counter.close()
    if trace_dir:
        jax.profiler.stop_trace()
        run.trace = trace_mod.from_xplane(trace_mod.find_xplane(trace_dir),
                                          run.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}
    attempted = failed = 0
    for i, rec in enumerate(run.iterations):
        metas = [r.meta for r in rec["results"]]
        failed += sum(1 for r in rec["results"]
                      if "error" in r.meta or not r.metrics)
        attempted += rec["cells"]
        print(json.dumps({
            "iteration": i, "wall_s": rec["wall_s"], "cells": rec["cells"],
            "sweep_chunks": [m.get("sweep_chunks") for m in metas],
            "n_flows": [m.get("n_flows") for m in metas],
            "delivered_bytes": [m.get("delivered_bytes") for m in metas],
            "build_s": [m.get("build_s") for m in metas]}), flush=True)
    for line in (run.iterations[-1]["logs"] if run.iterations else []):
        print(line, flush=True)
    print(json.dumps({"setup_s": run.setup_s, "window_s": run.window_s,
                      "iterations": len(run.iterations),
                      "window_compiles": counter.compiles,
                      "window_cache_loads": counter.cache_loads,
                      "device": device}), flush=True)

    t_ref = time.perf_counter()
    numbers = _compare(entry, run.config, run.traffic)
    correct, checks = check.verdict(numbers, limits)
    print(json.dumps({"reference_s": time.perf_counter() - t_ref}),
          flush=True)

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in applicable(bench[kind], run.workload):
        value = load_module(os.path.join(HERE, "metrics",
                                         f"{m['name']}.py")).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = trace_mod.busy_s(run.trace)
        device["window_s"] = trace_mod.window_s(run.trace)
        result["breakdown"] = {
            "device_ops": trace_mod.top_modules(run.trace),
            "idle_gaps": trace_mod.idle_gaps(run.trace)}
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    sys.exit(main())
