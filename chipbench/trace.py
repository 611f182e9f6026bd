"""Reduce a JAX profiler trace to the benchmark's device numbers.

A trace is read once (:func:`from_xplane`) into plain interval lists:
per used chip, the XLA modules and operations that ran on it, and the
benchmark's own host spans (names starting ``chipbench.``).  Everything
after that works on those lists:

* busy time is the union of a chip's operation intervals inside the
  traced window, averaged over the chips used; idle share is one minus
  busy over the window;
* a module's device time is the sum of its events' durations, matched
  by a regular expression on the module name;
* an operation's device time within a module is the sum of the
  durations of the operations matched by their HLO instruction name
  that start inside such a module's interval on the same chip;
* each idle gap (between merged busy intervals of the first chip used)
  is attributed to the innermost benchmark span that holds its midpoint.

Times are in nanoseconds inside, seconds outside.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["Trace", "from_xplane", "find_xplane", "union_ns", "busy_s",
           "window_s", "idle_pct", "module_seconds", "op_seconds_in_modules",
           "top_modules", "idle_gaps", "SPAN_PREFIX"]

SPAN_PREFIX = "chipbench."
Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    modules: List[List[Interval]]            # per chip used
    ops: List[List[Interval]]                # per chip used
    spans: List[Interval]                    # benchmark host spans
    window: Tuple[float, float]              # first span start, last end


_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_xplane(path: str, chips: int) -> Trace:
    """Read the first ``chips`` TPU device planes and the benchmark's
    host spans.  The window runs from the first ``chipbench.iteration``
    span's start to the last one's end."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Tuple[List[Interval], List[Interval]]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            mods: List[Interval] = []
            ops: List[Interval] = []
            for line in plane.lines:
                dest = {"XLA Modules": mods, "XLA Ops": ops}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
            devices[int(m.group(1))] = (mods, ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    used = sorted(devices)[:chips]
    iters = [s for s in spans if s[0] == "iteration"]
    window = ((min(s[1] for s in iters), max(s[2] for s in iters))
              if iters else (0.0, 0.0))
    return Trace(modules=[devices[d][0] for d in used],
                 ops=[devices[d][1] for d in used], spans=spans,
                 window=window)


def _merged(intervals: List[Interval], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    """Intervals clipped to [lo, hi], sorted and merged."""
    out: List[Tuple[float, float]] = []
    for _, a, b in sorted(intervals, key=lambda x: x[1]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_ns(intervals: List[Interval], lo: float, hi: float) -> float:
    return sum(b - a for a, b in _merged(intervals, lo, hi))


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the chips used;
    None when the trace holds no device operation."""
    if not trace.ops or not any(trace.ops):
        return None
    lo, hi = trace.window
    return sum(union_ns(ops, lo, hi) for ops in trace.ops) / len(
        trace.ops) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_pct(trace: Optional[Trace]) -> Optional[float]:
    """Percent of the window in which no operation ran (per chip,
    averaged over the chips used); None without a device operation."""
    if trace is None:
        return None
    busy = busy_s(trace)
    if busy is None or window_s(trace) <= 0:
        return None
    return (1.0 - busy / window_s(trace)) * 100.0


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def module_seconds(trace: Trace, pattern: str) -> Optional[float]:
    """Device seconds of the modules whose name matches ``pattern``,
    summed over the chips used; None when no module matches."""
    rx = re.compile(pattern)
    lo, hi = trace.window
    hits = [(n, max(a, lo), min(b, hi)) for mods in trace.modules
            for n, a, b in mods if rx.search(_module_name(n))]
    hits = [h for h in hits if h[2] > h[1]]
    if not hits:
        return None
    return sum(b - a for _, a, b in hits) / 1e9


def op_seconds_in_modules(trace: Trace, op_pattern: str,
                          module_pattern: str) -> Optional[float]:
    """Device seconds of the operations whose HLO instruction name (the
    event name's part before `` = ``) matches ``op_pattern`` and that
    start inside a module whose name matches ``module_pattern`` on the
    same chip, clipped to the window and summed over the chips used;
    None when no such operation ran in the window."""
    op_rx, mod_rx = re.compile(op_pattern), re.compile(module_pattern)
    lo, hi = trace.window
    total, hit = 0.0, False
    for mods, ops in zip(trace.modules, trace.ops):
        # one chip runs one module at a time: intervals do not overlap
        held = sorted((a, b) for n, a, b in mods
                      if mod_rx.search(_module_name(n)))
        starts = [a for a, _ in held]
        for name, a, b in ops:
            if not op_rx.search(name.split(" = ", 1)[0]):
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= held[i][1]:
                continue
            d = min(b, hi) - max(a, lo)
            if d > 0:
                total, hit = total + d, True
    return total / 1e9 if hit else None


def top_modules(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` modules with the most device time: [[name, seconds]]."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for mods in trace.modules:
        for n, a, b in mods:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                tot[_module_name(n)] = tot.get(_module_name(n), 0.0) + b - a
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in top]


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps of the first chip used, each named by
    the innermost benchmark span holding its midpoint ("untraced" where
    none does): [[span, seconds]]."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    busy = _merged(trace.ops[0], lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2
        holders = [s for s in trace.spans if s[1] <= mid < s[2]]
        name = (min(holders, key=lambda s: s[2] - s[1])[0] if holders
                else "untraced")
        out.append([name, (b - a) / 1e9])
    return out
