"""Host spans and counters of the simulator, on the profiler's clock.

``span(name)`` (context manager or decorator) records a span (name,
parent, start and end in ``time.time_ns()``, the clock the JAX profiler
stamps host events with) in every open :class:`Record`, and opens
``jax.profiler.TraceAnnotation("repro.<name>", cell=<cell id>)``, a no-op
unless a profile is captured.  ``count(name, n)`` adds to the open
records' counters; backend compiles and compile-cache loads count, with
their seconds, against the innermost open span (``compile.<span>``,
``compile_s.<span>``, ``cache_load.<span>``, ``cache_load_s.<span>``).
:func:`totals` reduces records to ``{name: [inclusive_s, calls]}`` and
counters.  Spans are always on.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = ["span", "count", "record", "Record", "Span", "totals"]

_clock = time.time_ns
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _State(threading.local):
    def __init__(self):
        self.stack: List[Span] = []          # open spans, innermost last
        self.records: List[Record] = []
        self.cache_hit = False               # the next compile is a load


_state = _State()


class Span:
    __slots__ = ("name", "parent", "start_ns", "end_ns")

    def __init__(self, name: str, parent: Optional["Span"]):
        self.name, self.parent = name, parent
        self.start_ns = self.end_ns = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Record:
    """Spans and counters closed while this record is open.  A record
    may be entered more than once (a sweep bucket is dispatched, then
    collected later); ``wall_s`` runs from its first entry to its last
    exit."""

    def __init__(self, cell: Optional[str] = None):
        self.cell = cell
        self.spans: List[Span] = []
        self.counts: Dict[str, float] = collections.Counter()
        self.start_ns: Optional[int] = None
        self.end_ns: Optional[int] = None

    def __enter__(self) -> "Record":
        if self.start_ns is None:
            self.start_ns = _clock()
        _state.records.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _state.records.pop()
        self.end_ns = _clock()

    @property
    def wall_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def seconds(self, name: str) -> float:
        """Inclusive seconds of the spans called ``name``."""
        return sum((s.seconds for s in self.spans if s.name == name), 0.0)

    def self_seconds(self, name: str) -> float:
        """Seconds of the spans called ``name`` outside their children."""
        kids = sum((s.seconds for s in self.spans
                    if s.parent is not None and s.parent.name == name
                    and s.parent in self.spans), 0.0)
        return self.seconds(name) - kids


record = Record


@contextlib.contextmanager
def span(name: str):
    sp = Span(name, _state.stack[-1] if _state.stack else None)
    cell = next((r.cell for r in reversed(_state.records)
                 if r.cell is not None), None)
    kw = {} if cell is None else {"cell": cell}
    with jax.profiler.TraceAnnotation(f"repro.{name}", **kw):
        _state.stack.append(sp)
        sp.start_ns = _clock()
        try:
            yield sp
        finally:
            sp.end_ns = _clock()
            _state.stack.pop()
            for r in _state.records:
                r.spans.append(sp)


def count(name: str, n: float = 1) -> None:
    for r in _state.records:
        r.counts[name] += n


def totals(*records: Record) -> Tuple[Dict[str, list], Dict[str, float]]:
    """``({name: [inclusive_s, calls]}, {counter: n})`` over ``records``."""
    spans: Dict[str, list] = {}
    counts: Dict[str, float] = collections.Counter()
    for r in records:
        for s in r.spans:
            tot = spans.setdefault(s.name, [0.0, 0])
            tot[0] += s.seconds
            tot[1] += 1
        counts.update(r.counts)
    return spans, dict(counts)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _state.cache_hit = True


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event != _COMPILE:
        return
    kind = "cache_load" if _state.cache_hit else "compile"
    _state.cache_hit = False
    where = _state.stack[-1].name if _state.stack else "untraced"
    count(f"{kind}.{where}")
    count(f"{kind}_s.{where}", secs)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
