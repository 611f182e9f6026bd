"""What the benchmark attaches to the program under test.

* Capture: every :class:`repro.core.transport.SimResult` the timed path
  builds (the per-flow end state of each simulation) and the shapes of
  every prepared scan operand set (``transport.prepare``).  The check
  compares the first; the per-layer readers count from the second.
* Spans (traced runs only): ``jax.profiler.TraceAnnotation`` around the
  program's layer calls, named ``chipbench.<layer>``, so that idle gaps
  on the device can be attributed to what the host was doing.  A layer
  call the program no longer has is skipped, not an error.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Tuple

__all__ = ["Hooks", "SPANS"]

# (module, attribute, span name): the calls into each layer.
SPANS = (
    ("repro.experiments.session", "Session.resolve", "resolve"),
    ("repro.core.transport", "prepare", "prepare"),
    ("repro.core.transport", "_to_result", "finalise"),
    ("repro.experiments.dist_sweep", "_dispatch_bucket", "dispatch"),
    ("repro.experiments.dist_sweep", "_finalize_bucket", "collect"),
)


def _wrap(module_name: str, attr: str, make: Callable,
          undo: List) -> None:
    import importlib
    owner = importlib.import_module(module_name)
    path = attr.split(".")
    for part in path[:-1]:
        owner = getattr(owner, part, None)
        if owner is None:
            return
    fn = getattr(owner, path[-1], None)
    if fn is None:
        return
    setattr(owner, path[-1], functools.wraps(fn)(make(fn)))
    undo.append((owner, path[-1], fn))


class Hooks:
    """Installed before the warm-up; :meth:`remove` puts the program
    back as it was."""

    def __init__(self, spans: bool):
        self.sims: List = []
        self.prepared: List[Tuple[Tuple[int, ...], int]] = []
        self._undo: List = []

        def capture(init):
            def call(obj, *a, **k):
                init(obj, *a, **k)
                self.sims.append(obj)
            return call

        def shapes(fn):
            def call(*a, **k):
                arrs, static = fn(*a, **k)
                self.prepared.append((tuple(arrs["path_edges"].shape),
                                      int(static[0])))
                return arrs, static
            return call

        _wrap("repro.core.transport", "SimResult.__init__", capture,
              self._undo)
        _wrap("repro.core.transport", "prepare", shapes, self._undo)
        if spans:
            for module_name, attr, name in SPANS:
                _wrap(module_name, attr, functools.partial(_span, name),
                      self._undo)

    def remove(self) -> None:
        while self._undo:
            owner, name, fn = self._undo.pop()
            setattr(owner, name, fn)

    def reset(self) -> None:
        self.sims.clear()
        self.prepared.clear()


def _span(name: str, fn: Callable) -> Callable:
    import jax

    def call(*a, **k):
        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            return fn(*a, **k)
    return call


def find_sim(sims: List, balancing: str, n_flows: int, seed: int
             ) -> Optional[object]:
    """The captured simulation of one cell: its balancing, flow count
    and sim seed identify it within one iteration."""
    for s in sims:
        if (s.config.balancing == balancing and len(s.size) == n_flows
                and int(s.config.seed) == int(seed)):
            return s
    return None
