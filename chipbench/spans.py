"""Per-cell means of the program's own spans: ``RunResult.meta["spans"]``
is {name: [inclusive seconds, calls]}, written by ``repro.tracing``.  A
program without those spans gives None, not an error."""

from typing import Iterable, Optional


def mean_per_cell(run, name: str, less: Iterable[str] = ()
                  ) -> Optional[float]:
    """Mean over the window's cells of the seconds of span ``name`` less
    those of the spans ``less`` (0 where a cell has none); None where no
    cell carries ``name``."""
    vals = []
    for rec in run.iterations:
        for r in rec["results"]:
            spans = r.meta.get("spans") or {}
            if name in spans:
                vals.append(spans[name][0] - sum(
                    spans.get(k, [0.0])[0] for k in less))
    return sum(vals) / len(vals) if vals else None
