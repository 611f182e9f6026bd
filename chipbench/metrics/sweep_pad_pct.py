"""sweep_pad_pct: percent of the flow x hop slots that ``dist_sweep``'s
bucketing padded in.  Padded slots are, per bucket, its programs times
the padded flow count F and hop slots H of its ``log=`` line; real slots
are, per prepared cell, its flows times its own hop slots (one sim seed
per cell)."""

import re

_BUCKET = re.compile(r"= (\d+) programs via .* padded to F=(\d+) E=\d+ H=(\d+)")


def read(run):
    padded = real = 0
    for rec in run.iterations:
        for line in rec["logs"]:
            m = _BUCKET.search(line)
            if m:
                n, f, h = (int(g) for g in m.groups())
                padded += n * f * h
        for (_, f, s), _ in rec["prepared"]:
            real += f * s
        if any(int(r.meta.get("n_seeds", 1)) != 1 for r in rec["results"]):
            return None
    if not padded:
        return None
    return (1.0 - real / padded) * 100.0
