"""sweep_host_ms.sweep: host milliseconds per grid cell of
``dist_sweep``'s bucket work: each bucket's ``prepare`` spans less their
``prepare.device`` spans (operand building on the host), its
``sweep.pad`` span (padding and stacking) and its ``sweep.dispatch``
span (the launch of the bucket's program), summed over the window's
buckets and divided by its grid cells.

A bucket's spans ride in the meta of every member cell, so each bucket
is counted once: by its ``meta["sweep_bucket"]`` within an iteration."""

BUCKET_SPANS = ("sweep.pad", "sweep.dispatch")


def read(run):
    total, cells, seen = 0.0, 0, False
    for rec in run.iterations:
        cells += rec["cells"]
        buckets = {}
        for r in rec["results"]:
            if "sweep_bucket" in r.meta and "spans" in r.meta:
                buckets.setdefault(r.meta["sweep_bucket"], r.meta["spans"])
        for spans in buckets.values():
            if "prepare" not in spans:
                continue
            seen = True
            total += (spans["prepare"][0]
                      - spans.get("prepare.device", [0.0])[0]
                      + sum(spans.get(k, [0.0])[0] for k in BUCKET_SPANS))
    return total / cells * 1e3 if seen and cells else None
