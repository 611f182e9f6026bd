"""Bring-up smoke run: the FatPaths simulator's main path on a TPU.

  python chip_smoke.py               # one chip, every phase below
  python chip_smoke.py --four-chips  # four chips: the shard_map sweep only

Everything runs in this one process, through the entry points a user
calls (``repro.experiments.Session`` and ``dist_sweep``).  Phases:

1. device   — a TPU must be present (else exit nonzero, naming what jax
   found instead); the kernels resolve to compiled Pallas.
2. cells    — sf(q=19) (722 routers, 10,830 endpoints) x permutation of
   4 MiB flows x transport(steps=2000) under fatpaths(n_layers=9,rho=0.6)
   and ecmp.  The flows outlast one 64-step scan chunk, so the chunked
   scan carries its state across chunks (checked: more than one chunk
   ran), and the two schemes must give different metrics.  Each cell
   runs twice in one Session: the first call pays the stack build and
   the compiles (set-up), the second is the run alone.  The fatpaths
   cell is run again, the same way, under REPRO_KERNEL_BACKEND=ref (the
   jnp water-filling oracle) and must agree with the TPU path (the
   link-sorted form) within the kernel-vs-oracle tolerances of
   tests/test_waterfill.py.  Each cell prints the water-filling form it
   ran.
3. minplus  — a fatpaths(scheme=ksp) stack on sf(q=5), whose (min, +)
   distances run the semiring kernel: its tables must be loop-free, and
   the dense and block-sparse kernels must equal the jnp oracle bitwise.
4. grid     — sf(q=5), sf(q=11) x ecmp, fatpaths x permutation through
   dist_sweep(devices=1), identical (rtol 0) to the sequential engine,
   every cell on the link-sorted water-filling.

``--four-chips`` runs only phase 4's grid over four cell seeds with
dist_sweep(devices=4), so every bucket takes the shard_map branch, and
compares it with the sequential engine at rtol 0.

Any failure exits nonzero.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

REAL_TOPO = "sf(q=19)"
REAL_SCHEMES = ("fatpaths(n_layers=9,rho=0.6)", "ecmp")
REAL_PATTERN = "permutation(flow_size=4194304.0)"
TRANSPORT = "transport(steps=2000)"
GRID = dict(topos=["sf(q=5)", "sf(q=11)"], routings=["ecmp", "fatpaths"],
            patterns=["permutation"], evaluators=[TRANSPORT])

# Pallas vs jnp-oracle agreement, as tests/test_waterfill.py holds the
# simulator to it: FCTs (and throughput, which is size / FCT) to rtol
# 1e-5, delivered-byte quantities to rtol 1e-4, completion exactly.
REF_RTOL = {"fct_mean_us": 1e-5, "fct_p50_us": 1e-5, "fct_p99_us": 1e-5,
            "tput_gbs": 1e-5, "link_util": 1e-4, "finished": 0.0}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def check_result(rr) -> None:
    """A cell came back from the measured path with finite metrics and
    bytes actually delivered."""
    check("error" not in rr.meta, f"{rr.cell_id}: {rr.meta.get('error')}")
    for k, v in rr.metrics.items():
        check(math.isfinite(v), f"{rr.cell_id}: metric {k} = {v}")
    check(rr.meta["delivered_bytes"] > 0,
          f"{rr.cell_id}: delivered {rr.meta['delivered_bytes']} bytes")


def phase_device(four_chips: bool):
    import jax

    from repro.kernels import interpret_default, kernel_backend

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU chip: jax finds {len(devs)} {d.platform} device(s) "
          f"({d.device_kind})")
    need = 4 if four_chips else 1
    check(len(devs) >= need, f"need {need} chips, jax sees {len(devs)}")
    say(f"# device: {d.device_kind} x {len(devs)} ({d.platform})")
    check(kernel_backend() == "pallas",
          f"kernel_backend() = {kernel_backend()!r} on a TPU")
    check(interpret_default(None) is False,
          "interpret_default(None) is not False on a TPU")
    return d, len(devs)


def timed_cell(session, spec):
    """Run one cell twice in one Session: the first call pays the stack
    build and the compiles (set-up), the second is the run alone."""
    t0 = time.perf_counter()
    first = session.run(spec)
    t1 = time.perf_counter()
    # Session.run returns host (numpy) results, so each wall below ends
    # after the device has finished.
    rr = session.run(spec)
    t2 = time.perf_counter()
    check_result(rr)
    check(first.metrics == rr.metrics,
          f"{spec.cell_id}: two runs in one session differ")
    say(f"# cell {spec.cell_id}: setup_s={(t1 - t0) - (t2 - t1):.3f} "
        f"(stack build {first.meta['build_s']:.3f}) "
        f"run_s={t2 - t1:.3f} chunks={rr.meta['sweep_chunks']} "
        f"flows={rr.meta['n_flows']} "
        f"fct_mean_us={rr.metrics['fct_mean_us']!r} "
        f"fct_p50_us={rr.metrics['fct_p50_us']!r} "
        f"fct_p99_us={rr.metrics['fct_p99_us']!r} "
        f"delivered_bytes={rr.meta['delivered_bytes']!r} "
        f"finished={rr.metrics['finished']!r} "
        f"waterfill={waterfill_form(rr)}")
    return rr


def waterfill_form(rr) -> str:
    """The water-filling form the cell's scan ran, from its counters."""
    forms = [k.split(".", 1)[1] for k in rr.meta["counts"]
             if k.startswith("waterfill.")]
    check(len(forms) == 1, f"{rr.cell_id}: water-filling forms {forms}")
    return forms[0]


@contextlib.contextmanager
def kernel_backend_env(name: str):
    """Run the enclosed cells with REPRO_KERNEL_BACKEND=name."""
    from repro.kernels import kernel_backend

    was = os.environ.get("REPRO_KERNEL_BACKEND")
    os.environ["REPRO_KERNEL_BACKEND"] = name
    try:
        check(kernel_backend() == name,
              f"REPRO_KERNEL_BACKEND={name} resolves to {kernel_backend()}")
        yield
    finally:
        if was is None:
            del os.environ["REPRO_KERNEL_BACKEND"]
        else:
            os.environ["REPRO_KERNEL_BACKEND"] = was


def phase_cells(session, topo: str = REAL_TOPO, schemes=REAL_SCHEMES,
                pattern: str = REAL_PATTERN,
                evaluator: str = TRANSPORT) -> None:
    from repro.experiments import ExperimentSpec

    def cell(scheme):
        return timed_cell(session, ExperimentSpec.make(
            topo, scheme, pattern, evaluator))

    runs = {scheme: cell(scheme) for scheme in schemes}
    for scheme, rr in runs.items():
        check(rr.meta["sweep_chunks"] > 1,
              f"{topo} {scheme}: the scan ran {rr.meta['sweep_chunks']} "
              f"chunk(s); the traffic must outlast one")
    a, b = (runs[s].metrics for s in schemes[:2])
    check(a != b, f"{topo}: {schemes[0]} and {schemes[1]} give identical "
          f"metrics {a}")
    scheme = schemes[0]
    pal = runs[scheme]
    # The kernel backend is part of the scan's jit-static config, so the
    # same cell under the override compiles and runs the jnp oracle.
    with kernel_backend_env("ref"):
        ref = cell(scheme)
    for k, rtol in REF_RTOL.items():
        a, b = pal.metrics[k], ref.metrics[k]
        check(abs(a - b) <= rtol * max(abs(a), abs(b)),
              f"{topo} {scheme}: pallas {k}={a!r} vs ref {b!r} "
              f"(rtol {rtol:g})")
    a, b = pal.meta["delivered_bytes"], ref.meta["delivered_bytes"]
    check(abs(a - b) <= 1e-4 * max(abs(a), abs(b)),
          f"{topo} {scheme}: pallas delivered {a!r} vs ref {b!r}")
    say(f"# pallas == ref within tolerance: {scheme} on {topo}")


def phase_minplus(session, topo: str = "sf(q=5)") -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import semiring_matmul, sparse_semiring_matmul

    lr = session.routing(topo, "fatpaths(scheme=ksp)").routing
    L, n, _ = lr.nh.shape
    report = lr.validate_loop_free(n_samples=L * n * (n - 1))
    check(report.ok and report.exhaustive, f"ksp tables: {report.describe()}")
    say(f"# ksp stack on {topo}: {report.n_checked} table entries "
        f"loop-free (build {lr.build_stats['device_s']:.3f}s on device)")

    adj = jnp.asarray(np.asarray(session.topology(topo).adj, bool))
    u = jax.random.uniform(jax.random.PRNGKey(0), (3, n, n))
    w = jnp.where(adj[None], 1.0 + 0.25 * u, jnp.inf)
    w = w.at[:, jnp.arange(n), jnp.arange(n)].set(0.0)
    operands = {"minplus": w, "count": adj.astype(jnp.float32),
                "bool": adj}
    for semiring, x in operands.items():
        want = np.asarray(semiring_matmul(x, x, semiring, backend="ref"))
        for kernel in (semiring_matmul, sparse_semiring_matmul):
            got = np.asarray(kernel(x, x, semiring))
            check(np.array_equal(got, want),
                  f"{kernel.__name__}({semiring}) != jnp oracle")
    say("# semiring and sparse kernels == jnp oracle, bitwise "
        "(count, bool, minplus)")


def phase_grid(devices: int, seeds=(0,), grid=None) -> None:
    from repro.experiments import Session, compare_results
    from repro.experiments.dist_sweep import dist_sweep

    grid = dict(grid or GRID, seeds=list(seeds))
    t0 = time.perf_counter()
    seq = Session().sweep(**grid)
    t1 = time.perf_counter()
    s = Session()
    logs = []
    dist = dist_sweep(s, s.grid(**grid), devices=devices, log=logs.append)
    t2 = time.perf_counter()
    for line in logs:
        say(line)
    for rr in seq + dist:
        check_result(rr)
    if devices > 1:
        buckets = [m for m in logs if " programs via " in m]
        check(buckets and all(f"shard_map[{devices}]" in m
                              for m in buckets),
              f"not every bucket took the shard_map branch: {buckets}")
    diffs = compare_results(seq, dist)
    check(diffs == [], f"dist_sweep(devices={devices}) != sequential: "
          f"{diffs[:5]}")
    forms = {waterfill_form(rr) for rr in seq + dist}
    check(forms == {"sorted"}, f"grid water-filling forms {forms}")
    say(f"# dist_sweep(devices={devices}) == sequential at rtol 0: "
        f"{len(dist)} cells (sequential {t1 - t0:.3f}s, "
        f"dist {t2 - t1:.3f}s, both cold)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map sweep")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke: FAIL: src/repro not found next to chip_smoke.py",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    try:
        dev, count = phase_device(args.four_chips)
        say(f"# compile cache: {cache}")
        if args.four_chips:
            phase_grid(devices=4, seeds=(0, 1, 2, 3))
        else:
            from repro.experiments import Session

            session = Session()
            phase_cells(session)
            phase_minplus(session)
            phase_grid(devices=1)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
