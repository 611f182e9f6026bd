"""A run with the timed path broken underneath reads ``correct`` false.

Each fault is planted in the program for one run of the harness at a
CPU size (everything but the device check), with jax's caches cleared so
that the planted code is traced."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import run
from chipbench.tests._small import measure

SEED = 2147483701


@pytest.fixture
def fresh_programs():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _state_unchanged(monkeypatch):
    """Every scan step leaves the flow state as it was: the scan runs no
    step at all."""
    from repro.core import transport
    impl = transport._run_scan_impl

    def frozen(arrs, key0, cfg, static):
        e_tot, n_layers, _ = static
        return impl(arrs, key0, cfg, (e_tot, n_layers, 0))

    monkeypatch.setattr(transport, "_run_scan_impl", frozen)


def _half_batch_left_out(monkeypatch):
    """The second half of the flows is left out of every water-filling
    step, and the metrics are taken over the rest."""
    import jax.numpy as jnp
    from repro.core import transport
    step = transport.waterfill_step

    def half(edges, w, desired, cap, *, active=None, **kw):
        keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
        return step(edges, w * keep, desired * keep, cap,
                    active=None if active is None else active & keep, **kw)

    monkeypatch.setattr(transport, "waterfill_step", half)


def _answer_altered(monkeypatch):
    """One flow's answer is altered where the scan's end state is turned
    into per-flow results: it reads as never having sent a byte."""
    from repro.core import transport
    to_result = transport._to_result

    def altered(size, final, cfg, start=None):
        final = dict(final)
        rem = np.array(final["remaining"])
        dep = np.array(final["depart_step"])
        rem[0], dep[0] = size[0], -1
        final.update(remaining=rem, depart_step=dep)
        return to_result(size, final, cfg, start=start)

    monkeypatch.setattr(transport, "_to_result", altered)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch_left_out,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch", "answer"])
@pytest.mark.parametrize("routing", [0, 1], ids=["ecmp", "fatpaths"])
def test_planted_fault_reads_incorrect(plant, routing, monkeypatch,
                                       fresh_programs):
    plant(monkeypatch)
    result = measure(SEED, routing)
    assert not result["correct"], result["checks"]


_EXCHANGE = r'''
import sys
import jax
import numpy as np
jax.config.update("jax_enable_compilation_cache", False)
sys.path.insert(0, {root!r})
from chipbench.tests._small import measure
from repro.experiments import dist_sweep

ok = measure({seed}, routing=None, entry="dist_sweep", seeds=4, chips=4)
finalize = dist_sweep._finalize_bucket

def no_exchange(works, finals, elements):
    # Only the first chip's shard comes back; the other chips' elements
    # are read as copies of it.
    n_dev = 4
    out = {{}}
    for k, v in finals.items():
        v = np.asarray(v)
        per = len(v) // n_dev
        out[k] = np.concatenate([v[:per]] * n_dev)
    return finalize(works, out, elements)

dist_sweep._finalize_bucket = no_exchange
bad = measure({seed}, routing=None, entry="dist_sweep", seeds=4, chips=4)
print("RESULT", ok["correct"], bad["correct"])
'''


def test_exchange_between_chips_left_out_reads_incorrect():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _EXCHANGE.format(root=run.ROOT, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=run.ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT")][-1]
    assert line == "RESULT True False", p.stdout[-3000:]
