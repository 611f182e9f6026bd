"""Experiments CLI: error paths (unknown names, out-of-whitelist
parameters), ``list`` output, single-cell ``run``, artifact ``diff``,
and where ``--devices N`` finds its devices."""

import json
import os
import sys

import pytest

from repro.experiments.__main__ import _ensure_devices, main

QUICK = ["--evaluator", "transport(steps=30)"]


# ---- error paths ------------------------------------------------------------
def test_unknown_topology_exits_2(capsys):
    rc = main(["sweep", "--topos", "notatopo", "--schemes", "ecmp",
               "--patterns", "uniform", "--quick"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "unknown topology" in err and "notatopo" in err
    assert "sf" in err                      # lists the valid options


def test_unknown_scheme_exits_2(capsys):
    rc = main(["run", "--topo", "clique(k=6)", "--scheme", "ospf",
               "--pattern", "uniform", *QUICK])
    assert rc == 2
    assert "unknown routing scheme" in capsys.readouterr().err


def test_out_of_whitelist_parameter_exits_2(capsys):
    rc = main(["run", "--topo", "clique(k=6)",
               "--scheme", "fatpaths(layers=9)",     # 'n_layers', not 'layers'
               "--pattern", "uniform", *QUICK])
    assert rc == 2
    err = capsys.readouterr().err
    assert "no parameter" in err and "n_layers" in err


def test_malformed_spec_exits_2(capsys):
    rc = main(["run", "--topo", "sf(q=5", "--scheme", "ecmp",
               "--pattern", "uniform", *QUICK])
    assert rc == 2
    assert "error:" in capsys.readouterr().err


# ---- list -------------------------------------------------------------------
def test_list_covers_registered_axes(capsys):
    assert main(["list"]) == 0
    out = capsys.readouterr().out
    for section in ("topologies:", "routing schemes:", "traffic patterns:",
                    "evaluators:"):
        assert section in out
    for name in ("sf(", "fatpaths(", "adversarial(", "transport("):
        assert name in out
    assert "n_layers=9" in out              # defaults are shown


# ---- run: one cell ----------------------------------------------------------
def test_run_single_cell_emits_runresult_json(capsys, tmp_path):
    out_json = str(tmp_path / "cell.json")
    rc = main(["run", "--topo", "clique(k=6)", "--scheme", "ecmp(n=2)",
               "--pattern", "uniform", "--evaluator", "transport(steps=30)",
               "--seed", "3", "--json", out_json])
    assert rc == 0
    d = json.loads(capsys.readouterr().out)
    assert d["topo"] == "clique(k=6)" and d["seed"] == 3
    assert d["metrics"]["finished"] > 0
    [on_disk] = json.load(open(out_json))
    assert on_disk == d


def test_run_quick_caps_unpinned_steps(capsys):
    rc = main(["run", "--topo", "clique(k=6)", "--scheme", "ecmp(n=2)",
               "--pattern", "uniform", "--evaluator", "transport(steps=25)",
               "--quick"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out)
    assert d["evaluator"] == "transport(steps=25)"   # pinned steps survive


# ---- sweep --filter ----------------------------------------------------------
def test_sweep_filter_runs_matching_subset(capsys):
    rc = main(["sweep", "--topos", "clique(k=6)",
               "--schemes", "ecmp(n=2),fatpaths(n_layers=3)",
               "--patterns", "uniform",
               "--evaluators", "transport(steps=30)",
               "--filter", "fatpaths"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "1 of 2 cell(s)" in out
    assert "fatpaths" in out
    assert "# 1 cells" in out               # only the matching cell ran


def test_sweep_filter_no_match_exits_2_with_cell_list(capsys):
    rc = main(["sweep", "--topos", "clique(k=6)",
               "--schemes", "ecmp(n=2),fatpaths(n_layers=3)",
               "--patterns", "uniform",
               "--evaluators", "transport(steps=30)",
               "--filter", "nosuchcell"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "matches none of the 2 grid cell(s)" in err
    # the full cell list is printed so the user can fix the filter
    assert "clique(k=6)/ecmp(n=2)/uniform/transport(steps=30)@s0" in err
    assert "clique(k=6)/fatpaths(n_layers=3)/uniform/transport(steps=30)@s0" \
        in err


# ---- diff -------------------------------------------------------------------
@pytest.fixture()
def artifact(tmp_path):
    path = str(tmp_path / "sweep.json")
    rc = main(["sweep", "--topos", "clique(k=6)", "--schemes", "ecmp(n=2)",
               "--patterns", "uniform", "--evaluators", "transport(steps=30)",
               "--json", path])
    assert rc == 0
    return path


def test_diff_identical_and_differing(artifact, capsys, tmp_path):
    assert main(["diff", artifact, artifact]) == 0
    assert "identical" in capsys.readouterr().out

    mutated = json.load(open(artifact))
    mutated[0]["metrics"]["fct_p50_us"] += 1.0
    other = str(tmp_path / "other.json")
    json.dump(mutated, open(other, "w"))
    assert main(["diff", artifact, other]) == 1
    cap = capsys.readouterr()
    assert "fct_p50_us" in cap.out and "difference" in cap.err


# ---- --devices N: forced host devices only where the caller chose the CPU --
@pytest.mark.parametrize("platforms,forced", [("cpu", True), (None, False),
                                              ("tpu", False)])
def test_ensure_devices_forces_host_devices_only_on_cpu(monkeypatch,
                                                        platforms, forced):
    """Before jax loads, ``--devices 4`` forces four host CPU devices
    only under ``JAX_PLATFORMS=cpu``; unset (jax picks the host's
    accelerator) or an accelerator platform leaves the chips alone."""
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    _ensure_devices(4)
    want = "--xla_force_host_platform_device_count=4" if forced else None
    assert os.environ.get("XLA_FLAGS") == want
    assert os.environ.get("JAX_PLATFORMS") == platforms
