"""Everything BENCHMARK.json names is found by name under chipbench/."""

import json
import os

import pytest

from chipbench import check, run

BENCH = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_found_and_complete(cfg):
    path = os.path.join(run.ROOT, cfg["file"])
    data = json.load(open(path))
    assert data["name"] == cfg["name"]
    for key in ("spec", "reference", "source", "deployment"):
        assert key in data, key
    assert len(data["spec"]["topologies"]) == len(
        data["reference"]["topologies"])
    assert len(data["spec"]["routings"]) == len(data["reference"]["routings"])
    assert set(cfg["reduced"]) <= set(data.get("reduced", {}))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_configuration_traffic_entry_and_limits(cell):
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert os.path.exists(os.path.join(run.HERE, "configs",
                                       f"{cell['config']}.json"))
    traffic = json.load(open(os.path.join(run.HERE, "traffic",
                                          f"{cell['traffic']}.json")))
    assert os.path.exists(os.path.join(run.HERE, "entries",
                                       f"{traffic['entry']}.py"))
    assert set(check.load_limits(cell["name"])) == set(check.NUMBERS)
    for kind in ("end_to_end", "per_layer"):
        assert run.applicable(BENCH[kind], cell["name"]), kind


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found(metric):
    mod = run.load_module(os.path.join(run.HERE, "metrics",
                                       f"{metric['name']}.py"))
    assert callable(mod.read)
    names = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", names)) <= names


def test_every_per_layer_metric_moves_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(target.get("workloads",
                                                     m["workloads"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        run._peaks("TPU v99", require_accelerator=True)
    assert run._peaks("TPU v5 lite", True)["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_spec_strings_and_reference_parameters_agree(cell):
    """What the program is asked to run (the spec strings, with the
    program's defaults filled in) is what the reference computes."""
    from repro.experiments.catalog import (EVALUATORS, ROUTINGS,
                                           TOPOLOGIES, TRAFFIC)
    from repro.experiments.specs import Spec

    cfg = json.load(open(os.path.join(run.HERE, "configs",
                                      f"{cell['config']}.json")))
    traffic = json.load(open(os.path.join(run.HERE, "traffic",
                                          f"{cell['traffic']}.json")))
    spec, ref = cfg["spec"], cfg["reference"]
    for text, params in zip(spec["topologies"], ref["topologies"]):
        name, kw = Spec.parse(text).name, TOPOLOGIES.resolve(
            Spec.parse(text))[1]
        assert (name, kw["q"]) == (params["family"], params["q"])
    for text, params in zip(spec["routings"], ref["routings"]):
        s = Spec.parse(text)
        kw = ROUTINGS.resolve(s)[1]
        assert s.name == params["balancing"]
        if s.name == "ecmp":
            assert kw["n"] == params["n_tables"]
        else:
            assert (kw["n_layers"], kw["rho"], kw["scheme"]) == (
                params["n_layers"], params["rho"], "rand")
    ev = EVALUATORS.resolve(Spec.parse(spec["evaluator"]))[1]
    tr = ref["transport"]
    assert (ev["steps"], ev["chunk"], ev["seeds"], ev["transport"]) == (
        tr["steps"], tr["chunk"], tr["sim_seeds"], "ndp")
    assert (ev["dt"], ev["flowlet_gap"]) == (tr["dt"], tr["flowlet_gap"])
    pat = Spec.parse(traffic["pattern"])
    kw = TRAFFIC.resolve(pat)[1]
    assert pat.name == traffic["reference"]["kind"]
    assert kw["flow_size"] == traffic["reference"]["flow_size"]
    assert bool(kw["randomize"]) == traffic["reference"]["randomize"]
    from repro.core.transport import SimConfig
    sim = SimConfig()
    for key in ("line_rate", "link_latency", "sw_latency", "gap_eps",
                "fair_iters", "max_hops"):
        assert getattr(sim, key) == tr[key], key
