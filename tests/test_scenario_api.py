"""Scenario API: spec serialization, registry, CLI, and the equivalence
guarantee — `run_scenario` composes the existing layers without touching
their arithmetic, so a spec-driven run is bit-for-bit the hand-wired glue
it replaced (checked against the pre-API builders across all three
engines)."""
import dataclasses
import json

import numpy as np
import pytest

from repro.api import (SCENARIOS, Scenario, build_scenario, get_scenario,
                       list_scenarios, run_scenario, scenario_names,
                       variants, with_overrides)
from repro.api.cli import main as cli_main
from repro.api.spec import (SPEC_FORMAT, SPEC_VERSION, ManagerSpec,
                            NodeSpec, TelemetrySpec, WorkloadSpec)
from repro.configs import get_config
from repro.core.backends import ClusterSimBackend, SimBackend
from repro.core.c3sim import NodeSim, SimConfig
from repro.core.cluster import ClusterConfig, ClusterSim
from repro.core.manager import (FleetManagerConfig, ManagerConfig,
                                run_closed_loop, run_fleet_closed_loop)
from repro.core.thermal import MI300X_PRESET, ChurnEvent, ChurnModel
from repro.core.workload import fsdp_llm_iteration
from repro.telemetry import TelemetryCollector


# --------------------------------------------------------------------------- #
# spec serialization
# --------------------------------------------------------------------------- #
def _odd_scenario() -> Scenario:
    """A scenario exercising the tricky serialization corners: non-repr-
    friendly floats, NaN/Inf, nested churn models, int-keyed dicts."""
    return Scenario(
        name="test/odd",
        workload=WorkloadSpec(arch="llama3.1-8b", n_layers=4),
        sim=SimConfig(seed=3, noise=0.1 + 0.2, comm_gbps=1e9 / 3.0),
        node=NodeSpec(caps_w=float("nan")),
        fleet=ClusterConfig(
            n_nodes=2, tp_gbps=float("inf"),
            churn={1: ChurnModel(drift_rate=0.125,
                                 events=[ChurnEvent(2.5, 3, 1.0 / 3.0)])}),
        manager=ManagerSpec(scope="fleet", tune_after=7,
                            config=FleetManagerConfig(
                                max_adjustment=1.0 / 7.0)),
        telemetry=TelemetrySpec(max_samples=17, keep_truth=True),
        iterations=9, seed=11)


def test_json_round_trip_is_exact():
    sc = _odd_scenario()
    text = sc.to_json()
    sc2 = Scenario.from_json(text)
    # dict-level identity covers every float bit pattern (NaN encoded as
    # {"$float": "nan"}, so == is well-defined)
    assert sc.to_dict() == sc2.to_dict()
    assert sc2.to_json() == text
    # spot-check the decoded values really came back as the same doubles
    assert sc2.sim.noise == 0.1 + 0.2
    assert sc2.sim.comm_gbps == 1e9 / 3.0
    assert np.isnan(sc2.node.caps_w)
    assert np.isinf(sc2.fleet.tp_gbps)
    assert sc2.fleet.churn[1].events[0].factor == 1.0 / 3.0
    assert isinstance(sc2.manager.config, FleetManagerConfig)
    assert sc2.manager.config.max_adjustment == 1.0 / 7.0


def test_json_is_valid_strict_json():
    # NaN/Inf must never leak as bare tokens (json.dumps allow_nan=False)
    text = _odd_scenario().to_json()
    json.loads(text)                      # strict parse
    assert "NaN" not in text and "Infinity" not in text


def test_save_load_file(tmp_path):
    p = str(tmp_path / "sc.json")
    sc = _odd_scenario()
    sc.save(p)
    assert Scenario.load(p).to_dict() == sc.to_dict()


def test_version_and_format_guards():
    sc = Scenario()
    doc = json.loads(sc.to_json())
    assert doc["format"] == SPEC_FORMAT and doc["version"] == SPEC_VERSION
    newer = dict(doc, version=SPEC_VERSION + 1)
    with pytest.raises(ValueError, match="newer than supported"):
        Scenario.from_json(json.dumps(newer))
    unversioned = {k: v for k, v in doc.items() if k != "version"}
    with pytest.raises(ValueError, match="no version"):
        Scenario.from_json(json.dumps(unversioned))
    with pytest.raises(ValueError, match="not a lit-silicon-scenario"):
        Scenario.from_json(json.dumps({"format": "something-else",
                                       "version": 1}))


def test_unknown_keys_rejected_at_every_level():
    good = Scenario().to_dict()
    bad_top = dict(good, bogus_knob=1)
    with pytest.raises(ValueError, match="bogus_knob"):
        Scenario.from_dict(bad_top)
    bad_nested = json.loads(json.dumps(good))
    bad_nested["sim"]["kappa_typo"] = 0.5
    with pytest.raises(ValueError, match=r"scenario\.sim.*kappa_typo"):
        Scenario.from_dict(bad_nested)
    bad_fleet = _odd_scenario().to_dict()
    bad_fleet["fleet"]["churn"]["1"]["events"][0]["when"] = 3
    with pytest.raises(ValueError, match="when"):
        Scenario.from_dict(bad_fleet)


def test_omitted_keys_take_defaults():
    sc = Scenario.from_dict({"workload": {"arch": "mistral-7b"}})
    assert sc.workload.arch == "mistral-7b"
    assert sc.workload.batch == WorkloadSpec().batch
    assert sc.fleet is None and sc.manager is None


def test_scope_validation():
    with pytest.raises(ValueError, match="requires a fleet"):
        Scenario(manager=ManagerSpec(scope="fleet",
                                     config=FleetManagerConfig())).validate()
    with pytest.raises(ValueError, match="scope='fleet'"):
        Scenario(fleet=ClusterConfig(n_nodes=2),
                 manager=ManagerSpec(scope="node")).validate()
    with pytest.raises(ValueError, match="unknown device preset"):
        Scenario(node=NodeSpec(preset="h100")).validate()


def test_with_overrides_and_variants():
    sc = get_scenario("cluster/dp")
    sc2 = with_overrides(sc, {"fleet.n_nodes": 8, "sim.noise": 0.004,
                              "manager.tune_after": 3})
    assert sc2.fleet.n_nodes == 8 and sc2.sim.noise == 0.004
    assert sc2.manager.tune_after == 3
    assert sc.fleet.n_nodes == 4                 # base untouched
    with pytest.raises((KeyError, ValueError)):
        with_overrides(sc, {"fleet.n_knobs": 8})
    grid = variants("cluster/dp", {"fleet.n_nodes": [1, 2],
                                   "fleet.topology": ["dp", "pp"]})
    assert len(grid) == 4
    labels = [lbl for lbl, _ in grid]
    assert labels[0] == "fleet.n_nodes=1,fleet.topology=dp"
    assert {s.fleet.n_nodes for _, s in grid} == {1, 2}


# --------------------------------------------------------------------------- #
# registry completeness
# --------------------------------------------------------------------------- #
def test_registry_lists_the_issue_scenarios():
    names = scenario_names()
    for required in ("paper/table1-tdp", "paper/node-cap", "paper/cpu-slosh",
                     "cluster/dp", "cluster/pp", "cluster/tp",
                     "cluster/hetero-cooling", "cluster/churn",
                     "telemetry/rocm-smi-like", "telemetry/replay"):
        assert required in names
    rows = list_scenarios()
    assert len(rows) == len(names)
    assert all(desc for _, _, desc in rows)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_registry_scenario_round_trips_and_smoke_runs(name):
    sc = get_scenario(name)
    assert sc.name == name
    assert Scenario.from_json(sc.to_json()).to_dict() == sc.to_dict()
    res = run_scenario(sc, iterations=2)
    assert res.iterations == 2
    tput = res.metrics.get(
        "throughput", res.metrics.get(
            "fleet_tput", res.metrics.get("tokens_per_s")))
    assert np.isfinite(tput)
    if sc.telemetry is not None:
        assert res.metrics["telemetry_samples"] >= 1


def test_get_scenario_returns_fresh_instances():
    a, b = get_scenario("cluster/dp"), get_scenario("cluster/dp")
    a.fleet.n_nodes = 99
    assert b.fleet.n_nodes == 4


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #
def test_cli_list_and_show(capsys):
    assert cli_main(["list"]) == 0
    out = capsys.readouterr().out
    assert "cluster/dp" in out and "paper/table1-tdp" in out
    assert cli_main(["show", "cluster/dp"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["format"] == SPEC_FORMAT


def test_cli_unknown_scenario_exits_2(capsys):
    assert cli_main(["show", "no/such-scenario"]) == 2
    assert cli_main(["run", "no/such-scenario"]) == 2
    err = capsys.readouterr().err
    assert "available:" in err


def test_cli_run_json(capsys, tmp_path):
    # the acceptance-criteria invocation
    out_file = str(tmp_path / "res.json")
    assert cli_main(["run", "cluster/dp", "--iterations", "2", "--json",
                     "--out", out_file]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["scenario"] == "cluster/dp" and doc["iterations"] == 2
    assert np.isfinite(doc["metrics"]["fleet_tput"])
    with open(out_file) as f:
        assert json.load(f)["metrics"] == doc["metrics"]


def test_cli_run_spec_file_and_overrides(capsys, tmp_path):
    p = str(tmp_path / "sc.json")
    get_scenario("paper/characterization").save(p)
    assert cli_main(["run", "--spec", p, "--iterations", "2",
                     "--set", "workload.n_layers=2", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["iterations"] == 2
    # a bad override is a usage error, not a crash
    assert cli_main(["run", "--spec", p, "--set", "sim.bogus=1"]) == 2


def test_cli_sweep(capsys):
    assert cli_main(["sweep", "paper/characterization", "--iterations", "2",
                     "--grid", "workload.n_layers=2,4", "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert len(rows) == 2
    assert rows[0]["variant"] == "workload.n_layers=2"


def test_sweep_spec_json_round_trip():
    """SweepSpec survives JSON exactly — the `{"$float": ...}` discipline
    the scenario codec uses covers the sweep document too."""
    from repro.api.sweep import SWEEP_SPEC_FORMAT, Dist, SweepSpec
    spec = SweepSpec(
        scenario="cluster/dp", samples=7, seed=3, iterations=None,
        dists={"fleet.straggler_boost": Dist(kind="uniform", low=0.1 + 0.2,
                                             high=1e9 / 3.0),
               "sim.noise": Dist(kind="choice",
                                 choices=[0.002, float("inf"), None])},
        node_preset_pool=["mi300x", "mi300x-air"],
        grid=None)
    text = spec.to_json()
    json.loads(text)                          # strict JSON, no NaN/Inf tokens
    assert "Infinity" not in text
    back = SweepSpec.from_json(text)
    assert back == spec
    assert back.dists["fleet.straggler_boost"].low == 0.1 + 0.2
    assert np.isinf(back.dists["sim.noise"].choices[1])
    doc = json.loads(text)
    assert doc["format"] == SWEEP_SPEC_FORMAT
    # unknown keys are rejected loudly, at both levels
    with pytest.raises(ValueError, match="bogus"):
        SweepSpec.from_dict(dict(spec.to_dict(), bogus=1))
    bad = json.loads(json.dumps(spec.to_dict()))
    bad["dists"]["sim.noise"]["width"] = 2
    with pytest.raises(ValueError, match="width"):
        SweepSpec.from_dict(bad)


def test_sweep_samples_are_prefix_stable():
    """Sample k of an N-sample sweep equals sample k of an M-sample sweep
    (per-sample child generators) — growing a population never reshuffles
    the part already run."""
    from repro.api.sweep import Dist, SweepSpec, _sample_overrides
    base = get_scenario("cluster/dp")
    kw = dict(scenario="cluster/dp", seed=9,
              dists={"fleet.straggler_boost": Dist(low=1.1, high=1.5)},
              node_preset_pool=["mi300x", "mi300x-air"])
    big = _sample_overrides(SweepSpec(samples=8, **kw), base)
    small = _sample_overrides(SweepSpec(samples=4, **kw), base)
    assert big[:4] == small


def test_cli_sweep_mc(capsys, tmp_path):
    # the acceptance-criteria invocation (scaled down)
    out_file = str(tmp_path / "sweep.json")
    assert cli_main(["sweep", "cluster/dp", "--samples", "3",
                     "--iterations", "30", "--json", "--out",
                     out_file]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["format"] == "lit-silicon-sweep" and doc["n_samples"] == 3
    assert doc["mode"] == "mc"
    assert {"samples", "summary", "reference",
            "sweep_spec"} <= set(doc)
    with open(out_file) as f:
        assert json.load(f) == doc
    # a sweep spec file drives the same path; --samples still overrides
    from repro.api.sweep import SweepSpec
    spec_file = str(tmp_path / "spec.json")
    SweepSpec.from_dict(doc["sweep_spec"]).save(spec_file)
    assert cli_main(["sweep", "--sweep-spec", spec_file, "--samples", "2",
                     "--json"]) == 0
    doc2 = json.loads(capsys.readouterr().out)
    assert doc2["n_samples"] == 2
    # prefix stability end to end: shrinking the population keeps sample 0
    assert doc2["samples"][0]["label"] == doc["samples"][0]["label"]
    # node-scoped scenarios are a usage error, not a crash
    assert cli_main(["sweep", "paper/node-cap", "--samples", "2"]) == 2
    # naming a different scenario than the spec file is a usage error
    assert cli_main(["sweep", "cluster/tp", "--sweep-spec",
                     spec_file]) == 2


def test_cli_replay(capsys, tmp_path):
    p = str(tmp_path / "trace.jsonl")
    sc = get_scenario("telemetry/rocm-smi-like")
    run_scenario(sc, iterations=12, save_trace_path=p)
    assert cli_main(["replay", p, "--json", "--use-case", "gpu-red"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["scope"] == "node" and "final_caps" in doc
    assert cli_main(["replay", str(tmp_path / "missing.jsonl")]) == 2


# --------------------------------------------------------------------------- #
# equivalence guards: the facade adds no arithmetic
# --------------------------------------------------------------------------- #
def _wl8():
    cfg = get_config("llama3.1-8b").replace(n_layers=8)
    return fsdp_llm_iteration(cfg, batch=2, seq=4096, n_shards=8)


@pytest.mark.parametrize("engine", ["event", "batched", "vector", "jax"])
def test_cluster_dp_scenario_matches_hand_wired_bit_for_bit(engine):
    """`run_scenario` on ``cluster/dp`` == the pre-API ClusterSim +
    FleetPowerManager composition, float for float, per engine."""
    iters, tune = (12, 4) if engine == "event" else (24, 6)
    sc = get_scenario("cluster/dp")
    sc.fleet.engine = engine
    sc.manager.tune_after = tune
    res = run_scenario(sc, iterations=iters)

    cl = ClusterSim(_wl8(), MI300X_PRESET,
                    SimConfig(seed=1, comm_gbps=40.0),
                    ClusterConfig(n_nodes=4, straggler_boost=1.28,
                                  engine=engine),
                    devices_per_node=8, seed=5)
    for n in range(4):
        cl.set_node_caps(n, np.full(8, 700.0))
    mgr = run_fleet_closed_loop(
        ClusterSimBackend(cl),
        FleetManagerConfig(use_case="gpu-realloc", sampling_period=2,
                           warmup=2, window_size=2, node_window_size=2,
                           power_cap=700.0,
                           cluster_power_budget=4 * 8 * 700.0),
        iters, tune_after=tune)

    assert len(cl.history) == len(res.cluster.history) == iters
    for a, b in zip(cl.history, res.cluster.history):
        assert a["t_fleet"] == b["t_fleet"]
        assert np.array_equal(a["t_local"], b["t_local"])
        assert np.array_equal(a["lead"], b["lead"])
        assert np.array_equal(a["node_power"], b["node_power"])
    assert len(mgr.budget_log) == len(res.manager.budget_log)
    assert all(np.array_equal(x, y) for x, y in
               zip(mgr.budget_log, res.manager.budget_log))
    assert np.array_equal(mgr.node_budgets, res.manager.node_budgets)
    for n in range(4):
        assert np.array_equal(cl.get_node_caps(n),
                              res.cluster.get_node_caps(n))
        assert all(np.array_equal(x, y) for x, y in
                   zip(mgr.managers[n].adjust_log,
                       res.manager.managers[n].adjust_log))
    # the managed loop must actually have adjusted something, or the
    # equality above is vacuous
    assert len(mgr.budget_log) > 0


def test_node_manager_scenario_matches_hand_wired_bit_for_bit():
    """``paper/table1-tdp`` (trimmed) == the pre-API NodeSim +
    run_closed_loop composition from examples/power_management.py."""
    iters = 60
    sc = get_scenario("paper/table1-tdp")
    res = run_scenario(sc, iterations=iters)

    cfg = get_config("llama3.1-8b")
    wl = fsdp_llm_iteration(cfg, batch=2, seq=4096, n_shards=8)
    node = NodeSim(wl, MI300X_PRESET,
                   SimConfig(seed=1, comm_gbps=40.0, engine="batched"),
                   8, seed=1)
    mgr = run_closed_loop(
        SimBackend(node),
        ManagerConfig(use_case="gpu-red", sampling_period=2, warmup=3,
                      window_size=2, power_cap=700.0, cpu_budget=20.0),
        iters)

    assert len(node.history) == len(res.node.history) == iters
    for a, b in zip(node.history, res.node.history):
        assert a["t_iter"] == b["t_iter"]
        assert np.array_equal(a["power"], b["power"])
        assert np.array_equal(a["cap"], b["cap"])
    assert np.array_equal(mgr.backend.get_power_caps(),
                          res.manager.backend.get_power_caps())
    assert all(np.array_equal(x, y) for x, y in
               zip(mgr.adjust_log, res.manager.adjust_log))


def test_telemetry_scenario_records_identically_to_hand_wired():
    """A telemetry-attached fleet scenario records the same samples the
    pre-API examples/telemetry_study.py glue produced."""
    iters = 10
    sc = with_overrides(get_scenario("cluster/dp"),
                        {"manager": None, "telemetry": {},
                         "fleet.n_nodes": 2})
    res = run_scenario(sc, iterations=iters)

    cl = ClusterSim(_wl8(), MI300X_PRESET,
                    SimConfig(seed=1, comm_gbps=40.0),
                    ClusterConfig(n_nodes=2, straggler_boost=1.28),
                    devices_per_node=8, seed=5)
    for n in range(2):
        cl.set_node_caps(n, np.full(8, 700.0))
    col = TelemetryCollector(max_samples=2 * iters + 1)
    col.attach_cluster(cl)
    for _ in range(iters):
        cl.step()

    a, b = list(col.samples), list(res.collector.samples)
    assert len(a) == len(b) == 2 * iters
    for sa, sb in zip(a, b):
        assert (sa.iteration, sa.node) == (sb.iteration, sb.node)
        assert np.array_equal(sa.comp_start, sb.comp_start)
        assert np.array_equal(sa.power, sb.power)
        assert sa.t_wall == sb.t_wall
    fa, fb = list(col.fleet), list(res.collector.fleet)
    assert len(fa) == len(fb) == iters
    for x, y in zip(fa, fb):
        assert x.t_fleet == y.t_fleet
        assert np.array_equal(x.lead, y.lead)


def test_build_scenario_exposes_handles():
    built = build_scenario(get_scenario("paper/characterization"))
    assert built.node is not None and built.cluster is None
    built.node.step()
    assert len(built.node.history) == 1


# --------------------------------------------------------------------------- #
# review regressions
# --------------------------------------------------------------------------- #
def test_envelope_typo_is_rejected_not_defaulted():
    """A typo'd envelope must never silently load an all-defaults spec."""
    with pytest.raises(ValueError, match="unknown envelope"):
        Scenario.from_json(json.dumps({"format": SPEC_FORMAT, "version": 1,
                                       "scenarios": {"iterations": 999}}))
    with pytest.raises(ValueError, match="no 'scenario' body"):
        Scenario.from_json(json.dumps({"format": SPEC_FORMAT,
                                       "version": 1}))


def test_override_deep_under_null_section_materializes_defaults():
    sc = get_scenario("paper/characterization")      # telemetry is None
    sc2 = with_overrides(sc, {"telemetry.sensor.dropout_p": 0.1})
    assert sc2.telemetry is not None
    assert sc2.telemetry.sensor.dropout_p == 0.1
    assert sc2.telemetry.keep_truth is TelemetrySpec().keep_truth
    sc3 = with_overrides(sc, {"manager.config.power_cap": 650.0})
    assert sc3.manager.config.power_cap == 650.0


def test_cli_chrome_trace_alone_enables_telemetry(capsys, tmp_path):
    p = str(tmp_path / "out.chrome.json")
    assert cli_main(["run", "paper/characterization", "--iterations", "2",
                     "--chrome-trace", p, "--json"]) == 0
    capsys.readouterr()
    with open(p) as f:
        assert json.load(f)["traceEvents"]
