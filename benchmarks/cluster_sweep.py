"""Cluster-scale sweep: fleet throughput vs node count, straggler
placement, and parallelism topology, plus the hierarchical manager's
recovery — the datacenter-scale aggregation of the paper's node-level claim.

All fleets are built through the scenario API (`repro.api`): each row is a
`Scenario` — either a registered one (``cluster/dp``,
``cluster/hetero-cooling``) or a programmatic variant — run through the
same `run_scenario`/`build_scenario` driver the CLI uses, with the derived
metrics bit-identical to the pre-API hand-wired builders (equivalence is
pinned in tests/test_scenario_api.py).

Rows:
  * cluster_scale_N{n}       — fleet throughput per node as the fleet grows
                               (barrier + slower inter-node all-reduce)
  * cluster_straggler_*      — healthy vs one hot GPU, by placement
  * cluster_topology_{t}     — coupling strength per topology (dp/pp/tp)
  * cluster_hetero           — preset-driven straggler (air-cooled node)
  * cluster_churn            — straggler migration under cooling churn
  * cluster_fleet_manager    — FleetPowerManager recovery under a fixed
                               cluster power budget
  * cluster_fault_recovery   — goodput of detect→drain→elastic restart vs
                               ignoring the fault vs hair-trigger draining
                               (the registered ``cluster/fault-heal`` /
                               ``cluster/fault-ignored`` scenarios)
  * c3_engine_speedup        — batched fast path vs event-loop reference
  * cluster_vector_speedup   — vectorized all-lanes engine vs per-node
                               batched at sweep scale
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from benchmarks.common import Row, make_node
from repro.api import (NodeSpec, Scenario, WorkloadSpec, build_scenario,
                       get_scenario, run_scenario)
from repro.core.c3sim import SimConfig
from repro.core.cluster import ClusterConfig
from repro.core.thermal import ChurnEvent, ChurnModel

CAP = 700.0
SMOKE = False           # run.py --smoke trims iterations for CI


def _iters(full: int) -> int:
    return max(10, full // 4) if SMOKE else full


def _scenario(n_nodes: int, boost: float, iterations: int, seed: int = 5,
              straggler_node: int = 0, caps: Optional[float] = CAP,
              **cc_kw) -> Scenario:
    """A fleet scenario with the sweep's shared defaults (8-layer Llama,
    calibrated sim knobs, 700 W initial caps) — the spec-level analogue of
    the old hand-wired ``_cluster`` builder."""
    return Scenario(
        workload=WorkloadSpec(arch="llama3.1-8b", n_layers=8),
        sim=SimConfig(seed=1, comm_gbps=40.0, engine="batched"),
        node=NodeSpec(caps_w=caps),
        fleet=ClusterConfig(n_nodes=n_nodes, straggler_boost=boost,
                            straggler_node=straggler_node, **cc_kw),
        iterations=iterations, seed=seed)


def scale_sweep() -> List[Row]:
    """Fleet throughput vs node count (straggler on node 0)."""
    rows: List[Row] = []
    base = None
    for n_nodes in (1, 2, 4, 8):
        t0 = time.perf_counter()
        res = run_scenario(_scenario(n_nodes, 1.28, _iters(40)))
        tput = res.cluster.fleet_throughput(last=10)
        us = (time.perf_counter() - t0) * 1e6
        base = tput if base is None else base
        rows.append((f"cluster_scale_N{n_nodes}", us,
                     f"fleet_tput={tput:.3f};per_node_eff={tput / base:.3f};"
                     f"allreduce_ms={res.cluster.allreduce_time() * 1e3:.1f}"))
    return rows


def straggler_placement() -> List[Row]:
    """One hot GPU vs healthy fleet, straggler on node 0 vs last node."""
    rows: List[Row] = []
    cases = [("healthy", 1.0, 0), ("node0", 1.28, 0), ("node3", 1.28, 3)]
    tputs = {}
    for label, boost, where in cases:
        t0 = time.perf_counter()
        res = run_scenario(_scenario(4, boost, _iters(60),
                                     straggler_node=where))
        tputs[label] = res.cluster.fleet_throughput()
        us = (time.perf_counter() - t0) * 1e6
        slow = [h["slowest_node"] for h in res.cluster.history[-10:]]
        rows.append((f"cluster_straggler_{label}", us,
                     f"fleet_tput={tputs[label]:.4f};"
                     f"slowest_node_mode={int(np.bincount(slow).argmax())}"))
    gap = (tputs["healthy"] - tputs["node0"]) / tputs["healthy"]
    rows.append(("cluster_straggler_gap", 0.0, f"gap={gap:+.3%}"))
    return rows


def fleet_manager_recovery() -> List[Row]:
    """FleetPowerManager under a fixed cluster budget of N*G*700 W: the
    registered ``cluster/dp`` scenario is the managed leg."""
    t0 = time.perf_counter()
    healthy = run_scenario(_scenario(4, 1.0, 60))
    strag = run_scenario(_scenario(4, 1.28, 60))
    # the closed loop needs its full horizon to converge — not trimmed in
    # smoke mode (it is cheap under the batched engine)
    managed = run_scenario(get_scenario("cluster/dp"))
    us = (time.perf_counter() - t0) * 1e6
    tp_h = healthy.metrics["fleet_tput"]
    tp_s = strag.metrics["fleet_tput"]
    tp_m = managed.metrics["fleet_tput"]
    rec = (tp_m - tp_s) / max(tp_h - tp_s, 1e-12)
    return [("cluster_fleet_manager", us,
             f"healthy={tp_h:.4f};straggler={tp_s:.4f};managed={tp_m:.4f};"
             f"recovered={rec:.2f};"
             f"node0_budget={managed.manager.node_budgets[0]:.0f}W")]


def fault_recovery() -> List[Row]:
    """The escalation layer's acceptance ordering, as gated metrics:
    healing (detect → drain → elastic restart) must out-goodput both
    ignoring the fault and draining on the first blip.  The fault schedule
    is pinned in simulated seconds, so the full horizon always runs (the
    runs are cheap under the batched engine)."""
    from repro.api import with_overrides
    t0 = time.perf_counter()
    heal = run_scenario(get_scenario("cluster/fault-heal"))
    ignored = run_scenario(get_scenario("cluster/fault-ignored"))
    immediate = run_scenario(with_overrides(
        get_scenario("cluster/fault-heal"),
        {"escalation.drain_mode": "immediate"}))
    us = (time.perf_counter() - t0) * 1e6
    g_heal = heal.metrics["goodput"]
    g_ign = ignored.metrics["goodput"]
    g_imm = immediate.metrics["goodput"]
    return [("cluster_fault_recovery", us,
             f"heal_goodput={g_heal:.4f};ignored_goodput={g_ign:.4f};"
             f"immediate_goodput={g_imm:.4f};"
             f"heal_over_ignored={g_heal / g_ign:.2f};"
             f"detect_s={heal.metrics['time_to_detect_s']:.2f};"
             f"false_drains={heal.metrics['false_drains']};"
             f"immediate_false_drains={immediate.metrics['false_drains']}")]


def engine_speedup() -> List[Row]:
    """Batched fast path vs the event-loop reference engine (kernel-level
    micro-benchmark: times `C3Sim.run_iteration` itself, below the
    scenario layer)."""
    node = make_node()
    freq = node.state.freq
    reps = 2 if SMOKE else 5
    out = []
    for engine in ("event", "batched"):
        t0 = time.perf_counter()
        for _ in range(reps):
            node.sim.run_iteration(freq, engine=engine)
        out.append((time.perf_counter() - t0) / reps * 1e6)
    ev, ba = out
    return [("c3_engine_speedup", ba,
             f"event_us={ev:.0f};batched_us={ba:.0f};"
             f"speedup={ev / ba:.1f}x")]


def topology_coupling() -> List[Row]:
    """Coupling strength per parallelism topology: one hot GPU's relative
    fleet-throughput cost under dp / pp / tp (fast DP fabric so the
    all-reduce constant does not drown the coupling term)."""
    rows: List[Row] = []
    gaps = {}
    for topo in ("dp", "pp", "tp"):
        t0 = time.perf_counter()
        # thermal settling needs the full horizon (tau >> t_iter) — cheap
        # under the batched engine, so not trimmed in smoke mode
        healthy = run_scenario(_scenario(4, 1.0, 50, topology=topo,
                                         inter_node_gbps=100.0))
        hot = run_scenario(_scenario(4, 1.28, 50, topology=topo,
                                     inter_node_gbps=100.0))
        tp_h = healthy.metrics["fleet_tput"]
        tp_s = hot.metrics["fleet_tput"]
        gaps[topo] = (tp_h - tp_s) / tp_h
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"cluster_topology_{topo}", us,
                     f"healthy_tput={tp_h:.4f};hot_tput={tp_s:.4f};"
                     f"coupling={gaps[topo]:.5f}"))
    order_ok = gaps["tp"] >= gaps["dp"] >= gaps["pp"]
    rows.append(("cluster_topology_order", 0.0,
                 f"tp={gaps['tp']:.5f};dp={gaps['dp']:.5f};"
                 f"pp={gaps['pp']:.5f};tp_ge_dp_ge_pp={int(order_ok)}"))
    return rows


def hetero_fleet() -> List[Row]:
    """Mixed air-/liquid-cooled fleet: the preset, not a boosted device,
    creates the straggler (the registered ``cluster/hetero-cooling``)."""
    t0 = time.perf_counter()
    res = run_scenario(get_scenario("cluster/hetero-cooling"),
                       iterations=_iters(50))
    us = (time.perf_counter() - t0) * 1e6
    slow = [h["slowest_node"] for h in res.cluster.history[-10:]]
    return [("cluster_hetero", us,
             f"fleet_tput={res.metrics['fleet_tput']:.4f};"
             f"slowest_node_mode={int(np.bincount(slow).argmax())}")]


def churn_migration() -> List[Row]:
    """Cooling churn: a straggler emerges on node 0, then migrates to
    node 2 when a harder degradation lands there mid-run."""
    t0 = time.perf_counter()
    probe = run_scenario(_scenario(4, 1.0, 1, inter_node_gbps=100.0))
    t1 = probe.cluster.history[0]["t_fleet"]
    # churn dynamics ride the thermal time constant — full horizon always
    iters = 80
    churn = {0: ChurnModel(events=[ChurnEvent(0.0, 3, 1.35)]),
             2: ChurnModel(events=[ChurnEvent(0.4 * iters * t1, 5, 1.8)])}
    res = run_scenario(_scenario(4, 1.0, iters, inter_node_gbps=100.0,
                                 churn=churn))
    us = (time.perf_counter() - t0) * 1e6
    slow = np.array([h["slowest_node"] for h in res.cluster.history])
    early = int(np.bincount(slow[5:iters // 3]).argmax())
    late = int(np.bincount(slow[-iters // 4:]).argmax())
    return [("cluster_churn", us,
             f"early_straggler=node{early};late_straggler=node{late};"
             f"migrated={int(early != late)}")]


def vector_speedup() -> List[Row]:
    """Vectorized all-lanes cluster engine vs per-node batched runs at
    sweep scale (the ROADMAP per-window device-loop item)."""
    n_nodes = 8 if SMOKE else 16
    reps = _iters(12)
    out = {}
    for engine in ("batched", "vector"):
        built = build_scenario(_scenario(n_nodes, 1.28, reps,
                                         engine=engine))
        t0 = time.perf_counter()
        for _ in range(reps):
            built.cluster.step()
        out[engine] = (time.perf_counter() - t0) / reps * 1e6
    return [("cluster_vector_speedup", out["vector"],
             f"nodes={n_nodes};batched_us={out['batched']:.0f};"
             f"vector_us={out['vector']:.0f};"
             f"speedup={out['batched'] / out['vector']:.2f}x")]


def jax_speedup() -> List[Row]:
    """End-to-end jitted fleet scan (`run_fleet_scan`, the compiled
    whole-run program behind Monte-Carlo sweeps) vs the vectorized numpy
    engine, stepping a 256-node fleet.

    Both legs are timed end-to-end from construction: the ClusterSim leg
    pays its per-node 30-iteration thermal warmup at build time, the scan
    leg runs the same warmup inside the program — so each leg is charged
    the identical physics.  Compile time is excluded (the program caches
    per workload plan / fleet shape, which is how sweeps use it)."""
    from repro.core.jax_engine import (build_fleet_arrays, fleet_scan_spec,
                                      run_fleet_scan)
    n_nodes = 256
    reps = _iters(12)
    sc = _scenario(n_nodes, 1.28, reps, engine="vector")
    t0 = time.perf_counter()
    built = build_scenario(sc)
    for _ in range(reps):
        built.cluster.step()
    vector_s = time.perf_counter() - t0
    wl = sc.workload.build()
    spec = fleet_scan_spec(wl, sc.sim, sc.fleet, reps)
    warm = build_fleet_arrays(wl, sc.node.build_preset(), sc.sim,
                              sc.fleet, sc.node.caps_w, sc.seed)
    run_fleet_scan(spec, warm)              # compile once (cached program)
    t0 = time.perf_counter()
    arrays = build_fleet_arrays(wl, sc.node.build_preset(), sc.sim,
                                sc.fleet, sc.node.caps_w, sc.seed)
    run_fleet_scan(spec, arrays)
    scan_s = time.perf_counter() - t0
    # bare float (no cosmetic "x" suffix) so compare.py can gate it
    return [("cluster_jax_speedup", scan_s / reps * 1e6,
             f"nodes={n_nodes};iters={reps};vector_ms={vector_s * 1e3:.0f};"
             f"scan_ms={scan_s * 1e3:.0f};"
             f"speedup={vector_s / scan_s:.2f}")]


def run() -> List[Row]:
    rows: List[Row] = []
    for fn in (engine_speedup, vector_speedup, jax_speedup, scale_sweep,
               straggler_placement, topology_coupling, hetero_fleet,
               churn_migration, fleet_manager_recovery, fault_recovery):
        rows.extend(fn())
    return rows
