"""engine="jax": the XLA port of the C3 window arithmetic, plus the
whole-run fleet scan behind Monte-Carlo sweeps.

Two equivalence tiers (docs/engines.md):

  * ``jax_iteration`` consumes the *same numpy noise stream* as the vector
    engine, so per-iteration traces line up float-for-float (tolerance for
    accumulation order) — property-tested across topologies, heterogeneous
    presets, and churn.
  * ``run_fleet_scan`` keeps the whole warmup/churn/iteration loop inside
    one jitted scan with jax-PRNG noise: identical thermal lotteries and
    physics, a different noise stream — so the check is statistical
    (tail-mean fleet metrics), driven through the sweep module against its
    own per-sample ``ClusterSim`` path (``_run_one_python``).
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import small_workload
from repro.core.c3sim import SimConfig
from repro.core.cluster import ClusterConfig, ClusterSim
from repro.core.jax_engine import window_plan
from repro.core.thermal import MI300X_PRESET, ChurnEvent, ChurnModel

HETERO = ["mi300x", "mi300x-air", "mi300x", "v5e"]


def _cluster(engine, topo="dp", seed=5, hetero=False, churn=False,
             noise=None):
    kw = {}
    if hetero:
        kw["node_presets"] = HETERO
    if churn:
        # fresh ChurnModel per sim — the model is stateless but keep the
        # two engines' configs independent anyway
        kw["churn"] = {0: ChurnModel(events=[ChurnEvent(0.0, 3, 1.4)])}
    sim_kw = dict(seed=1, comm_gbps=40.0)
    if noise is not None:
        sim_kw["noise"] = noise
    return ClusterSim(small_workload(n_layers=8), MI300X_PRESET,
                      SimConfig(**sim_kw),
                      ClusterConfig(n_nodes=4, straggler_boost=1.28,
                                    topology=topo, engine=engine, **kw),
                      devices_per_node=8, seed=seed)


def _assert_traces_close(ta, tb):
    for field in ("comp_start", "comp_end", "comp_overlap",
                  "comm_start", "comm_end", "util"):
        a, b = getattr(ta, field), getattr(tb, field)
        np.testing.assert_array_equal(np.isnan(a), np.isnan(b),
                                      err_msg=f"{field}: NaN pattern")
        np.testing.assert_allclose(a, b, rtol=1e-9, atol=1e-12,
                                   equal_nan=True, err_msg=field)
    assert ta.t_iter == pytest.approx(tb.t_iter, rel=1e-9)


# --------------------------------------------------------------------------- #
# per-iteration equivalence: jax vs vector
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("topo", ["dp", "pp", "tp"])
def test_cluster_jax_engine_matches_vector(topo):
    """engine='jax' steps all N*G lanes as one XLA program and must emit
    the vector engine's traces (same RNG stream, float tolerance only for
    accumulation order) — the cluster layer on top cannot tell them
    apart."""
    cv, cj = _cluster("vector", topo), _cluster("jax", topo)
    for _ in range(3):
        tv, tj = cv.step(), cj.step()
        for a, b in zip(tv, tj):
            _assert_traces_close(a, b)
    assert cv.history[-1]["t_fleet"] == pytest.approx(
        cj.history[-1]["t_fleet"], rel=1e-9)
    np.testing.assert_allclose(cv.history[-1]["lead"],
                               cj.history[-1]["lead"],
                               rtol=1e-6, atol=1e-12)


@settings(deadline=None, max_examples=12)
@given(seed=st.integers(0, 2 ** 16),
       topo=st.sampled_from(["dp", "pp", "tp"]),
       hetero=st.booleans(), churn=st.booleans())
def test_jax_engine_matches_vector_property(seed, topo, hetero, churn):
    """Property: for any thermal-lottery seed, topology, fleet mix, and
    churn setting, the jax engine's iteration is the vector engine's."""
    cv = _cluster("vector", topo, seed=seed, hetero=hetero, churn=churn)
    cj = _cluster("jax", topo, seed=seed, hetero=hetero, churn=churn)
    for _ in range(2):
        tv, tj = cv.step(), cj.step()
    for a, b in zip(tv, tj):
        np.testing.assert_array_equal(np.isnan(a.comp_end),
                                      np.isnan(b.comp_end))
        np.testing.assert_allclose(a.comp_end, b.comp_end,
                                   rtol=1e-9, atol=1e-12, equal_nan=True)
        np.testing.assert_allclose(a.comm_end, b.comm_end,
                                   rtol=1e-9, atol=1e-12, equal_nan=True)
    assert cv.history[-1]["t_fleet"] == pytest.approx(
        cj.history[-1]["t_fleet"], rel=1e-9)


def test_window_plan_caches_on_workload():
    wl = small_workload(n_layers=8)
    assert window_plan(wl) is window_plan(wl)


# --------------------------------------------------------------------------- #
# the fleet scan's float32 normal draw: same stream as float64 normal
# --------------------------------------------------------------------------- #
def test_normal_f32_matches_float64_normal():
    """`_normal_f32` is `jax.random.normal(key, shape, float64)` rounded
    to float32 accuracy: the same bits, not another stream."""
    import jax
    import jax.numpy as jnp
    from repro.core.jax_engine import _normal_f32

    shape = (256 * 1024,)
    with jax.enable_x64(True):
        for seed in range(4):
            key = jax.random.PRNGKey(seed)
            z64 = np.asarray(jax.random.normal(key, shape, jnp.float64))
            z32 = jax.jit(_normal_f32, static_argnums=1)(key, shape)
            assert z32.dtype == jnp.float32
            z32 = np.asarray(z32, np.float64)
            np.testing.assert_array_less(
                np.abs(z32 - z64), 1e-6 * np.maximum(1.0, np.abs(z64)))


def test_normal_f32_is_finite_odd_and_exact_at_the_poles():
    """Uniforms within 2^-24 of ±1 round to ±1 in float32; the transform
    keeps 1 - |u| from float64, so it stays finite, odd and accurate out
    to the last uniform the draw can give."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import erfinv
    from repro.core.jax_engine import _sqrt2_erf_inv_f32

    # nextafter(-1, 0) = -1 + 2^-53: the draw's lower bound
    edges = [np.nextafter(-1.0, 0.0), 1 - 2.0 ** -24, -(1 - 2.0 ** -24),
             1 - 2.0 ** -53, 0.0]
    tail = list(1 - 2.0 ** -np.arange(1.0, 53.5, 0.25))   # every branch
    with jax.enable_x64(True):
        u = jnp.asarray(edges + tail, jnp.float64)
        z = np.asarray(_sqrt2_erf_inv_f32(u), np.float64)
        z_neg = np.asarray(_sqrt2_erf_inv_f32(-u), np.float64)
        ref = np.sqrt(2.0) * np.asarray(erfinv(u))
    assert np.all(np.isfinite(z)) and np.all(np.isfinite(z_neg))
    np.testing.assert_array_equal(z_neg, -z)
    np.testing.assert_array_less(np.abs(z - ref),
                                 1e-6 * np.maximum(1.0, np.abs(ref)))


@pytest.mark.parametrize("scenario", ["cluster/dp", "cluster/tp"])
def test_fleet_scan_has_no_float64_erf_inv(scenario):
    """The scan's noise (kernel, collective and TP jitter) takes no float64
    inverse error function: on a TPU that is emulated, and it was most of
    a sweep's device time.  Its transcendentals run in float32."""
    import functools
    import re

    import jax
    from repro.api.registry import get_scenario
    from repro.core.jax_engine import (_fleet_scan_core, build_fleet_arrays,
                                       fleet_scan_spec)

    sc = get_scenario(scenario).replace(manager=None)
    wl = sc.workload.build()
    spec = fleet_scan_spec(wl, sc.sim, sc.fleet, 3, collect="summary",
                           devices_per_node=sc.node.devices)
    assert spec.n_nodes == 4
    rows = [build_fleet_arrays(wl, sc.node.build_preset(), sc.sim, sc.fleet,
                               sc.node.caps_w, seed, rng_seed=seed,
                               devices_per_node=sc.node.devices)
            for seed in (1, 2)]
    stacked = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
    with jax.enable_x64(True):
        text = jax.jit(jax.vmap(functools.partial(
            _fleet_scan_core, spec))).lower(stacked).as_text()
    assert not re.findall(r"erf_inv.*f64", text)
    assert re.findall(r"stablehlo\.log .*f32>", text)


# --------------------------------------------------------------------------- #
# whole-run fleet scan: statistical equivalence via the sweep module
# --------------------------------------------------------------------------- #
def _python_samples(spec):
    """The population `run_sweep` builds for ``spec``, stepped sample by
    sample through plain ClusterSim (`_run_one_python`) instead of the
    vmapped scan: same overrides, thermal seeds and healthy reference."""
    from repro.api.registry import get_scenario
    from repro.api.spec import _encode, with_overrides
    from repro.api.sweep import _HEALTHY, _run_one_python, _sample_overrides

    base = get_scenario(spec.scenario).replace(manager=None)
    ref = _run_one_python(with_overrides(base, dict(_HEALTHY)), base.seed,
                          spec.iterations)
    rows = []
    for label, ov, seed in _sample_overrides(spec, base):
        row = _run_one_python(with_overrides(base, ov), seed, spec.iterations)
        rows.append({"label": label, "overrides": _encode(ov),
                     "thermal_seed": seed, **row,
                     "recovery": row["throughput"] / ref["throughput"]})
    return rows


@pytest.mark.slow
def test_fleet_scan_sweep_matches_python_fallback():
    """The same SweepSpec through both execution paths — one vmapped
    run_fleet_scan program vs per-sample ClusterSim stepping.  Thermal
    lotteries are shared; only the iteration-noise stream differs, so
    tail-mean fleet metrics must agree to well under a percent."""
    from repro.api.sweep import SweepSpec, run_sweep

    spec = SweepSpec(scenario="cluster/dp", samples=3, seed=0,
                     iterations=40)
    jax_art = run_sweep(spec)
    assert jax_art["engine"] == "jax-scan"

    py_samples = _python_samples(spec)
    assert len(py_samples) == len(jax_art["samples"])
    for a, b in zip(jax_art["samples"], py_samples):
        assert a["label"] == b["label"]
        assert a["thermal_seed"] == b["thermal_seed"]
        for key in ("t_fleet_s", "throughput", "fleet_power_w"):
            assert a[key] == pytest.approx(b[key], rel=5e-3), key
        assert a["recovery"] == pytest.approx(b["recovery"], rel=5e-3)


@pytest.mark.slow
def test_fleet_scan_handles_churn_and_hetero():
    """Churn event tables and per-node preset constants ride the scan as
    data: the churn scenario's population matches per-sample ClusterSim."""
    from repro.api.sweep import SweepSpec, run_sweep

    spec = SweepSpec(scenario="cluster/churn", samples=2, seed=1,
                     iterations=40, node_preset_pool=["mi300x",
                                                      "mi300x-air"])
    jax_art = run_sweep(spec)
    assert jax_art["engine"] == "jax-scan"

    py_samples = _python_samples(spec)
    assert len(py_samples) == len(jax_art["samples"])
    for a, b in zip(jax_art["samples"], py_samples):
        assert a["overrides"] == b["overrides"]
        assert a["t_fleet_s"] == pytest.approx(b["t_fleet_s"], rel=1e-2)


def test_sweep_rows_keep_their_own_workloads():
    """Rows share one built workload only where their workload specs
    agree: in a population whose rows switch between two workload specs,
    each row gets what a batch of that row alone gets."""
    from repro.api.registry import get_scenario
    from repro.api.spec import with_overrides
    from repro.api.sweep import _run_batch_jax

    base = get_scenario("cluster/dp").replace(manager=None)
    variants = [with_overrides(base, {"workload.batch": b,
                                      "fleet.straggler_boost": 1.1 + 0.1 * k})
                for k, b in enumerate((1, 1, 2, 1))]
    seeds, noise = [5, 6, 7, 8], [11, 12, 13, 14]
    rows = _run_batch_jax(variants, seeds, noise, 3)
    assert rows is not None
    assert rows[1]["t_fleet_s"] != rows[2]["t_fleet_s"]
    for i, row in enumerate(rows):
        (alone,) = _run_batch_jax([variants[i]], [seeds[i]], [noise[i]], 3)
        assert row == pytest.approx(alone, rel=1e-12), i


def test_sweep_artifact_schema(tmp_path):
    """The artifact validates against the docs/sweeps.md schema and is
    valid strict JSON (no NaN/Inf literals)."""
    from repro.api.sweep import SWEEP_FORMAT, SweepSpec, run_sweep

    art = run_sweep(SweepSpec(scenario="cluster/dp", samples=2,
                              iterations=30))
    assert art["format"] == SWEEP_FORMAT and art["version"] == 1
    assert art["mode"] == "mc" and art["n_samples"] == 2
    names = {"t_fleet_s", "throughput", "lead_max_s", "fleet_power_w"}
    assert set(art["reference"]) == names
    for s in art["samples"]:
        assert names | {"sample", "label", "overrides", "thermal_seed",
                        "recovery"} == set(s)
        assert s["recovery"] > 0
    assert set(art["summary"]) == names | {"recovery"}
    for q in art["summary"].values():
        assert set(q) == {"mean", "p10", "p50", "p90"}
        assert q["p10"] <= q["p50"] <= q["p90"]
    text = json.dumps(art, allow_nan=False)      # raises on NaN/Inf
    assert json.loads(text) == art


def test_sweep_rejects_node_scenarios():
    from repro.api.sweep import SweepSpec, run_sweep
    with pytest.raises(ValueError, match="fleet"):
        run_sweep(SweepSpec(scenario="paper/node-cap", samples=2))
