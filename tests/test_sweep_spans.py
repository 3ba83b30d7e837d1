"""Profiler spans of a sweep's host stages (docs/sweeps.md, "Tracing a
sweep"): one span per stage per sweep, nested in ``run_sweep`` on the
calling thread, disjoint, in order, covering the call, and leaving the
artifact bit-for-bit what it is without the profiler."""
import json

import jax
import pytest
from jax.profiler import ProfileData

from repro.api import SCENARIOS, get_scenario, with_overrides
from repro.api.sweep import SweepSpec, run_sweep

ROOT = "run_sweep"
STAGES = ("sweep.sample", "sweep.workload", "sweep.fleet_arrays",
          "fleet_scan.put", "fleet_scan.call", "fleet_scan.fetch",
          "sweep.collect")
NAME = "test/dp-2n"


def _spec() -> SweepSpec:
    return SweepSpec(scenario=NAME, samples=2, seed=3, iterations=3)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(artifact without the profiler, artifact under it, the spans of the
    host line that holds ``run_sweep``, as (name, start, end) in ns)."""
    def scenario():
        return with_overrides(get_scenario("cluster/dp"),
                              {"fleet.n_nodes": 2}).replace(name=NAME)

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(SCENARIOS, NAME, scenario)
        plain = run_sweep(_spec())
        logdir = tmp_path_factory.mktemp("sweep_trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(str(logdir), profiler_options=options):
            art = run_sweep(_spec())
    pd = ProfileData.from_file(str(sorted(logdir.rglob("*.xplane.pb"))[-1]))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    ours = [[ev for ev in evs if ev[0] in (ROOT,) + STAGES] for evs in lines]
    ours = [evs for evs in ours if evs]
    return plain, art, ours


def test_each_span_once_per_sweep(traced):
    _, _, lines = traced
    names = [n for evs in lines for n, _, _ in evs]
    assert len(names) <= 10
    for name in (ROOT,) + STAGES:
        want = (1, 2) if name == "sweep.collect" else (1,)
        assert names.count(name) in want, (name, names)


def test_stage_spans_nest_in_run_sweep_on_one_line(traced):
    _, _, lines = traced
    assert len(lines) == 1, "the spans sit on more than one host line"
    (root,) = [(s, e) for n, s, e in lines[0] if n == ROOT]
    for n, s, e in lines[0]:
        assert root[0] <= s <= e <= root[1], n


def test_stage_spans_are_disjoint_and_in_order(traced):
    _, _, lines = traced
    stages = sorted((s, e, n) for n, s, e in lines[0] if n != ROOT)
    names = [n for _, _, n in stages]
    assert [n for i, n in enumerate(names)
            if i == 0 or n != names[i - 1]] == list(STAGES)
    for (_, e, _), (s, _, _) in zip(stages, stages[1:]):
        assert e <= s


def test_stage_spans_cover_run_sweep(traced):
    _, _, lines = traced
    (root,) = [e - s for n, s, e in lines[0] if n == ROOT]
    covered, reach = 0, None
    for s, e in sorted((s, e) for n, s, e in lines[0] if n != ROOT):
        s = s if reach is None else max(s, reach)
        covered += max(0, e - s)
        reach = e if reach is None else max(reach, e)
    assert covered >= 0.9 * root


def test_traced_artifact_is_bit_for_bit(traced):
    plain, art, _ = traced
    assert art["engine"] == "jax-scan"
    assert (json.dumps(art, sort_keys=True, allow_nan=False)
            == json.dumps(plain, sort_keys=True, allow_nan=False))
