"""The readers of the program's sweep spans (``bench/spans.py``) on
synthetic traces, and the metric files that call them."""
import pytest

from bench.common import ROOT, load_json
from bench.spans import STAGES, idle_unattributed_ms, stage_ms
from bench.xplane import Trace

SPAN_METRICS = {"sweep.sample_ms": "sweep.sample",
                "sweep.workload_ms": "sweep.workload",
                "sweep.arrays_ms": "sweep.fleet_arrays",
                "sweep.collect_ms": "sweep.collect",
                "sweep.idle_unattributed_ms": None}


def trace(host, ops=None) -> Trace:
    """Two 100 ns sweeps, 0-100 and 100-200, in the benchmark's ``sweep``
    span; device 0 busy 50-60 and 150-160 unless ``ops`` says otherwise."""
    ops = ops or {0: [("while.1", 50, 60), ("while.1", 150, 160)]}
    steps = [("sweep", 0, 100), ("sweep", 100, 200)]
    return Trace(ops, {}, steps + host, "sweep")


def test_stage_ms_is_per_sweep_and_clipped_to_the_window():
    t = trace([("sweep.fleet_arrays", -20, 30),
               ("sweep.fleet_arrays", 120, 150),
               ("sweep.fleet_arrays", 190, 230),
               ("sweep.workload", 30, 40)])
    # 0-30, 120-150 and 190-200 of the window: 70 ns over two sweeps
    assert stage_ms(t, "sweep.fleet_arrays") == pytest.approx(35e-6)
    assert stage_ms(t, "sweep.workload") == pytest.approx(5e-6)
    assert stage_ms(t, "sweep.sample") is None


def test_two_collect_spans_in_one_sweep_are_summed():
    t = trace([("sweep.collect", 60, 64), ("sweep.collect", 65, 71),
               ("sweep.collect", 160, 170)])
    assert stage_ms(t, "sweep.collect") == pytest.approx(10e-6)


def test_idle_under_run_sweep_alone_is_unattributed():
    # idle 0-50, 60-150, 160-200; stages cover 0-45 and 100-145, run_sweep
    # all of it: unattributed 45-50, 60-100, 145-150 and 160-200
    t = trace([("run_sweep", 0, 99), ("run_sweep", 100, 199),
               ("sweep.sample", 0, 20), ("sweep.workload", 20, 45),
               ("sweep.sample", 100, 120), ("sweep.workload", 120, 145)])
    assert idle_unattributed_ms(t) == pytest.approx((5 + 40 + 5 + 40)
                                                    / 2 * 1e-6)


def test_a_gap_inside_a_stage_span_counts_zero():
    ops = {0: [("while.1", 0, 10), ("while.1", 30, 190),
               ("while.1", 195, 200)]}
    t = trace([("run_sweep", 0, 200), ("sweep.workload", 5, 35),
               ("fleet_scan.fetch", 185, 200)], ops)
    assert idle_unattributed_ms(t) == 0.0


def test_every_stage_span_explains_idle():
    """Each stage, the ``fleet_scan`` ones among them, removes the idle it
    covers from the unattributed remainder."""
    for name in STAGES:
        t = trace([("run_sweep", 0, 200), (name, 0, 50), (name, 60, 150),
                   (name, 160, 200)])
        assert idle_unattributed_ms(t) == 0.0, name


def test_a_program_without_spans_gives_none():
    t = trace([("PjitFunction(_fleet_scan_core)", 40, 50)])
    assert idle_unattributed_ms(t) is None
    assert all(stage_ms(t, n) is None for n in STAGES)


def _read(t):
    import bench.run as harness
    run = harness.Run({}, {}, {}, 0, 1.0)
    run.trace = t
    specs = [m for m in load_json(ROOT / "BENCHMARK.json")["per_layer"]
             if m["name"] in SPAN_METRICS]
    assert sorted(m["name"] for m in specs) == sorted(SPAN_METRICS)
    return harness.read_metrics(run, specs)


def test_metric_files_read_their_spans():
    host = [("run_sweep", 0, 100)]
    start = 0
    for name in STAGES:
        host.append((name, start, start + 5))
        start += 5
    got = _read(trace(host))
    # the stages cover 0-35 of the first sweep: idle 35-50, 60-150 and
    # 160-200 is unattributed
    for metric, span in SPAN_METRICS.items():
        assert got[metric]["unit"] == "ms"
        want = 2.5e-6 if span else (15 + 90 + 40) / 2 * 1e-6
        assert got[metric]["value"] == pytest.approx(want), metric


def test_metric_files_leave_out_a_program_without_spans():
    assert _read(trace([])) == {}
