"""The reduction from a profiler trace to per-layer numbers: interval
arithmetic by hand, and a small trace recorded on a TPU v5e chip."""
from pathlib import Path

import pytest

from bench import xplane
from bench.xplane import Trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_drops_empty():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


def test_subtract_leaves_the_uncovered_parts():
    a = [(0, 10), (20, 30)]
    b = [(2, 3), (5, 12), (25, 40)]
    assert xplane.subtract(a, b) == [(0, 2), (3, 5), (20, 25)]
    assert xplane.subtract(a, []) == a
    assert xplane.subtract([], b) == []


def test_gaps_and_clip():
    busy = [(2, 4), (6, 7)]
    assert xplane.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert xplane.clip([(0, 5), (8, 12)], 1, 10) == [(1, 5), (8, 10)]


def synthetic() -> Trace:
    """Two devices over a 100 ns window of two steps.  Device 0: compute
    0-30 and 50-70, an all-gather 20-45 (exposed 30-45), idle 70-100.
    Device 1: compute 0-60, a reduce-scatter 55-80 (exposed 60-80)."""
    ops = {0: [("fusion.1", 0, 30), ("all-gather.3", 20, 45),
               ("fusion.2", 50, 70)],
           1: [("convolution.7", 0, 60), ("reduce-scatter.1", 55, 80)]}
    modules = {0: [("jit_train_step", 0, 70)],
               1: [("jit_train_step", 0, 80)]}
    host = [("train", 0, 50), ("train", 50, 100),
            ("PjitFunction(train_step)", 48, 52),
            ("batch", 72, 99)]
    return Trace(ops, modules, host, "train")


def test_busy_idle_and_programs():
    t = synthetic()
    assert t.window_s == pytest.approx(100e-9)
    # device 0 busy 0-45 and 50-70 (65), device 1 busy 0-80 (80)
    assert t.busy_s() == pytest.approx(72.5e-9)
    assert t.idle_share() == pytest.approx(0.275)
    assert t.program_s("train_step") == pytest.approx(75e-9)
    assert t.program_s("nothing_here") is None


def test_exposed_collectives():
    t = synthetic()
    # device 0: 30-45 = 15; device 1: 60-80 = 20
    assert t.collective_exposed_s() == pytest.approx(17.5e-9)


def test_idle_gaps_are_named_by_the_host():
    t = synthetic()
    assert t.idle_gaps() == [["train/batch", pytest.approx(30e-9)],
                             ["train", pytest.approx(5e-9)]]
    names = [n for n, _ in t.top_ops()]
    assert names[0] == "convolution.7"


def test_trace_that_dropped_events_is_refused():
    ops = {0: [("fusion.1", 0, 30)]}
    with pytest.raises(ValueError, match="dropped"):
        Trace(ops, {}, [("train", 0, 50), ("train", 50, 100)], "train")


def test_trace_without_work_is_refused():
    with pytest.raises(ValueError):
        Trace({0: []}, {}, [("train", 0, 1)], "train")
    with pytest.raises(ValueError):
        Trace({0: [("a", 0, 1)]}, {}, [], "train")


# ------------------------------------------------- a trace recorded on a chip
def recorded() -> Trace:
    """Three tiny train steps on one TPU v5e chip, each in a ``train`` span
    (``bench/tests/record_trace.py``)."""
    return Trace.load(DATA, [0], "train")


def test_recorded_trace_reads_its_device_and_spans():
    from jax.profiler import ProfileData
    t = recorded()
    assert len(t.steps) == 3 and t.devices == [0]
    # busy is the union of the ops line's events, clipped to the spans
    pd = ProfileData.from_file(str(DATA / "train_tiny.xplane.pb"))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    events = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    assert len(t.ops[0]) == len(events)
    ends = sorted(events)
    busy, cur_s, cur_e = 0.0, None, None
    for s, e in ends:
        s, e = max(s, t.lo), min(e, t.hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            busy += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    assert t.busy_s() == pytest.approx(busy / 1e9)
    assert 0 < t.busy_s() < t.window_s
    assert t.idle_share() == pytest.approx(1 - t.busy_s() / t.window_s)


def test_recorded_trace_programs_gaps_and_names():
    t = recorded()
    step = t.program_s("train_step")
    assert step is not None and step >= t.busy_s()
    assert t.program_s("_fleet_scan_core") is None
    assert t.collective_exposed_s() is None          # one chip
    gaps = t.idle_gaps(10)
    assert gaps and all(n.startswith("train") for n, _ in gaps)
    assert sum(g for _, g in gaps) <= t.window_s - t.busy_s() + 1e-12
    top = t.top_ops(10)
    assert len(top) == 10 and all(" = " not in n for n, _ in top)
    assert top[0][1] >= top[-1][1] > 0
