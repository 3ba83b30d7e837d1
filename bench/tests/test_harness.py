"""The harness is data: cells, configurations, traffic mixes and metrics are
files found by name, and the command refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.common import BENCH, ROOT, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench_json():
    return load_json(ROOT / "BENCHMARK.json")


def test_names_and_units_are_plain():
    b = bench_json()
    names = [c["name"] for c in b["configs"]]
    names += [w["name"] for w in b["workloads"]]
    names += [w["config"] for w in b["workloads"]]
    names += [w["traffic"] for w in b["workloads"]]
    names += [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [k for c in b["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names
                                               if not NAME.match(n)]
    units = [m["unit"] for m in b["end_to_end"] + b["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


def test_every_name_has_its_files():
    b = bench_json()
    for c in b["configs"]:
        cfg = load_json(ROOT / c["file"])
        assert (BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
    for w in b["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert "limits" in load_json(BENCH / "cells" / f"{w['name']}.json")
    for m in b["end_to_end"] + b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_cell_added_as_files_alone_is_found_by_name(tmp_path, monkeypatch):
    """A copy of the benchmark gains a cell, a traffic mix and a metric by
    new files and BENCHMARK.json entries; the harness finds each by name."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    b = bench_json()
    b["workloads"].append({"name": "sweep.dp2.mc8", "config": "cluster-dp.2n",
                           "traffic": "mc8_it20", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "sweep.answer", "unit": "s",
                           "better": "lower", "source": "program_counter",
                           "layer": "device", "moves": "setup_s",
                           "workloads": ["sweep.dp2.mc8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    (root / "bench/traffic/mc8_it20.json").write_text(json.dumps(
        {"samples": 8, "iterations": 20, "verify_samples": 1}))
    (root / "bench/cells/sweep.dp2.mc8.json").write_text(json.dumps(
        {"limits": {"t_fleet_s_rel": 1.0}}))
    (root / "bench/metrics/sweep.answer.py").write_text(
        "def read(run):\n    return 42.0\n")

    import bench.run as harness
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    bj = load_json(root / "BENCHMARK.json")
    cell = harness.cell_entry(bj, "sweep.dp2.mc8")
    assert cell["traffic"] == "mc8_it20"
    specs = harness.metric_specs(bj, "sweep.dp2.mc8", trace=True)
    assert [m["name"] for m in specs] == ["sweep.answer"]
    run = harness.Run(cell, {}, {}, 0, 1.0)
    assert harness.read_metrics(run, specs) == {
        "sweep.answer": {"value": 42.0, "unit": "s"}}
    # the new cell reports none of the other cells' per-layer metrics
    assert "sweep.scan_ms" not in [m["name"] for m in specs]


CELLS = [w["name"] for w in bench_json()["workloads"]]
SWEEP_CELLS = ["sweep.dp256.mc64", "sweep.dp2.mc1024"]
TRAIN_CELLS = ["train.qwen3-4b.2l"]


def reported(cell, trace):
    import bench.run as harness
    return [m["name"] for m in harness.metric_specs(bench_json(), cell,
                                                     trace=trace)]


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "per_layer"])
@pytest.mark.parametrize("cell", CELLS)
def test_metrics_are_filtered_per_cell(cell, trace):
    """A cell reports the metrics whose ``workloads`` list names it, and
    those that have no such list."""
    b = bench_json()
    group = b["per_layer"] if trace else b["end_to_end"]
    want = [m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]]
    assert reported(cell, trace) == want
    assert want


@pytest.mark.parametrize("cell", SWEEP_CELLS)
def test_sweep_cells_report_what_they_did(cell):
    assert reported(cell, False) == ["sweep_node_iters_per_s", "setup_s"]
    assert reported(cell, True) == [
        "sweep.scan_ms", "sweep.idle_share", "sweep.sample_ms",
        "sweep.workload_ms", "sweep.arrays_ms", "sweep.collect_ms",
        "sweep.idle_unattributed_ms"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_cells_report_no_sweep_metric(cell):
    e2e = reported(cell, False)
    assert "train_tokens_per_s" in e2e and "setup_s" in e2e
    names = e2e + reported(cell, True)
    assert not [n for n in names if n.startswith("sweep")], names
    assert "train.mfu" in names


def test_peak_bytes_adds_the_space_reserved_for_temporaries():
    import bench.run as harness

    class Device:
        def memory_stats(self):
            return {"peak_bytes_in_use": 7_128_674_816,
                    "peak_bytes_reserved": 8_003_321_856}
    assert harness.peak_bytes(Device()) == 15_131_996_672


def _command(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sweep.dp2.mc1024",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert "chip" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _command(tmp_path)
    assert out.returncode != 0
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v9 imaginary"])
def test_peaks_are_keyed_by_device_kind(kind):
    from bench.common import peaks
    if kind == "TPU v5 lite":
        p = peaks(kind)
        assert p["bf16_flops_per_s"] == 197e12
        assert p["hbm_bytes_per_s"] == 819e9
    else:
        with pytest.raises(KeyError):
            peaks(kind)


def test_window_traces_only_its_first_units(tmp_path):
    """With ``trace_units`` 2 the profiler holds the first two units of
    work, each in the driver's span, and the window runs on untraced."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    import bench.run as harness

    class Driver:
        span = "unit"

        def step(self, k):
            jnp.ones(8).sum().block_until_ready()
            return {"work": 1}

    run = harness.Run({}, {}, {}, 0, 0.3)
    harness.window(run, Driver(), 0.3, 2, tmp_path)
    assert len(run.records) > 2
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = [e for p in ProfileData.from_file(str(path)).planes
             for ln in p.lines for e in ln.events if e.name == "unit"]
    assert len(spans) == 2
