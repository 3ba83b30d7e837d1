#!/usr/bin/env python3
"""Record the small chip trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Three steps of the train driver at a tiny size, each in the harness's
``train`` span, traced as a ``--trace 1`` run traces (Python tracer off).
Run it on a machine with a TPU; the trace is written to ``<out>``.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.common import BENCH, load_module, require_tpu  # noqa: E402
from bench.tests.tiny import train_case                  # noqa: E402


def main(out: str) -> int:
    import jax
    devices = require_tpu(1)
    drv = load_module(BENCH / "drivers" / "train.py", "bench_driver_train")
    cfg, traffic = train_case()
    d = drv.Driver(cfg, traffic, 7, devices)
    d.setup()
    logdir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    for k in range(3):
        with jax.profiler.StepTraceAnnotation(d.span, step_num=k):
            d.step(k)
    jax.profiler.stop_trace()
    path = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(logdir, ignore_errors=True)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
