"""Per-sweep host-stage times from the program's own profiler spans.

``repro.api.sweep.run_sweep`` and ``repro.core.jax_engine.run_fleet_scan``
open one ``jax.profiler.TraceAnnotation`` per stage of a sweep
(docs/sweeps.md, "Tracing a sweep").  They land on the benchmark's host
line of the trace (``Trace.host``), on the clock the device events use, so
a stage's time and the device idle that no stage explains are interval
arithmetic over the traced window.  A program without these spans gives
None from every reader here, and the result line leaves the metric out.
"""
from __future__ import annotations

from typing import Optional

from bench.xplane import clip, gaps, length, subtract, union

# every span of a sweep but its root, ``run_sweep``
STAGES = ("sweep.sample", "sweep.workload", "sweep.fleet_arrays",
          "fleet_scan.put", "fleet_scan.call", "fleet_scan.fetch",
          "sweep.collect")


def _spans(trace, names) -> list:
    """The disjoint union of the host spans named in ``names``, clipped to
    the traced window."""
    return clip(union((s, e) for n, s, e in trace.host if n in names),
                trace.lo, trace.hi)


def stage_ms(trace, name: str) -> Optional[float]:
    """Milliseconds per sweep in the spans named ``name`` inside the traced
    window (a stage opened twice in a sweep counts both); None where the
    trace has no such span."""
    spans = _spans(trace, (name,))
    if not spans:
        return None
    return length(spans) / (1e6 * len(trace.steps))


def idle_unattributed_ms(trace) -> Optional[float]:
    """Milliseconds per sweep in which the first device is idle inside the
    traced window and no stage span is open on the host; None where the
    trace has no stage span."""
    spans = _spans(trace, STAGES)
    if not spans:
        return None
    idle = gaps(trace.busy(trace.devices[0]), trace.lo, trace.hi)
    return length(subtract(idle, spans)) / (1e6 * len(trace.steps))
