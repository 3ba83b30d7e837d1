"""Device milliseconds per sweep in the compiled fleet-scan program
(``core/jax_engine.py``'s ``_fleet_scan_core``), from the trace."""

PROGRAM = r"_fleet_scan_core"


def read(run):
    s = run.trace.program_s(PROGRAM)
    return None if s is None else 1e3 * s / len(run.trace.steps)
