"""Device milliseconds per step in the jitted train step
(``parallel/fsdp.py``'s ``train_step``), from the trace."""

PROGRAM = r"train_step"


def read(run):
    s = run.trace.program_s(PROGRAM)
    return None if s is None else 1e3 * s / len(run.trace.steps)
