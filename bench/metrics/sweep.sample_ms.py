"""Host milliseconds per sweep in the program's ``sweep.sample`` span,
from the trace (``bench/spans.py``)."""
from bench.spans import stage_ms


def read(run):
    return stage_ms(run.trace, "sweep.sample")
