"""Milliseconds per sweep in which the device is idle and no stage span of
the program is open on the host, from the trace (``bench/spans.py``)."""
from bench.spans import idle_unattributed_ms


def read(run):
    return idle_unattributed_ms(run.trace)
