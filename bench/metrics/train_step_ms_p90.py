"""90th percentile of the times of all train steps in the window, each
from the call to its loss on the host."""
from bench.common import percentile


def read(run):
    return 1e3 * percentile([r["end"] - r["start"] for r in run.records], 90)
