"""Device milliseconds per step in the held experts' grouped products and
their activation: the operations under the program's ``moe.experts`` scope
(forward, recomputation and backward), from the trace and the compiled
step's scope map (``bench/scopes.py``)."""
from bench.scopes import scope_ms


def read(run):
    return scope_ms(run, ("moe.experts",))
