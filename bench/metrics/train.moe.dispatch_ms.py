"""Device milliseconds per step in the expert layers' routing, dispatch and
combine: the operations under the program's ``moe.route``,
``moe.dispatch`` and ``moe.combine`` scopes (forward, recomputation and
backward), from the trace and the compiled step's scope map
(``bench/scopes.py``)."""
from bench.scopes import scope_ms


def read(run):
    return scope_ms(run, ("moe.route", "moe.dispatch", "moe.combine"))
