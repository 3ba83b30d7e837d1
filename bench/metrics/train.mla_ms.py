"""Device milliseconds per step in the operations under the program's
``mla`` scope (latent attention: projections, the latent's norm and
expansion, rope, attention, the output projection; forward, recomputation
and backward), from the trace and the compiled step's scope map
(``bench/scopes.py``)."""
from bench.scopes import scope_ms


def read(run):
    return scope_ms(run, ("mla",))
