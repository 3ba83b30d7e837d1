"""Pieces every part of the benchmark shares: the device check, the compile
clock, the peaks table and loading a file of the benchmark by its name.

Nothing here imports the program under test.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; raises `NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r} devices and no TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while active, and
    how many backend compilations (or persistent-cache loads) it made."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            if event == _COMPILE_EVENTS[-1]:
                self.compiles += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind missing
    from ``peaks.json`` is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: str):
    """Import the file ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod

