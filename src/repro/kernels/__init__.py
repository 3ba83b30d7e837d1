"""Pallas TPU kernels: each package has the kernel, a jit'd ``ops``
wrapper that the models call, and a pure-jnp ``ref`` oracle.

The wrappers choose the execution mode from the backend: compiled on the
TPU, interpreted on the CPU (tests), and nothing else.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas runs interpreted on the current default backend."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels run compiled on 'tpu' or "
                       f"interpreted on 'cpu', not on {backend!r}")
