"""jit'd wrapper for the fused RMSNorm kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd


def rmsnorm(x, w, residual=None, eps: float = 1e-5):
    return rmsnorm_fwd(x, w, residual, eps=eps, interpret=interpret_mode())
