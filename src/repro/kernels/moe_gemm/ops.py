"""jit'd wrapper for the grouped-GEMM kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.moe_gemm.kernel import moe_gemm_fwd


def moe_gemm(x, w):
    """x: (E, C, d), w: (E, d, h) -> (E, C, h)."""
    return moe_gemm_fwd(x, w, interpret=interpret_mode())
