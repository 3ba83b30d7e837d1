"""jit'd wrapper for the chunked WKV6 kernel."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd


def wkv6(r, k, v, w_log, u, *, chunk: int = 64):
    return wkv6_fwd(r, k, v, w_log, u, chunk=chunk,
                    interpret=interpret_mode())
