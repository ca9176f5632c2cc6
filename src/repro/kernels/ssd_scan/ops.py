"""jit'd wrapper: drop-in SSD mixer backed by the Pallas chunk kernel.

``ssd_scan(..., backend="pallas")`` matches ``repro.models.mamba2
.ssd_chunked`` numerically (tests sweep shapes/dtypes against
``ssd_naive``).  The kernel runs compiled on a TPU and interpreted only
on the CPU backend.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.models.mamba2 import ssd_chunked, ssd_naive


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan(x, log_a, B, C, dt, chunk: int = 256, backend: str = "jnp"):
    if backend == "pallas":
        return ssd_scan_pallas(x, log_a, B, C, dt, chunk=chunk,
                               interpret=jax.default_backend() == "cpu")
    if backend == "jnp":
        return ssd_chunked(x, log_a, B, C, dt, chunk)
    if backend == "naive":
        return ssd_naive(x, log_a, B, C, dt)
    raise ValueError(backend)
