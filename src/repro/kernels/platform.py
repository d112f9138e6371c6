"""Where the Pallas kernels run, decided once from the platform.

On a TPU backend every kernel is compiled by Mosaic; there is no silent
interpreter fallback on the chip. Everywhere else (the CPU test suite) the
same kernels run in the Pallas interpreter, so one code path is validated
on CPU and compiled on the chip.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"
