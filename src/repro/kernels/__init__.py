"""Pallas kernels, and the one place that decides how they run.

A TPU compiles every kernel; the CPU runs them in interpret mode, which
is how the tests check them against their jnp references.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True where Pallas kernels must run in interpret mode: on the CPU,
    and nowhere else.  A TPU never falls back to interpreting."""
    return jax.default_backend() == "cpu"
