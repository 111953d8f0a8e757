"""The one place that decides numeric precision.

The simulator engines, the host §6 optimizer, the lint entries and the
tests all trace or run their float64/int64 work inside :func:`x64`, so a
later change of precision regime (for example an f32/i32 regime on the
TPU) is made here once rather than at every call site.
"""

from __future__ import annotations

import jax


def x64():
    """Context manager under which JAX creates float64/int64 values.

    Usage: ``with x64(): ...``.  Outside it JAX keeps its default
    32-bit types.
    """
    return jax.enable_x64(True)
