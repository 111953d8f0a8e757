"""Storage precision of the float32 parts: float32 as the configuration
states, or bfloat16 for the control (values rounded to it and held in
float32 arrays, so products still accumulate in float32)."""

from __future__ import annotations

import ml_dtypes
import numpy as np


def rounder(lo):
    lo = np.dtype(lo)
    if lo == np.float32:
        return lambda a: a
    if lo == np.dtype(ml_dtypes.bfloat16):
        return lambda a: np.asarray(a, np.float32).astype(lo).astype(np.float32)
    raise ValueError(f"unsupported storage precision {lo}")


BF16 = np.dtype(ml_dtypes.bfloat16)
