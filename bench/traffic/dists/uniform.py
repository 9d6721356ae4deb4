"""Uniform lengths: ``{"dist": "uniform", "min", "max"}``, rounded."""
import numpy as np


def at(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    return np.clip(np.rint(lo + u * (hi - lo)), lo, hi)
