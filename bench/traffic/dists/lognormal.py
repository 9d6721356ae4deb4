"""Lognormal lengths: ``{"dist": "lognormal", "median", "sigma", "min",
"max"}``, clipped to [min, max] and rounded."""
from statistics import NormalDist

import numpy as np


def at(spec: dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in u])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"]))
