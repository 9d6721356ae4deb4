"""Poisson arrivals: ``{"dist": "poisson", "rate_per_s"}``, exponential
gaps of mean 1 / rate, in seconds."""
import numpy as np


def at(spec: dict, u: np.ndarray) -> np.ndarray:
    return -np.log1p(-u) / float(spec["rate_per_s"])
