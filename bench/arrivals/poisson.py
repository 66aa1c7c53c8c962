"""Poisson arrivals: exponential gaps of mean 1/rate.

The gaps are taken at evenly spaced quantiles and shuffled by the run's
seed, so every seed gets the same set of gaps in another order.
"""

import numpy as np

PARAMS = ()


def gaps(n: int, rate: float, rng) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)
