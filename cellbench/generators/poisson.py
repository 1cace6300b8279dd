"""Poisson arrivals for an open loop."""

import numpy as np


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Send offsets in seconds: Poisson gaps at ``rate`` drawn from the
    traffic file's ``traffic_seed``, handed out in the run seed's
    order, so every seed sends the same set of gaps."""
    rate = float(traffic["rate"])
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(int(traffic["traffic_seed"])).exponential(
        1.0 / rate, size=n)
    gaps *= seconds / (gaps.sum() + gaps.mean())  # all due inside window
    order = np.random.default_rng([int(seed), 0xa771]).permutation(n)
    return np.cumsum(gaps[order])
