"""The harness's own copy of the replica-exchange acceptance rule: even/odd
neighbour swaps on a 1-D chain of learning rates, Metropolis criterion on
member losses, draws from ``numpy.random.default_rng((seed, cycle))``."""
from __future__ import annotations

import math

import numpy as np


def metropolis_swaps(losses, temps, cycle: int, seed: int):
    """Returns (new_temps, accepted_pairs) for one exchange."""
    losses = np.asarray(losses, dtype=np.float64)
    temps = np.asarray(temps, dtype=np.float64).copy()
    rng = np.random.default_rng((seed, cycle))
    accepted = []
    for i in range(cycle % 2, len(losses) - 1, 2):
        j = i + 1
        d = (losses[i] - losses[j]) * (1.0 / temps[i] - 1.0 / temps[j])
        if math.log(max(rng.random(), 1e-12)) < d:
            temps[i], temps[j] = temps[j], temps[i]
            accepted.append((i, j))
    return temps, accepted
