"""Bounded Zipf keys over ``[0, n_keys)``: rank ``r`` has probability
proportional to ``r ** -exponent``, drawn by inverse CDF, and ranks are
scattered over the key space by the multiplicative hash ``r * 2654435761
mod n_keys`` so that hot keys are not neighbours.  ``read_write`` is the
read:write ratio; a write is a Bernoulli draw after the keys."""
from __future__ import annotations

import numpy as np

from ._mix import writes


def stream(n_keys: int, n_ops: int, exponent: float = 0.99,
           read_write=(1, 0), seed: int = 0):
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -exponent)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(n_ops)).tolist()
    keys = [r * 2654435761 % n_keys for r in ranks]
    return keys, writes(n_ops, read_write, rng)
