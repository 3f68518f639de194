"""Uniform keys over ``[0, n_keys)``; ``read_write`` is the read:write
ratio, a write being a Bernoulli draw after the keys."""
from __future__ import annotations

import numpy as np

from ._mix import writes


def stream(n_keys: int, n_ops: int, read_write=(1, 0), seed: int = 0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n_ops).tolist()
    return keys, writes(n_ops, read_write, rng)
