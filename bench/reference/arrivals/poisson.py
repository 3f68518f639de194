"""Poisson arrivals: ``n`` timestamps (seconds) whose gaps are exponential
with mean ``1 / rate``, drawn from numpy's PCG64 stream keyed on ``seed``."""
from __future__ import annotations

import numpy as np


def timestamps(spec: dict, n: int) -> list:
    rng = np.random.default_rng(spec["seed"])
    return np.cumsum(rng.exponential(1.0 / spec["rate"], n)).tolist()
