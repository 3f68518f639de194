"""The read/write mix shared by the key distributions."""
from __future__ import annotations


def writes(n_ops: int, read_write, rng) -> list:
    """``n_ops`` write flags: none for a read-only mix, else a Bernoulli
    draw with probability ``w / (r + w)`` per operation."""
    r, w = read_write
    if w == 0:
        return [False] * n_ops
    return (rng.random(n_ops) < w / (r + w)).tolist()
