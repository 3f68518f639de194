"""An Aerospike-like hash index: an open-addressing table of 16-byte slots
on slow memory (load factor at most ``load_factor``, capacity a power of
two), values on SSD.  The table is bulk-loaded, untraced, with every key in
a seeded random order.  A read hashes the key (DRAM compute), walks the
linear-probe run paying one hop per 64-byte line of slots it touches, and
reads the value from SSD if the key is there; a write updates the slot in
place (one hop) and every ``flush_block // value_size`` writes flushes
the write buffer (one IO)."""
from __future__ import annotations

import numpy as np

from ..trace import US

_GOLDEN = 0x9E3779B97F4A7C15
_M64 = (1 << 64) - 1


class Store:
    def __init__(self, n_keys: int, times, load_factor: float = 0.7,
                 slots_per_line: int = 4, value_size: int = 1024,
                 flush_block: int = 131072, seed: int = 0):
        self.t = times
        self.spl = slots_per_line
        self.flush_every = max(flush_block // value_size, 1)
        cap = 1
        while cap * load_factor < n_keys:
            cap *= 2
        self.mask = cap - 1
        self.slots = [-1] * cap
        for k in np.random.default_rng(seed).permutation(n_keys).tolist():
            i = self._home(k)
            while self.slots[i] >= 0:
                i = (i + 1) & self.mask
            self.slots[i] = k
        self.pending = 0

    def _home(self, k: int) -> int:
        return (((k * _GOLDEN) & _M64) >> 32) & self.mask

    def op(self, k: int, is_write: bool, rec) -> None:
        t = self.t
        rec.cpu(t.t_probe)
        i = self._home(k)
        line = -1
        while True:
            if i // self.spl != line:
                line = i // self.spl
                rec.mem()
            s = self.slots[i]
            if s == k or s < 0:
                break
            i = (i + 1) & self.mask
        found = s == k
        if is_write:
            rec.cpu(t.t_value)
            rec.mem()
            self.pending += 1
            if self.pending >= self.flush_every:
                self.pending = 0
                rec.io(pre_extra=0.5 * US)
        elif found:
            rec.io()
            rec.cpu(t.t_value)
        rec.end_op()
