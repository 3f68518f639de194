"""An Aerospike-like primary index: the keys are spread over ``n_sprigs``
sprigs by a multiplicative hash, each sprig an unbalanced binary search
tree of 64-byte nodes on slow memory (smaller keys to the left), values on
SSD.  The trees are bulk-loaded, untraced, with every key in a seeded
random order.  A read hashes the key to its sprig (DRAM compute), walks the
sprig's tree paying one hop per node it visits, the matching one included,
and reads the value from SSD if the key is there; a write updates the
index entry in place (one hop) and every ``flush_block // value_size``
writes flushes the write buffer (one IO).  Aerospike's storage path spends
3.0 us of CPU to submit an IO and 2.0 us to complete one, in place of the
recorder's usual costs."""
from __future__ import annotations

import numpy as np

from ..trace import US

_GOLDEN = 0x9E3779B97F4A7C15
_M64 = (1 << 64) - 1
IO_PRE, IO_POST = 3.0 * US, 2.0 * US


class Store:
    def __init__(self, n_keys: int, times, n_sprigs: int = 256,
                 value_size: int = 1536, flush_block: int = 131072,
                 seed: int = 0):
        self.t = times
        self.n_sprigs = n_sprigs
        self.flush_every = max(flush_block // value_size, 1)
        self.pre = IO_PRE - times.t_io_pre
        self.post = IO_POST - times.t_io_post
        # A node is its key: each tree is a root per sprig and the two
        # child maps.
        self.root: dict = {}
        self.left: dict = {}
        self.right: dict = {}
        for k in np.random.default_rng(seed).permutation(n_keys).tolist():
            s = self._sprig(k)
            cur = self.root.get(s)
            if cur is None:
                self.root[s] = k
                continue
            while True:
                side = self.left if k < cur else self.right
                nxt = side.get(cur)
                if nxt is None:
                    side[cur] = k
                    break
                cur = nxt
        self.pending = 0

    def _sprig(self, k: int) -> int:
        return ((k * _GOLDEN) & _M64) % self.n_sprigs

    def op(self, k: int, is_write: bool, rec) -> None:
        t = self.t
        rec.cpu(t.t_probe)
        cur = self.root.get(self._sprig(k))
        found = False
        while cur is not None:
            rec.mem()
            if cur == k:
                found = True
                break
            cur = (self.left if k < cur else self.right).get(cur)
        if is_write:
            rec.cpu(t.t_value)
            rec.mem()
            self.pending += 1
            if self.pending >= self.flush_every:
                self.pending = 0
                rec.io(pre_extra=self.pre + 0.5 * US, post_extra=self.post)
        elif found:
            rec.io(pre_extra=self.pre, post_extra=self.post)
            rec.cpu(t.t_value)
        rec.end_op()
