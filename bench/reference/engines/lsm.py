"""A RocksDB-like store: one sorted run cut into data blocks on SSD, an LRU
block cache on slow memory, and the memtable and fence index in DRAM.

A read probes the memtable and the fence index (DRAM compute), then the
block cache's hash bucket (one hop).  A cache hit relinks the LRU entry
(two hops); a miss reads the 4-kB block from SSD, checks and decodes it,
installs it (two hops) and, past capacity, evicts the LRU tail (two more).
Either way the block is searched: a binary search over its restart points
plus the expected quarter of a restart interval scanned linearly, one hop
per probe; then the value is decoded.  A write goes to the memtable; every
``memtable_ops`` writes flush and compact (two IOs and a CPU burst).
"""
from __future__ import annotations

import math
from collections import OrderedDict

from ..trace import US


class Store:
    def __init__(self, n_keys: int, times, entries_per_block: int = 10,
                 cache_blocks: int | None = None, restart_interval: int = 16,
                 memtable_ops: int = 4096):
        self.t = times
        self.epb = entries_per_block
        n_blocks = -(-n_keys // entries_per_block)
        # sized for about a 67% hit ratio under Zipf 0.99
        self.cap = (cache_blocks if cache_blocks is not None
                    else max(n_blocks // 12, 1))
        n_restarts = max(entries_per_block // restart_interval, 1)
        self.search_hops = (max(math.ceil(math.log2(n_restarts + 1)), 1)
                            + min(restart_interval, entries_per_block) // 4)
        self.memtable_ops = memtable_ops
        self.cache: OrderedDict = OrderedDict()
        self.writes = 0

    def op(self, k: int, is_write: bool, rec) -> None:
        t = self.t
        if is_write:
            rec.cpu(t.t_probe + t.t_value)
            self.writes += 1
            if self.writes >= self.memtable_ops:
                self.writes = 0
                rec.io(pre_extra=1.0 * US)
                rec.io(pre_extra=1.0 * US)
                rec.cpu(20.0 * US)
            rec.end_op()
            return
        rec.cpu(t.t_probe)
        rec.cpu(t.t_probe)
        block = k // self.epb
        rec.mem()
        if block in self.cache:
            self.cache.move_to_end(block)
            rec.mem(2)
        else:
            rec.io()
            rec.cpu(t.t_value)
            self.cache[block] = None
            rec.mem(2)
            if len(self.cache) > self.cap:
                self.cache.popitem(last=False)
                rec.mem(2)
        rec.mem(self.search_hops)
        rec.cpu(t.t_value)
        rec.end_op()
