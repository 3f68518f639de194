"""Aerospike-like engine: in-memory tree index on slow memory, values on SSD."""
from __future__ import annotations

import numpy as np

from ..trace_ir import US
from .base import EngineTimes, register_engine
from .trace import Recorder

__all__ = ["TreeIndexStore"]


@register_engine("tree-index", "aerospike-like")
class TreeIndexStore:
    """Per-sprig unbalanced BSTs of 64-byte nodes (Aerospike primary index).

    get  = sprig hash (DRAM) + tree walk (slow-memory hops) + one SSD read.
    put  = tree walk + write-buffer append; a large flush IO every
           ``flush_block // value_size`` writes (Aerospike write blocks).
    """

    def __init__(
        self,
        n_keys: int,
        n_sprigs: int = 256,
        value_size: int = 1536,
        flush_block: int = 131072,
        times: EngineTimes | None = None,
        seed: int = 0,
    ):
        # Aerospike's storage path spends much more CPU per IO than raw
        # io_uring (network/defrag bookkeeping); the paper's Table 1
        # example quotes T_io_pre ~ 4 us, T_io_post ~ 3 us for this class.
        self.times = times or EngineTimes(t_io_pre=3.0 * US, t_io_post=2.0 * US)
        self.n_keys = n_keys
        self.n_sprigs = n_sprigs
        self.value_size = value_size
        self.flush_every = max(flush_block // value_size, 1)
        rng = np.random.default_rng(seed)
        order = rng.permutation(n_keys)
        self.sprig_of = (
            (np.arange(n_keys, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15))
            % np.uint64(n_sprigs)
        ).astype(np.int64)
        # array-based BST per sprig: node i holds the i-th inserted key
        # order[i], children left/right (-1: none)
        self.key = order.astype(np.int64)
        self.left, self.right, self.root = self._bulk_load(
            self.key, self.sprig_of[self.key], n_sprigs
        )
        self._pending_writes = 0

    @staticmethod
    def _bulk_load(key: np.ndarray, sprig: np.ndarray, n_sprigs: int):
        """Children and roots of the BSTs that inserting ``key`` in order gives.

        Such a tree is the Cartesian tree of its keys with the insertion
        index as min-heap priority: a node's parent is the later inserted of
        its nearest earlier-inserted neighbours in key order within its
        sprig, and it is the right child of a lower parent, the left child
        of a higher one.  Both neighbours come from a nearest-smaller-value
        search by pointer jumping; nodes are numbered by insertion index.
        """
        n = len(key)
        node = np.lexsort((key, sprig))  # nodes in (sprig, key) order
        same = sprig[node[1:]] == sprig[node[:-1]]
        pos = np.arange(n)
        below = np.where(np.r_[False, same], pos - 1, -1)
        above = np.where(np.r_[same, False], pos + 1, -1)
        for ptr in (below, above):
            # until ptr[j] is -1 or was inserted before node[j], jump over it
            live = pos[ptr >= 0]
            while len(live):
                live = live[node[ptr[live]] > node[live]]
                ptr[live] = ptr[ptr[live]]
                live = live[ptr[live] >= 0]
        # the nearest earlier-inserted node below / above (-1: none)
        lo = np.where(below >= 0, node[below], -1)
        hi = np.where(above >= 0, node[above], -1)
        left = np.full(n, -1, dtype=np.int64)
        right = np.full(n, -1, dtype=np.int64)
        right[lo[lo > hi]] = node[lo > hi]  # parent below: the later inserted
        left[hi[hi > lo]] = node[hi > lo]
        root = np.full(n_sprigs, -1, dtype=np.int64)
        is_root = lo == hi  # both -1
        root[sprig[node[is_root]]] = node[is_root]
        return left, right, root.tolist()

    def _sprig(self, k: int) -> int:
        # python ints: intentional 64-bit multiplicative hash without
        # numpy's overflow warning
        return ((int(k) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF) % self.n_sprigs

    def _walk(self, k: int, rec: Recorder) -> bool:
        rec.cpu(self.times.t_probe)  # sprig hash + root lookup (DRAM)
        cur = self.root[self._sprig(k)]
        while cur >= 0:
            rec.mem()  # node is a 64-byte record on slow memory
            if k == self.key[cur]:
                return True
            cur = self.left[cur] if k < self.key[cur] else self.right[cur]
        return False

    def op(self, k: int, is_write: bool, rec: Recorder) -> None:
        found = self._walk(k, rec)
        if is_write:
            rec.cpu(self.times.t_value)       # serialize into write buffer
            rec.mem()                          # update index entry in place
            self._pending_writes += 1
            if self._pending_writes >= self.flush_every:
                self._pending_writes = 0
                rec.io(pre_extra=0.5 * US)     # large-block flush write
        elif found:
            rec.io()                           # read value from SSD
            rec.cpu(self.times.t_value)
        rec.end_op()

    def stats(self) -> dict:
        return {}
