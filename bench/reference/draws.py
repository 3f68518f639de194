"""Where a cell's random numbers come from.

The scheduler consumes three kinds of draw (see :mod:`.sim`).  Two sources
supply them:

* :class:`Mersenne` -- Python's Mersenne twister seeded with the cell's
  seed, drawn in event order: one ``randrange`` per operation fetched
  (only the first sets the cursor), one ``random`` per thread's phase at
  start, one ``random`` per IO's jitter.  This is the draw order of the
  plain loop simulator.
* :class:`Threefry` -- the counter-based layout in which the batched grid
  draws: JAX's threefry (non-partitionable bit layout) keyed by
  ``PRNGKey(seed)``, folded with the cell's identity, the CRC-32 of its
  little-endian ``(L_mem as float64, n_threads as int64)``.  From that cell
  key, fold 0 gives the cursor's uniform, fold ``2 + t`` two uniforms for
  thread ``t`` (the first is its phase), and fold 1 then fold ``c`` a
  ``(1024, n_u)`` block of uniforms for the suboperations ``1024 c`` to
  ``1024 c + 1023``; with jitter the only draw of a step, ``n_u`` is 1.
  A uniform is the top 52 of 64 random bits times ``2**-52``, and the
  cursor's offset is ``floor(u * n_trace)``.

Given the same draws, the reference and the program under test compute the
same sample path, so their results can be compared exactly rather than
statistically.
"""
from __future__ import annotations

import random
import struct
import zlib

__all__ = ["Mersenne", "Threefry"]

_CHUNK = 1024


class Mersenne:
    def __init__(self, seed: int):
        self.rng = random.Random(seed)

    def offset(self, n: int) -> int:
        return self.rng.randrange(n)

    def phase(self, tid: int) -> float:
        return self.rng.random()

    def jitter(self, step: int) -> float:
        return self.rng.random()


class Threefry:
    def __init__(self, seed: int, L_mem: float, n_threads: int):
        import jax
        import numpy as np

        self._jax, self._np = jax, np
        stream = zlib.crc32(struct.pack("<dq", float(L_mem), int(n_threads)))
        cpu = jax.devices("cpu")[0]
        with jax.enable_x64(True), jax.threefry_partitionable(False), \
                jax.default_device(cpu):
            self.key = jax.random.fold_in(jax.random.PRNGKey(seed), stream)
            self._chunks = jax.random.fold_in(self.key, 1)
            self._cursor = self._uniform(jax.random.fold_in(self.key, 0), ())
            phases = jax.vmap(lambda t: jax.random.bits(
                jax.random.fold_in(self.key, 2 + t), (2,), "uint64"))(
                    np.arange(n_threads, dtype=np.int32))
        self._phases = self._to_unit(np.asarray(phases)[:, 0]).tolist()
        self._blocks: dict = {}
        self._first_fetch = True

    def _to_unit(self, bits):
        return (bits >> self._np.uint64(12)).astype(self._np.float64) \
            * 2.0 ** -52

    def _uniform(self, key, shape):
        jax = self._jax
        return self._to_unit(self._np.asarray(
            jax.random.bits(key, shape, "uint64")))

    def offset(self, n: int) -> int:
        # Only the first fetch sets the cursor; later fetches draw nothing.
        if not self._first_fetch:
            return 0
        self._first_fetch = False
        return int(self._np.floor(self._cursor * n))

    def phase(self, tid: int) -> float:
        return self._phases[tid]

    def jitter(self, step: int) -> float:
        c, row = divmod(step, _CHUNK)
        block = self._blocks.get(c)
        if block is None:
            jax = self._jax
            with jax.enable_x64(True), jax.threefry_partitionable(False), \
                    jax.default_device(jax.devices("cpu")[0]):
                block = self._uniform(jax.random.fold_in(self._chunks, c),
                                      (_CHUNK, 1))[:, 0].tolist()
            self._blocks = {c: block}
        return block[row]
