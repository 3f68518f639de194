"""One simulation cell, as a plain discrete-event loop on one core.

The machine: ``n_threads`` user-level threads on one core's FIFO ready
ring.  Each thread replays operations from the recorded trace, taken from
one shared cyclic cursor that starts at a random offset.  A suboperation
burns its CPU time and then yields, which costs one context switch
``T_sw``.  A slow-memory access (``MEM``) first waits for the prefetch that
was issued when the previous suboperation of the thread yielded; at most
``P`` prefetches are in flight, and a prefetch issued while all ``P`` slots
are busy starts when the earliest one completes, then takes ``L_mem``.  An
IO submission (``PREIO``) parks the thread until the IO completes: IOs are
striped round-robin over ``n_ssd`` devices, each admitting one IO every
``1 / R_io`` seconds (a token clock), and take ``L_io`` scaled by a uniform
jitter of +-``jitter`` plus the switch hop ``L_switch``.  Parked threads
rejoin the ring in wake-time order (first parked first on ties).  When no
thread is ready the core idles to the earliest wake-up.

Closed loop, a thread starts its next operation as soon as it finishes
one.  Open loop, operation ``i`` (counted in issue order, threads in tid
order first) arrives at ``arrivals[i]``; a thread whose next operation has
not arrived parks until it does, its prefetch is issued no earlier than the
arrival, and an operation's latency is its sojourn, arrival to completion.

The first ``2 * n_threads`` completions are warm-up.  Throughput is the
measured completions over the time from the first measured completion to
the end.  Three things are random, and ``draws`` (see :mod:`.draws`)
supplies them: the cursor's starting offset, each thread's start phase
(its first prefetch completes that fraction of ``L_mem`` after it starts),
and each IO's jitter, keyed by the number of suboperations executed before
it.

``f32=True`` computes every time value in float32 (each sum or product is
rounded to float32): the lower-precision control of the correctness check.
"""
from __future__ import annotations

import heapq
import math
import struct
from collections import deque
from dataclasses import dataclass

from .trace import MEM, PREIO

__all__ = ["Device", "simulate", "nearest_rank"]


@dataclass(frozen=True)
class Device:
    """The device pool and core constants of a configuration (seconds)."""

    P: int
    T_sw: float
    L_io: float
    jitter: float
    R_io: float
    n_ssd: int
    L_switch: float


_F32 = struct.Struct("f")


def _to_f32(x: float) -> float:
    return _F32.unpack(_F32.pack(x))[0]


def _same(x: float) -> float:
    return x


def nearest_rank(values: list, q: float) -> float:
    """Nearest-rank quantile of a sorted list."""
    return values[max(math.ceil(q * len(values)) - 1, 0)]


def simulate(trace, dev: Device, L_mem: float, n_threads: int, n_ops: int,
             draws, arrivals=None, deadline: float = 0.0,
             f32: bool = False) -> dict:
    """Run one cell until ``n_ops`` measured operations complete.

    ``trace`` is a :class:`~reference.trace.Trace`.  Returns the
    throughput (ops/s), the measured time, and with ``arrivals`` the
    sojourn percentiles (seconds) and the count that missed ``deadline``.
    """
    r = _to_f32 if f32 else _same
    kinds, starts, ends = trace.kinds, trace.starts, trace.ends
    durs = [r(d) for d in trace.durs]
    n_trace = len(starts)
    T_sw, L_mem_ = r(dev.T_sw), r(L_mem)
    L_io, L_switch = r(dev.L_io), r(dev.L_switch)
    spacing = r(1.0 / dev.R_io) if dev.R_io > 0.0 else 0.0
    warmup = 2 * n_threads
    arr = None if arrivals is None else [r(a) for a in arrivals]

    cursor = -1
    n_issued = 0
    pos = [0] * n_threads          # flat index of the thread's next subop
    end = [0] * n_threads          # flat end of the thread's current op
    pf = [0.0] * n_threads         # completion of its outstanding prefetch
    op_start = [0.0] * n_threads   # arrival (open) or issue (closed) time
    ready: deque = deque()
    parked: list = []              # (wake, order, tid)
    order = 0

    def fetch(tid: int, now: float) -> None:
        nonlocal cursor, n_issued
        j = draws.offset(n_trace)
        if cursor < 0:
            cursor = j
        pos[tid], end[tid] = starts[cursor], ends[cursor]
        cursor = (cursor + 1) % n_trace
        if arr is None:
            op_start[tid] = now
        else:
            op_start[tid] = arr[min(n_issued, len(arr) - 1)]
        n_issued += 1

    for tid in range(n_threads):
        fetch(tid, 0.0)
        pf[tid] = r(op_start[tid] + r(draws.phase(tid) * L_mem_))
        if op_start[tid] > 0.0:
            order += 1
            heapq.heappush(parked, (op_start[tid], order, tid))
        else:
            ready.append(tid)

    inflight: list = []            # completion times of in-flight prefetches
    tokens = [0.0] * dev.n_ssd     # next admission time per SSD
    next_ssd = 0
    done = counted = missed = 0
    t_first = None
    sojourns: list = []
    now = 0.0
    step = 0                       # suboperations executed so far
    while counted < n_ops:
        while parked and parked[0][0] <= now:
            ready.append(heapq.heappop(parked)[2])
        if not ready:
            now = max(now, parked[0][0])
            while parked and parked[0][0] <= now:
                ready.append(heapq.heappop(parked)[2])
        tid = ready.popleft()
        i = pos[tid]
        kind = kinds[i]
        if kind == MEM and pf[tid] > now:
            now = pf[tid]
        now = r(now + durs[i])
        i += 1
        finished = i >= end[tid]
        if finished:
            done += 1
            if done >= warmup:
                if t_first is None:
                    t_first = now
                counted += 1
                if arr is not None:
                    s = r(now - op_start[tid])
                    if deadline > 0.0 and s > deadline:
                        missed += 1
                    else:
                        sojourns.append(s)
            fetch(tid, now)
            i = pos[tid]
        wake = None
        if kind == PREIO and not finished:
            ssd = next_ssd % dev.n_ssd
            next_ssd += 1
            admit = now
            if spacing > 0.0:
                admit = max(admit, tokens[ssd])
                tokens[ssd] = r(admit + spacing)
            lat = L_io
            if dev.jitter > 0.0:
                u = draws.jitter(step)
                lat = r(lat * r(1.0 + r(dev.jitter * r(2.0 * u - 1.0))))
            wake = r(r(admit + lat) + L_switch)
        if kinds[i] == MEM:
            issue = max(now, op_start[tid]) if finished else now
            while inflight and inflight[0] <= issue:
                heapq.heappop(inflight)
            begin = issue if len(inflight) < dev.P else max(issue,
                                                             inflight[0])
            pf[tid] = r(begin + L_mem_)
            if len(inflight) >= dev.P:
                heapq.heappop(inflight)
            heapq.heappush(inflight, pf[tid])
        now = r(now + T_sw)
        step += 1
        pos[tid] = i
        if wake is not None:
            order += 1
            heapq.heappush(parked, (max(wake, now), order, tid))
        elif finished and op_start[tid] > now:
            order += 1
            heapq.heappush(parked, (op_start[tid], order, tid))
        else:
            ready.append(tid)

    elapsed = max(r(now - (t_first if t_first is not None else 0.0)), 1e-12)
    out = {"throughput": r(counted / elapsed), "time": elapsed,
           "counted": counted}
    if arr is not None:
        sojourns.sort()
        out["missed"] = missed
        out["count"] = len(sojourns)
        for name, q in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
            out[name] = nearest_rank(sojourns, q) if sojourns else math.nan
        out["max"] = sojourns[-1] if sojourns else math.nan
    return out
