"""The scheduler step of the jax sweep grid (batched over grid cells).

:mod:`repro.core.sim.replay_jax` replays one scheduler *step* -- wake the
completed parked threads, pop the FIFO ready ring, execute one suboperation
(MEM stall / PREIO submit / op completion), issue the next prefetch -- for
every cell of a latency x threads grid at once, as a ``lax.scan`` over
:func:`make_substep`'s body.  The step is one string of vector ops on every
backend: each thread-plane read is a one-hot select (:func:`_read_row`),
each write a one-hot merge (:func:`_write_row`), the histogram update a
one-hot add, and the idle-skip runs straight-line, so a compiled step holds
no scatter, no row gather over a thread plane and no ``conditional``.  On
the TPU, whose thread-slot axis is the vector lanes, a gather's or a
scatter's rows would be worked through one at a time.

Lexicographic minima
--------------------
Every plane that is reduced to "earliest entry + which slot" goes through
:func:`ring_min`: one ``min`` over the exact keys, then one ``min`` over
the slot indices that attain it.  Keys are never altered, so distinct
times never tie, and exact ties break toward the lower index -- except in
the ready ring, where a thread derived from the wake plane beats a
re-entrant runner's ticket at the same instant (the loops drain wake-ups
at iteration start, before the runner re-joins).  Nothing here reads a
float's bits: XLA:TPU emulates float64 and cannot rewrite a 64-bit
``bitcast_convert_type``, and its emulation keeps float32's exponent range
(normals below ``2**-126`` flush to zero), so the scheduler also uses no
sub-normal-magnitude spacing constants.

State layout
------------
``G`` cells, ``T`` thread slots, ``P`` prefetch slots, ``S`` SSDs:

  ============  ============  =================================================
  plane         shape/dtype   contents
  ============  ============  =================================================
  ``cf``        (G, 6) f64    0 now, 1 prefetch-bw clock, 2 lock clock,
                              3 t_start, 4 t_end, 5 measured stall seconds
  ``ci``        (G, 6) i32    0 trace cursor, 1 IO round-robin, 2 completed
                              ops, 3 measured ops, 4 measured MEM accesses,
                              5 measuring flag
  ``stamp``     (G, T) f64    ready threads' ring ticket: the *pop time*
                              at which the thread last started a
                              suboperation (``INIT_KEY`` for the initial
                              ring, which sorts ahead of any pop);
                              ``BIG`` when parked or inactive
  ``wake``      (G, T) f64    parked threads' IO completion time (the
                              idle-skip reads it back as a time);
                              ``+inf`` when ready or inactive.  Threads
                              whose IO completed are derived into the
                              ring at pop time, never written back
  ``pft``       (G, T, 2) f64 0 outstanding prefetch completion time,
                              1 trace span ``end * 2**SPAN_SHIFT + i``
                              (both integers < 2**SPAN_SHIFT: exact)
  ``pf_slots``  (G, P) f64    P-deep in-flight prefetch window completion
                              times (the all-busy delay reads the minimum
                              back as a time; ties pick the lower slot)
  ``io_tok``    (G, S) f64    per-device IOPS token clocks (clock configs)
  ``io_bw``     (G, S) f64    per-device bandwidth token clocks
  ============  ============  =================================================

With ``n_cores = C > 1`` (see :func:`make_substep`) the thread planes hold
``T = C * T_per_core`` core-major slots indexed by *global* tid,
``pf_slots`` becomes ``(G, C, P)``, and one extra plane ``cores``
``(G, C, 2)`` (0 local clock, 1 prefetch-bw clock) sits between
``pf_slots`` and the IO clocks; ``cf[:, 0]``/``cf[:, 1]`` then carry the
global drain horizon (running max of pop times, mirroring the loop's
shared parked heap -- see the in-step comment) / nothing.  The thread
index of a slot is its column, so no plane stores thread ids and the
thread count has no encoding limit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.sim.arrivals import HIST_BINS, HIST_INV_LN_RATIO, HIST_LO
from ..core.trace_ir import MEM, PREIO

__all__ = [
    "SPAN_SHIFT", "BIG", "INIT_KEY", "ring_min",
    "pack_span", "unpack_span", "make_substep",
]

# Sentinel for "no entry" (parked/inactive threads in the stamp plane).
# Finite, and inside float32's range: XLA:TPU's emulated float64 turns
# larger magnitudes into inf/NaN.
BIG = 1e30

# Ring key of the initial ready threads: below every pop-time ticket, a
# pop at time zero included, so the first runner re-enters behind the
# untouched initial ring; the equal keys break toward the lower tid, the
# loops' initial deque order.
INIT_KEY = -1.0

SPAN_SHIFT = 26                    # pft span packing: end*2**26 + i, exact
_SPAN = float(1 << SPAN_SHIFT)     # in f64 while both stay below 2**26
_INV_SPAN = 1.0 / _SPAN


def ring_min(keys, wake_first=None):
    """Row-wise lexicographic minimum of a ``(G, N)`` key plane.

    Returns ``(key, idx)``: the smallest key of each row and the column
    that holds it.  Equal keys break toward columns flagged in the boolean
    ``wake_first`` plane, then toward the lower column.
    """
    n = keys.shape[1]
    kmin = jnp.min(keys, axis=1)
    col = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    if wake_first is not None:
        col = jnp.where(wake_first, col, col + n)
    code = jnp.min(jnp.where(keys == kmin[:, None], col, 2 * n), axis=1)
    return kmin, jnp.where(code >= n, code - n, code)


def pack_span(start, end):
    """``end * 2**SPAN_SHIFT + start`` as exact f64 (both < 2**SPAN_SHIFT)."""
    return end * _SPAN + start


def unpack_span(span):
    """Inverse of :func:`pack_span` -> ``(i, end)`` as f64 integers."""
    end = jnp.floor(span * _INV_SPAN)
    return span - end * _SPAN, end


def _read_row(plane, tid):
    """``plane[g, tid[g]]`` for a ``(G, T)`` or ``(G, T, K)`` plane, as a
    one-hot select.

    The select is a row max with every other slot at ``-inf``: it does
    no arithmetic on any value, so it is exact for every value but NaN
    (none is stored), ``+inf`` included.  A one-term masked *sum* is
    not, on XLA:TPU: its emulated float64 is a float32 pair, and an
    addition that meets the wake plane's ``+inf`` can leave NaN in
    the low word."""
    T = plane.shape[1]
    hot = jax.lax.broadcasted_iota(jnp.int32, (plane.shape[0], T), 1) \
        == tid[:, None]
    if plane.ndim == 3:
        hot = hot[:, :, None]
    return jnp.max(jnp.where(hot, plane, -jnp.inf), 1)


def _write_row(plane, tid, val):
    """``plane.at[g, tid[g]].set(val[g])`` as a one-hot merge."""
    T = plane.shape[1]
    hot = jax.lax.broadcasted_iota(jnp.int32, (plane.shape[0], T), 1) \
        == tid[:, None]
    if plane.ndim == 3:
        return jnp.where(hot[:, :, None], val[:, None, :], plane)
    return jnp.where(hot, val[:, None], plane)


def _grant(submit, clocks, devmask, spacing):
    """One token-clock grant: ``svc = max(submit, clocks[dev])``, and the
    selected device's clock advances to ``svc + spacing``.

    ``submit`` is ``(G, 1)``, ``clocks``/``devmask`` are ``(G, n_ssd)``,
    ``spacing`` broadcasts.  A spacing of 0 disables the clock (grant
    passes through); a fully-masked row (no IO this step) is not gated:
    its ``at_dev`` reads 0.0, and simulated time is non-negative, so
    ``max(submit, 0) == submit``.
    """
    enabled = spacing > 0.0
    at_dev = jnp.sum(jnp.where(devmask, clocks, 0.0), axis=-1, keepdims=True)
    svc = jnp.where(enabled, jnp.maximum(submit, at_dev), submit)
    new_clocks = jnp.where(enabled & devmask, svc + spacing, clocks)
    return svc, new_clocks


def _update(submit, devmask, tok, bw, inv_r, cost_bw):
    """The striped per-device token clocks' grant for one step, IOPS clock
    first, then bandwidth -- the bandwidth grant sees the IOPS-delayed
    service time, like ``SSDClocks.submit`` / the compiled loop.
    ``devmask`` one-hot selects each cell's device (all-zero rows for
    cells that submit no IO); clocks of unselected devices pass through.
    All shapes as in :func:`_grant`; returns ``(svc, tok, bw)``."""
    svc, tok = _grant(submit, tok, devmask, inv_r)
    svc, bw = _grant(svc, bw, devmask, cost_bw)
    return svc, tok, bw


def make_substep(*, n_u, n_ssd, has_eps, has_rho, has_jitter, has_rio,
                 has_bio, has_bmem, has_lock, has_arr=False, has_lat=False,
                 has_deadline=False, has_degrade=False, n_cores=1):
    """Build the scheduler substep body, specialized on the static config.

    The returned ``substep(state, u, kd, se, arr, nthr_g, n_trace,
    L_mem_g, warm_g, n_ops, dyn) -> state`` advances every cell by one
    suboperation execution.  ``state`` is the tuple documented in the
    module docstring (``io_tok``/``io_bw`` present only when an IO clock
    is configured); ``u`` is the ``(n_u, G)`` uniform block for this step;
    ``kd``/``se`` are the packed trace columns; ``arr`` the shared
    open-loop arrival timestamp vector (a 1-wide dummy when ``has_arr``
    is off); ``nthr_g`` the per-cell thread counts (int32, read only when
    ``has_arr``); ``dyn`` the tuple of dynamic scalars (the degrade pair
    last).

    ``has_arr`` replays the loops' open-loop driver: op completions fetch
    the next arrival at the shared index ``n_cores * nthr_g + done``
    (clamped to the last entry), stamp it as the new op's start, gate the
    next prefetch issue at ``max(now, arrival)``, and park the thread on
    the wake plane until the arrival clock when it is still in the
    future.  ``has_lat`` widens ``pft`` with an op-start slot, widens
    ``ci`` with a missed-op counter, and appends two state planes --
    ``hist`` (G, HIST_BINS) f64 sojourn log-histogram counts and
    ``latmax`` (G,) f64 exact max sojourn (see
    :mod:`repro.core.sim.arrivals` for the binning and its error bound).
    ``has_deadline`` additionally classifies measured sojourns above
    ``dyn``'s deadline as missed (counted, excluded from the histogram).
    ``has_degrade`` multiplies ``L_io`` by ``dyn``'s ``io_degrade`` for
    IOs submitted at ``now >= T_degrade`` (mid-run device slowdown; same
    submission-time rule as the loops' ``SSDClocks.submit``).

    ``n_cores > 1`` adds a core axis: thread planes become ``(G, C*T)``
    core-major (the column is the global tid), the prefetch window and
    its bandwidth clock become per-core (``pf_slots`` is ``(G, C, P)``,
    and a new ``cores`` plane
    ``(G, C, 2)`` carries each core's local clock and prefetch-bw clock),
    while the trace cursor, op counters, T_lock clock, and SSD token
    clocks stay shared -- exactly the generic loop's sharing.  Each step
    first picks the core with the earliest yield clock (the loop's core
    heap; ties break to the lower core id like ``heapq``) and then runs the
    single-core step body on that core's thread segment.
    """
    has_io_clock = has_rio or has_bio
    multicore = n_cores > 1
    i4 = jnp.int32

    def substep(s, u, kd, se, arr, nthr_g, n_trace, L_mem_g, warm_g,
                n_ops, dyn):
        (T_sw, eps, rho, L_dram, L_io, jitter, inv_R, cost_bw_io, L_switch,
         cost_bmem, T_lock, deadline, T_degrade, io_degrade) = dyn
        cf, ci, stamp, wake, pft, pf_slots = s[:6]
        si = 6
        if multicore:
            cores = s[si]
            si += 1
        if has_io_clock:
            io_tok, io_bw = s[si], s[si + 1]
            si += 2
        if has_lat:
            lat_hist, latmax = s[si], s[si + 1]
            si += 2
        G, T = stamp.shape
        un = iter(range(n_u))

        def lmem(uu, L):
            """sample_lmem for scalar latencies: DRAM-tier short-circuit."""
            if has_rho:
                return jnp.where(uu >= rho, L_dram, L)
            return L

        counted0 = ci[:, 3]
        reached = counted0 >= n_ops    # cell already took its last op

        if multicore:
            # -- core selection: the loop's core heap -----------------------
            # Heap entries are the cores' clocks at their last *yield*, NOT
            # their next-event times: the loop pops the core whose last run
            # ended earliest, and a core popped with an empty ring jumps
            # straight to its earliest parked wake and executes there -- it
            # never re-enters the heap re-keyed.  So selection compares the
            # yield clocks, and the idle-skip applies only to the *selected*
            # core (the single-core path per core segment).  The scan is an
            # exact unrolled min (C is small and static); strict ``<``
            # breaks ties to the lower cid, exactly ``heapq``'s (t, cid)
            # entries.
            C, Tpc = n_cores, T // n_cores
            core_now = cores[:, :, 0]                        # (G, C)
            wake3 = wake.reshape(G, C, Tpc)
            stamp3 = stamp.reshape(G, C, Tpc)
            cstar = jnp.zeros((G,), i4)
            now = core_now[:, 0]
            for c in range(1, C):
                cand = core_now[:, c]
                earlier = cand < now
                cstar = jnp.where(earlier, c, cstar)
                now = jnp.where(earlier, cand, now)
            # The selected core's ring head / idle-skip, exactly the
            # single-core derivation over its thread segment; the local
            # column plus the core's offset is the global tid.
            wake_c = _read_row(wake3, cstar)                 # (G, Tpc)
            stamp_c = _read_row(stamp3, cstar)
            ring_wake, ring_stamp = wake_c, stamp_c
        else:
            now = cf[:, 0]
            ring_wake, ring_stamp = wake, stamp

        # -- pop the ring head: a lexicographic min replaces the deque ------
        # Ring stamps are *entry tickets*: a thread re-enters the ring keyed
        # by its pop time, and a parked thread whose IO completed joins at
        # its wake time -- so the FIFO order is just time order, and
        # parked-but-complete threads can be *derived* into the ring at pop
        # time instead of being written back.  The key plane below stays a
        # temporary the backend fuses into the reductions; the materialized
        # wake drain it replaces (two carried full-plane writes per step)
        # was the single largest cost of the old step.
        def ring_head(now_v):
            woken = ring_wake <= now_v[:, None]
            return ring_min(jnp.where(woken, ring_wake, ring_stamp), woken)

        head, slot_tid = ring_head(now)

        # -- idle-skip: nothing ready, nothing eligible -> jump to the ------
        # earliest wake-up and re-derive the keys.  Every step runs the
        # second pass, straight-line: a cell that did not starve
        # re-derives identical keys from an unchanged ``now``.
        starved = head >= BIG
        w_min = jnp.min(ring_wake, axis=1)
        now = jnp.where(starved, jnp.maximum(now, w_min), now)
        head, slot_tid = ring_head(now)
        if multicore:
            pop_now = now
            tid = cstar * Tpc + slot_tid
        else:
            tid = slot_tid
        # The popped thread's next ring ticket.  The scalar loop drains
        # wake-ups only at iteration start, *after* the previous runner
        # re-joined the deque -- so a thread woken during the runner's
        # execution window queues behind it.  Keying the re-entrant runner
        # by its pop time (not its yield time) reproduces that order
        # exactly: wakes before the pop time drained at or before this
        # iteration and sort ahead; later wakes sort behind; a wake *at*
        # the pop time was drained at the start of this very iteration,
        # which is why ``ring_min`` breaks that tie toward the woken thread.
        ticket = now

        pft_r = _read_row(pft, tid)                  # (G, 2) or (G, 3)
        pf_tid0 = pft_r[:, 0]
        op_start_r = pft_r[:, 2] if has_lat else None
        i_f, end_f = unpack_span(pft_r[:, 1])
        kd_i = kd[i_f.astype(i4)]                    # (G, 2)
        kind = kd_i[:, 0]
        dur = kd_i[:, 1]

        # -- MEM: stall on the outstanding prefetch (or an eps re-fetch) ----
        is_mem = kind == MEM
        ready_at = pf_tid0
        if has_eps:
            u_eps = u[next(un)]
            u_evict = u[next(un)]
            ready_at = jnp.where(u_eps < eps,
                                 now + lmem(u_evict, L_mem_g), ready_at)
        stall = ready_at - now
        stalled = is_mem & (stall > 0.0)
        live = (ci[:, 5] > 0) & ~reached
        mem_stall = cf[:, 5] + jnp.where(stalled & live, stall, 0.0)
        mem_acc = ci[:, 4] + (is_mem & live)
        now = jnp.where(stalled, ready_at, now) + dur

        # -- op completion: counters, measurement window, next op, T_lock ---
        i2 = i_f + 1.0
        eoo = i2 >= end_f
        done = ci[:, 2] + eoo
        meas_evt = eoo & (done >= warm_g) & ~reached
        measuring = jnp.maximum(ci[:, 5], meas_evt)
        counted = counted0 + meas_evt
        t_start = jnp.where(meas_evt & (cf[:, 3] < 0.0), now, cf[:, 3])
        if has_arr:
            # The next op's arrival: the loops consume one shared index
            # per issue -- n_cores * n_threads at init, then one per
            # completion -- so completion k (pre-increment ``done``) reads
            # index total_threads + ci[:, 2].  Clamped to the last entry,
            # matching the loops' guard (only reachable after the cell
            # latched, where nothing observable depends on it).
            arr_next = arr[jnp.minimum(
                n_cores * nthr_g + ci[:, 2], arr.shape[0] - 1)]
        if has_lat:
            # Sojourn at the pre-T_lock completion instant, mirroring the
            # loops (collection happens before the lock charge there).
            sojourn = now - op_start_r
            if has_deadline:
                is_miss = sojourn > deadline
            else:
                is_miss = jnp.zeros_like(eoo)
            rec = meas_evt & ~is_miss
            missed = ci[:, 6] + (meas_evt & is_miss)
            b = jnp.clip(
                jnp.floor(jnp.log(jnp.maximum(sojourn, HIST_LO) / HIST_LO)
                          * HIST_INV_LN_RATIO),
                0, HIST_BINS - 1).astype(i4)
            inc = jnp.where(rec, 1.0, 0.0)
            hot = jax.lax.broadcasted_iota(
                i4, lat_hist.shape, 1) == b[:, None]
            lat_hist = lat_hist + jnp.where(hot, inc[:, None], 0.0)
            latmax = jnp.where(rec, jnp.maximum(latmax, sojourn), latmax)
            op_start_new = jnp.where(
                eoo, arr_next if has_arr else now, op_start_r)
        se_c = se[ci[:, 0]]                          # (G, 2)
        span_next = jnp.where(eoo, pack_span(se_c[:, 0], se_c[:, 1]),
                              pft_r[:, 1] + 1.0)
        ni = jnp.where(eoo, se_c[:, 0], i2)
        cursor = jnp.where(eoo, (ci[:, 0] + 1) % n_trace, ci[:, 0])
        lock_next = cf[:, 2]
        if has_lock:
            lock_end = jnp.maximum(now, lock_next) + T_lock
            now = jnp.where(eoo, lock_end, now)
            lock_next = jnp.where(eoo, lock_end, lock_next)

        # -- PREIO: submit against the striped per-device token clocks ------
        park = (kind == PREIO) & ~eoo
        io_rr = ci[:, 1]
        if not has_io_clock:
            svc = now
            io_out = ()
        elif n_ssd == 1:
            # Inlined single-device clocks (the common matrix config);
            # clocks only advance for cells actually submitting an IO.
            tok1, bw1 = io_tok[:, 0], io_bw[:, 0]
            svc = now
            if has_rio:
                svc = jnp.maximum(svc, tok1)
                tok1 = jnp.where(park, svc + inv_R, tok1)
            if has_bio:
                svc = jnp.maximum(svc, bw1)
                bw1 = jnp.where(park, svc + cost_bw_io, bw1)
            io_out = (tok1[:, None], bw1[:, None])
        else:
            devmask = (jax.lax.broadcasted_iota(i4, (G, n_ssd), 1)
                       == (io_rr % n_ssd)[:, None]) & park[:, None]
            svc, tok2d, bw2d = _update(
                now[:, None], devmask, io_tok, io_bw, inv_R, cost_bw_io)
            svc = svc[:, 0]
            io_out = (tok2d, bw2d)
            io_rr = io_rr + park
        lat_io = L_io
        if has_degrade:
            # Same submission-time rule as the loops: the row's current
            # time decides whether this IO pays the degraded latency.
            lat_io = jnp.where(now >= T_degrade, L_io * io_degrade, L_io)
        if has_jitter:
            lat_io = lat_io * (1.0 + jitter * (2.0 * u[next(un)] - 1.0))
        park_until = svc + lat_io + L_switch

        # -- issue the next suboperation's prefetch (P-deep window) ---------
        issue = kd[ni.astype(i4)][:, 0] == MEM
        # All P slots in flight <=> the window minimum is still in the
        # future, so the all-busy delay is just max(now, min slot); the
        # minimum slot is also the replacement target either way.
        if multicore:
            # The selected core's private window + bandwidth clock.
            slots_row = _read_row(pf_slots, cstar)           # (G, P)
            pf_bw = _read_row(cores, cstar)[:, 1]
        else:
            slots_row = pf_slots
            pf_bw = cf[:, 1]
        slot_min, slot = ring_min(slots_row)
        if has_arr:
            # Open loop: a not-yet-arrived op issues at its arrival clock
            # (post-T_lock now, exactly the loops' max(now, arrival)).
            t_iss = jnp.where(eoo, jnp.maximum(now, arr_next), now)
        else:
            t_iss = now
        pstart = jnp.maximum(t_iss, slot_min)
        if has_bmem:
            pstart = jnp.maximum(pstart, pf_bw)
            pf_bw = jnp.where(issue, pstart + cost_bmem, pf_bw)
        u_pf = u[next(un)] if has_rho else None
        comp = pstart + lmem(u_pf, L_mem_g)
        slots_row = _write_row(
            slots_row, slot,
            jnp.where(issue, comp, slot_min))
        pf_slots = (_write_row(pf_slots, cstar, slots_row) if multicore
                    else slots_row)
        pf_tid = jnp.where(issue, comp, pf_tid0)

        # -- yield: context switch, park or re-enter the ready ring ---------
        now = now + T_sw
        if has_arr:
            # Open loop: the freshly fetched op has not arrived yet --
            # park until the arrival clock.  Mutually exclusive with the
            # IO park (that one requires ~eoo).
            park_arr = eoo & (arr_next > now)
            parked_any = park | park_arr
            wake_val = jnp.where(park_arr, arr_next,
                                 jnp.where(park,
                                           jnp.maximum(park_until, now),
                                           jnp.inf))
        else:
            parked_any = park
            wake_val = jnp.where(park, jnp.maximum(park_until, now),
                                 jnp.inf)
        stamp = _write_row(stamp, tid, jnp.where(parked_any, BIG, ticket))
        wake = _write_row(wake, tid, wake_val)
        pft_cols = [pf_tid, span_next]
        if has_lat:
            pft_cols.append(op_start_new)
        pft = _write_row(pft, tid, jnp.stack(pft_cols, axis=1))

        crossed = (counted >= n_ops) & ~reached
        if multicore:
            cores = _write_row(cores, cstar,
                               jnp.stack([now, pf_bw], axis=1))
            # -- global drain horizon: the loop's cross-core wake-ups -------
            # The scalar loop drains the *shared* parked heap against the
            # global pop horizon, so when one core's clock jumps ahead
            # (e.g. a starved idle-skip), parked threads of *lagging* cores
            # enter their rings early -- and run below their own core's
            # clock, before their IO completion time.  ``cf[:, 0]`` carries
            # that horizon H (the running max of pop times); threads whose
            # wake fell at or below H while still above their core's clock
            # have their wake pulled down to that clock here, so they are
            # derived into the ring at the ring-tail position the loop's
            # append gives them.  The loop appends the drained thread
            # before the core's next pop, whose runner re-enters ticketed
            # at that same clock value: ``ring_min``'s woken-first tie
            # break keeps the drained thread ahead of it.  Threads whose
            # wake is at or below their own clock stay derived (key =
            # wake) as in the single-core path.
            H = jnp.maximum(cf[:, 0], pop_now)
            clock_t = jnp.broadcast_to(
                cores[:, :, 0][:, :, None], (G, C, Tpc)).reshape(G, T)
            early = (wake <= H[:, None]) & (wake > clock_t)
            wake = jnp.where(early, clock_t, wake)
            # The loop reports elapsed time against the *latest* core clock
            # at exit (``max(c.now for c in cores)``).
            t_end = jnp.where(crossed, jnp.max(cores[:, :, 0], axis=1),
                              cf[:, 4])
            pf_bw = cf[:, 1]   # cf slot 1 is unused with a core axis
            now = H            # cf slot 0 carries the drain horizon
        else:
            t_end = jnp.where(crossed, now, cf[:, 4])
        cf = jnp.stack([now, pf_bw, lock_next, t_start, t_end, mem_stall],
                       axis=1)
        ci_cols = [cursor, io_rr, done, counted, mem_acc, measuring]
        if has_lat:
            ci_cols.append(missed)
        ci = jnp.stack(ci_cols, axis=1)
        out = (cf, ci, stamp, wake, pft, pf_slots)
        if multicore:
            out = out + (cores,)
        out = out + io_out
        if has_lat:
            out = out + (lat_hist, latmax)
        return out

    return substep
