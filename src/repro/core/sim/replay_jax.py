"""Vectorized JAX replay: the whole latency x threads grid as one jitted call.

The loop backends (:mod:`.engine_loop`) re-run an interpreter per grid cell;
this module instead lowers the columnar :class:`~repro.core.trace_ir.
CompiledTrace` into device arrays **once** (:class:`TraceArrays`), expresses
one cell's scheduler recurrence as a ``jax.lax.scan`` over suboperation
executions, and batches that scan across every ``(L_mem, n_threads)`` cell
of a sweep, so an entire Fig. 9-style grid is a single compiled XLA program
(:func:`sweep_grid`).

The recurrence
--------------
One scan step executes exactly one suboperation of one thread in every grid
cell.  The step body itself lives in :mod:`repro.kernels.sched_step` (see
that module for the state layout and the step's one op form):

  * thread selection: ready threads carry a monotone FIFO *stamp* (their
    last pop time), so a lexicographic (stamp, tid) minimum pops the ring
    head;
  * wake drain: every parked thread whose IO completed re-joins the back
    of the ring in wake order in one masked pass -- the *exact* drain the
    loop backends perform, not a bounded-per-step approximation -- and
    the clock idle-skips to the earliest wake-up when nothing is
    runnable;
  * MEM stalls against the thread's outstanding prefetch (or a resampled
    latency on an eps-eviction), PREIO submits to the per-device token
    clocks (round-robin striping, jitter, switch hop), op completion pays
    ``T_lock``, and the next suboperation's prefetch is issued against the
    P-deep in-flight window -- all the device arithmetic of
    :mod:`.devices`, expressed on ``(n_cells, ...)`` arrays.

Cells that complete their measured ops latch their measurement (the
counters stop; the simulation harmlessly idles on) while the scan drains
the slower cells; the scan length is a worst-case bound computed from the
trace's op-length prefix sums, so no cell can run out of steps.  Grids
whose thread candidates span a wide range are split into thread
*buckets* so small-thread cells do not pay the widest cell's ``T_max``
padding where padding costs: power-of-two buckets on the CPU, 128-lane
tiles on the TPU (per-cell RNG purity makes the split invisible to
results).

Exactness
---------
Scheduling, device arithmetic, and draw *distributions* match the loop
backends; the RNG streams do not (``jax.random`` threefry vs. the stdlib
Mersenne twister), and simultaneous-ready ties can resolve in a different
order.  Per-cell throughput therefore agrees with the loop backends to
sampling noise rather than bit-identically: ~0.5% typical (tails ~1.5%)
at the default ``n_ops=5000``, shrinking as ``1/sqrt(n_ops)`` -- the 1%
per-cell bound on the paper's default grid is enforced at
``n_ops=20_000`` by ``tests/test_replay_jax.py``.  Scalar
latencies and single-core configs only; ``sweep_latency(backend="jax")``
routes mixture latencies through the loop backend per-cell.

Everything here is computed in float64 (``jax.enable_x64``): the state
mixes ~second-scale clocks with 50 ns context switches, which float32
cannot carry.  On TPU, XLA emulates float64 with float32's exponent range
and ~48 mantissa bits; the program therefore reads no float's bits (no
64-bit ``bitcast_convert_type``, which that emulation cannot rewrite) and
keeps every constant inside float32's range.
"""
from __future__ import annotations

import numbers
import os
import struct
import time
import zlib
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Sequence

import numpy as np

# Host-device sharding opt-in (process-global, so only entry points that
# own the process should set it, *before* jax initializes).  XLA presents
# the host as N virtual CPU devices; sweep_grid(host_devices=N) then
# shard_maps cohorts over them so the jax backend uses every host core
# the way the forked loop pipeline already does (CPU backend only).
_n_host = os.environ.get("REPRO_JAX_HOST_DEVICES", "")
if _n_host.isdigit() and int(_n_host) > 1:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags +
            f" --xla_force_host_platform_device_count={int(_n_host)}"
        ).strip()

import jax
import jax.numpy as jnp
from jax import enable_x64

from ..trace_ir import CPU, CompiledTrace
from .arrivals import HIST_BINS, LatencySummary, hist_bin_value
from .config import SimConfig, SimResult

__all__ = ["TraceArrays", "GridResult", "GridRecord", "CohortRecord",
           "sweep_grid", "lower_trace"]

_STEP_BUCKET = 4096     # scan lengths round up to this (compile-cache reuse)
_LANES = 128            # lanes of a TPU vector register (thread-slot axis)
# Lane-tiled cohorts stop merging at this many cells.  On one TPU v5e, with
# a step that scattered its thread-plane writes, a 128-cell step cost
# ~78 us, a 12-cell step ~27 us: little of the 128-cell step was fixed
# cost, and one 384-cell step (262 us) cost more than three 128-cell steps
# (235 us together).  Not re-measured for the one-hot step, whose 128-cell
# step costs ~17 us.
_MERGE_CELLS = 128
_PAD_SENTINEL = CPU     # padded suboperations are inert plain-CPU entries


def _bucket(n: int, bucket: int) -> int:
    return max(bucket, -(-n // bucket) * bucket)


@dataclass(frozen=True)
class TraceArrays:
    """A :class:`CompiledTrace` lowered to device arrays, shape-padded.

    ``kinds``/``durs`` are the flat suboperation columns; ``op_starts`` /
    ``op_ends`` are the per-op slice bounds (``bounds[:-1]``/``bounds[1:]``
    of the source trace).  Arrays are padded up to power-of-two-ish buckets
    so traces of similar size share one compiled sweep program; ``n_ops`` /
    ``n_subops`` are the true (pre-padding) counts, and the replay indexes
    ops modulo ``n_ops`` so padding is never executed.  ``to_trace``
    reconstructs the source trace losslessly (``tests/test_replay_jax.py``
    proves the round-trip for every registered engine).
    """

    kinds: jax.Array      # int32 (n_subops_padded,)
    durs: jax.Array       # float64 (n_subops_padded,)
    op_starts: jax.Array  # int32 (n_ops_padded,)
    op_ends: jax.Array    # int32 (n_ops_padded,)
    n_ops: int
    n_subops: int

    @classmethod
    def from_trace(cls, trace: CompiledTrace,
                   bucket: int = 1024) -> "TraceArrays":
        n_ops, n_subops = trace.n_ops, trace.n_subops
        kinds = np.full(_bucket(n_subops, bucket), _PAD_SENTINEL,
                        dtype=np.int32)
        kinds[:n_subops] = trace.kinds
        durs = np.zeros(len(kinds), dtype=np.float64)
        durs[:n_subops] = trace.durs
        n_ops_pad = _bucket(n_ops, bucket)
        starts = np.empty(n_ops_pad, dtype=np.int32)
        ends = np.empty(n_ops_pad, dtype=np.int32)
        starts[:n_ops] = trace.bounds[:-1]
        ends[:n_ops] = trace.bounds[1:]
        starts[n_ops:] = trace.bounds[-2]    # replicate the last op; the
        ends[n_ops:] = trace.bounds[-1]      # replay never reads past n_ops
        with enable_x64(True):
            return cls(jnp.asarray(kinds), jnp.asarray(durs),
                       jnp.asarray(starts), jnp.asarray(ends),
                       n_ops, n_subops)

    def to_trace(self) -> CompiledTrace:
        """Decode back to the exact source :class:`CompiledTrace`."""
        starts = np.asarray(self.op_starts)[: self.n_ops]
        ends = np.asarray(self.op_ends)[: self.n_ops]
        bounds = np.concatenate([starts, ends[-1:]]).astype(np.int64)
        return CompiledTrace.from_columns(
            np.asarray(self.kinds)[: self.n_subops].astype(np.int8),
            np.asarray(self.durs)[: self.n_subops],
            bounds,
        )


def lower_trace(trace: CompiledTrace, bucket: int = 1024) -> TraceArrays:
    """Functional alias for :meth:`TraceArrays.from_trace`."""
    return TraceArrays.from_trace(trace, bucket)


@dataclass(frozen=True)
class CohortRecord:
    """One cohort's compiled call, as :func:`sweep_grid` ran it.

    ``cells`` grid cells share a ``T_max``-wide thread plane (per core)
    and a scan compiled for ``steps_bound`` steps; ``thread_slots`` is
    the slots the cells use, the sum of ``n_cores * n_threads`` over
    them (the rest of the plane is padding).  ``steps_run`` is what the
    chunk loop executed before its early exit (the longest shard's under
    ``host_devices``), ``cell_steps_run`` its sum over the cohort's cells.
    The ``t_*`` fields are host-clock (:func:`time.perf_counter_ns`) edges
    of the cohort's three phases, each also a profiler span of the same
    name carrying ``cells``, ``T_max`` and ``steps_bound``:
    ``grid_dispatch`` (the call of the jitted program, to its return; on a
    first call it holds tracing, lowering and the compile or cache load),
    ``grid_wait`` (the host waiting on the device for the outputs)
    and ``grid_reduce`` (the copy to the host and the host reduction).
    """

    cells: int
    T_max: int
    thread_slots: int
    steps_bound: int
    steps_run: int
    cell_steps_run: int
    t_dispatch: int
    t_wait: int
    t_reduce: int
    t_done: int

    @property
    def dispatch_ns(self) -> int:
        return self.t_wait - self.t_dispatch

    @property
    def wait_ns(self) -> int:
        return self.t_reduce - self.t_wait

    @property
    def reduce_ns(self) -> int:
        return self.t_done - self.t_reduce


@dataclass(frozen=True)
class GridRecord:
    """What one :func:`sweep_grid` call did: the host-clock edges of its
    ``grid_lower`` phase (lowering the trace, partitioning the cohorts,
    uploading the arrival array; a profiler span too) and one
    :class:`CohortRecord` per cohort, in run order, whose planes are
    ``n_cores * T_max`` slots wide.  The call's step counters and
    ``slot_fill`` derive from the cohort records."""

    t_lower: int
    t_lowered: int
    n_cores: int
    cohorts: tuple[CohortRecord, ...]

    @property
    def lower_ns(self) -> int:
        return self.t_lowered - self.t_lower

    @property
    def steps(self) -> int:
        """Scan length bound (max across cohorts)."""
        return max((c.steps_bound for c in self.cohorts), default=0)

    @property
    def cell_steps_bound(self) -> int:
        """Sum over cells of their cohort's bound."""
        return sum(c.steps_bound * c.cells for c in self.cohorts)

    @property
    def cell_steps_run(self) -> int:
        """Sum over cells of executed steps."""
        return sum(c.cell_steps_run for c in self.cohorts)

    @property
    def slot_fill(self) -> float:
        """Share of the cohorts' thread planes that cells use: the sum of
        ``thread_slots`` over the sum of ``cells * T_max * n_cores``; the
        rest is padding that merging narrow cells into wide planes costs."""
        planes = sum(c.cells * c.T_max for c in self.cohorts) * self.n_cores
        return sum(c.thread_slots for c in self.cohorts) / planes


@dataclass(frozen=True)
class GridResult:
    """Per-cell sweep results, shaped ``(n_latencies, n_candidates)``.

    ``cell_steps_bound`` / ``cell_steps_run`` sum, over all cells, the
    scan steps their cohort *scheduled* (the per-cohort worst-case bound)
    vs. actually *executed* before the cohort's early exit fired -- the
    difference is the wasted work the early-exit scan no longer pays.
    They, and ``steps``, derive from ``record``'s per-cohort records.
    """

    throughput: np.ndarray
    time: np.ndarray
    mem_stall_total: np.ndarray
    mem_accesses: np.ndarray
    ops: int                      # measured ops per cell (same for all)
    record: GridRecord            # the call's phases and cohort counters
    # Tail-latency planes, present only when ``collect_percentiles`` was
    # on: histogram-derived percentiles (source="hist"; each within
    # arrivals.HIST_REL_ERROR of the exact value), the exact max, the
    # recorded count, and the deadline-missed count per cell.
    p50: np.ndarray | None = None
    p90: np.ndarray | None = None
    p99: np.ndarray | None = None
    lat_max: np.ndarray | None = None
    lat_count: np.ndarray | None = None
    missed: np.ndarray | None = None
    # Raw per-cell histogram counts, shaped (n_latencies, n_candidates,
    # HIST_BINS) -- cluster sweeps sum these planes across nodes to build
    # fleet-wide percentile summaries without re-running cells.
    lat_hist: np.ndarray | None = None

    @property
    def steps(self) -> int:
        return self.record.steps

    @property
    def cell_steps_bound(self) -> int:
        return self.record.cell_steps_bound

    @property
    def cell_steps_run(self) -> int:
        return self.record.cell_steps_run

    def result(self, li: int, ci: int) -> SimResult:
        """One cell as a :class:`SimResult` (no per-op latency columns --
        use the loop backends for those)."""
        summary = None
        missed = 0
        if self.p50 is not None:
            missed = int(self.missed[li, ci])
            summary = LatencySummary(
                count=int(self.lat_count[li, ci]),
                p50=float(self.p50[li, ci]),
                p90=float(self.p90[li, ci]),
                p99=float(self.p99[li, ci]),
                max=float(self.lat_max[li, ci]),
                missed=missed,
                source="hist",
            )
        return SimResult(
            ops=self.ops,
            time=float(self.time[li, ci]),
            throughput=float(self.throughput[li, ci]),
            mem_stall_total=float(self.mem_stall_total[li, ci]),
            mem_accesses=int(self.mem_accesses[li, ci]),
            missed_ops=missed,
            latency_summary=summary,
        )


def _max_window_subops(bounds: np.ndarray, n_window_ops: int) -> int:
    """Worst-case suboperation count of ``n_window_ops`` consecutive ops of
    the cyclic trace, over all start offsets (exact, via prefix sums)."""
    lens = np.diff(bounds)
    n = len(lens)
    total = int(lens.sum())
    cycles, rem = divmod(n_window_ops, n)
    worst_rem = 0
    if rem:
        cs = np.concatenate([[0], np.cumsum(np.concatenate([lens, lens]))])
        worst_rem = int((cs[rem: rem + n] - cs[:n]).max())
    return cycles * total + worst_rem


def _steps_bound(trace: CompiledTrace, n_ops: int, max_warmup: int,
                 max_threads: int) -> int:
    """Scan length guaranteeing every cell completes its measured ops.

    A cell terminates once ``warmup + n_ops - 1`` ops have completed; every
    executed suboperation belongs to an op issued from the shared cyclic
    cursor, and at most ``completions + n_threads`` ops are ever issued --
    a consecutive window whose suboperation count bounds the step count.
    """
    window = max_warmup + n_ops + max_threads
    return _bucket(_max_window_subops(trace.bounds, window), _STEP_BUCKET)


# -- the jitted grid ---------------------------------------------------------


def _make_flags(cfg: SimConfig) -> dict:
    """Static specialization flags (Python bools baked into the program)."""
    return dict(
        has_eps=cfg.eps > 0.0,
        has_rho=cfg.rho < 1.0,
        has_jitter=cfg.L_io_jitter > 0.0,
        has_rio=cfg.R_io > 0.0,
        has_bio=cfg.B_io > 0.0,
        has_bmem=cfg.B_mem > 0.0,
        has_lock=cfg.T_lock > 0.0,
        has_degrade=cfg.io_degrade != 1.0,
    )


_RNG_CHUNK = 1024   # steps per generated uniform block (memory/dispatch knob)


def _uniform(key, shape):
    """``jax.random.uniform(key, shape, float64)``, bit for bit, without
    its u64 -> f64 bitcast (which XLA:TPU's float64 emulation cannot
    rewrite): the top 52 of 64 random bits, scaled into [0, 1)."""
    bits = jax.random.bits(key, shape, jnp.uint64)
    return (bits >> 12).astype(jnp.float64) * 2.0 ** -52


def _grid_body(kinds, durs, op_starts, op_ends, n_trace,
               L_mem_g, nthr_g, warm_g, n_ops, dyn, key, stream_ids, arr, *,
               T_max, P, n_ssd, steps, unroll, early_exit, n_cores,
               has_eps, has_rho, has_jitter, has_rio, has_bio, has_bmem,
               has_lock, has_arr=False, has_lat=False, has_deadline=False,
               has_degrade=False):
    """The (unjitted) grid program; ``_run_grid`` jits it, the host-device
    sharding path wraps it in ``shard_map`` over the cell axis first."""
    from repro.kernels import sched_step as sk

    has_io_clock = has_rio or has_bio
    multicore = n_cores > 1
    f = jnp.float64
    i4 = jnp.int32
    G = L_mem_g.shape[0]
    CT = n_cores * T_max    # total thread slots (core-major when C > 1)

    rho, L_dram = dyn[2], dyn[3]

    def lmem(u, L):
        """sample_lmem for scalar latencies: DRAM-tier short-circuit."""
        if has_rho:
            return jnp.where(u >= rho, L_dram, L)
        return L

    # Packed trace columns: one gather serves (kind, dur) / (start, end);
    # op bounds are carried as exact f64 integers so a thread's (i, end)
    # pair packs into a single span scalar (see sched_step.pack_span).
    kd = jnp.stack([kinds.astype(f), durs], axis=1)          # (n_subops, 2)
    se = jnp.stack([op_starts.astype(f), op_ends.astype(f)], axis=1)

    # Uniform draws actually consumed per step, in consumption order (the
    # static flags decide): eps-eviction test + its resample, IO jitter,
    # the prefetch latency sample.  Draws are generated one _RNG_CHUNK of
    # steps at a time and fed to the inner scan as xs, so the step body
    # contains no hashing.
    n_u = 2 * has_eps + has_jitter + has_rho

    # -- per-cell RNG streams ------------------------------------------------
    # Every draw derives from fold_in(key, stream_id) where the stream id
    # hashes the cell's (L_mem, n_threads) identity -- NOT its position or
    # the batch size -- so a cell's numbers are identical whether it runs
    # alone, inside the full grid, as a thread bucket of a wider sweep, or
    # as the cache-miss remainder of a partially memoized sweep (the cell
    # cache requires cell values to be a pure function of their key).
    # Per-thread init draws fold in the thread index individually for the
    # same reason: they must not depend on the batch's T_max padding.
    cell_keys = jax.vmap(jax.random.fold_in, (None, 0))(key, stream_ids)
    k_chunks = jax.vmap(lambda k: jax.random.fold_in(k, 1))(cell_keys)
    tids = jnp.arange(CT, dtype=i4)
    t_local = tids % T_max                 # slot within the owning core
    active = t_local[None, :] < nthr_g[:, None]                # (G, CT)
    u_cursor = jax.vmap(lambda k: _uniform(
        jax.random.fold_in(k, 0), ()))(cell_keys)
    cursor0 = jnp.floor(u_cursor * n_trace).astype(i4)
    # Active threads consume consecutive cursor slots in core-major tid
    # order, like the loops' init (padding slots alias harmlessly: they
    # never execute).
    rank = (tids // T_max)[None, :] * nthr_g[:, None] + t_local[None, :]
    opidx0 = (cursor0[:, None] + rank) % n_trace
    cursor_init = (cursor0 + n_cores * nthr_g) % n_trace
    u_thread = jax.vmap(lambda k: jax.vmap(
        lambda t: _uniform(jax.random.fold_in(k, 2 + t), (2,)))(tids)
    )(cell_keys)                                                 # (G, CT, 2)
    pf0 = u_thread[:, :, 0] * lmem(u_thread[:, :, 1], L_mem_g[:, None])
    if has_arr:
        # Open loop: thread ``rank`` takes arrival index ``rank`` (the
        # loops' cid-major init order); its first prefetch is anchored at
        # the arrival, and a future arrival parks the thread on the wake
        # plane -- wake keys tie-break toward the lower tid, the loops'
        # heap-push order.  Inactive padding slots read a clamped arrival
        # but never run.
        arr0 = arr[jnp.minimum(rank, arr.shape[0] - 1)]          # (G, CT)
        pf0 = pf0 + arr0
        parked0 = active & (arr0 > 0.0)
    else:
        arr0 = None
        parked0 = jnp.zeros_like(active)

    # Initial state, in the sched_step layout: active threads populate the
    # ready ring in tid order (equal INIT_KEY stamps, which break toward
    # the lower tid), parked/inactive slots hold the BIG sentinel / +inf,
    # and the prefetch window starts empty (all slots free at time zero).
    span0 = sk.pack_span(op_starts[opidx0].astype(f),
                         op_ends[opidx0].astype(f))
    pf_shape = (G, n_cores, P) if multicore else (G, P)
    ci_cols = [cursor_init, jnp.zeros(G, i4), jnp.zeros(G, i4),
               jnp.zeros(G, i4), jnp.zeros(G, i4),
               (warm_g <= 0).astype(i4)]
    if has_lat:
        ci_cols.append(jnp.zeros(G, i4))           # missed-op counter
    pft_cols = [pf0, span0]
    if has_lat:
        pft_cols.append(arr0 if has_arr else jnp.zeros((G, CT), f))
    state = (
        jnp.zeros((G, 6), f).at[:, 3].set(-1.0),
        jnp.stack(ci_cols, axis=1),
        jnp.where(active & ~parked0, sk.INIT_KEY, sk.BIG),
        (jnp.where(parked0, arr0, jnp.inf) if has_arr
         else jnp.full((G, CT), jnp.inf, f)),
        jnp.stack(pft_cols, axis=2),
        jnp.zeros(pf_shape, f),
    )
    if multicore:
        state = state + (jnp.zeros((G, n_cores, 2), f),)
    if has_io_clock:
        state = state + (jnp.zeros((G, n_ssd), f), jnp.zeros((G, n_ssd), f))
    if has_lat:
        state = state + (jnp.zeros((G, HIST_BINS), f), jnp.zeros((G,), f))

    sub = sk.make_substep(
        n_u=n_u, n_ssd=n_ssd, has_eps=has_eps, has_rho=has_rho,
        has_jitter=has_jitter, has_rio=has_rio, has_bio=has_bio,
        has_bmem=has_bmem, has_lock=has_lock, has_arr=has_arr,
        has_lat=has_lat, has_deadline=has_deadline, has_degrade=has_degrade,
        n_cores=n_cores)

    def step(s, u):
        return sub(s, u, kd, se, arr, nthr_g, n_trace, L_mem_g,
                   warm_g, n_ops, dyn), None

    def chunk(s, ck):
        if n_u:
            us = jax.vmap(lambda k: _uniform(            # per cell
                jax.random.fold_in(k, ck), (_RNG_CHUNK, n_u)))(k_chunks)
            us = jnp.moveaxis(us, 0, -1)         # (G, CH, n_u) -> (CH, n_u, G)
        else:
            us = jnp.zeros((_RNG_CHUNK, 0, G), f)
        return jax.lax.scan(step, s, us, unroll=unroll)

    n_chunks = steps // _RNG_CHUNK
    if early_exit:
        # Stop scanning once every cell in the call latched its measured
        # ops: finished cells are inert (counters and t_start/t_end are
        # latched, the state only idles on), so cutting the tail chunks
        # cannot change any result -- it only stops paying for cells that
        # are already done.  The chunk counter ck rides in the carry, so
        # the uniform feed fold_in(k_chunks, ck) is identical to the
        # monolithic scan's; XLA keeps the while carry in donated buffers.
        def w_cond(carry):
            ck, s = carry
            return (ck < n_chunks) & ~jnp.all(s[1][:, 3] >= n_ops)

        def w_body(carry):
            ck, s = carry
            s2, _ = chunk(s, ck)
            return ck + jnp.int32(1), s2

        ck_end, state = jax.lax.while_loop(
            w_cond, w_body, (jnp.int32(0), state))
    else:
        state, _ = jax.lax.scan(
            chunk, state, jnp.arange(n_chunks, dtype=i4))
        ck_end = jnp.int32(n_chunks)
    cf, ci = state[0], state[1]
    elapsed = jnp.maximum(cf[:, 4] - cf[:, 3], 1e-12)
    out = dict(
        throughput=n_ops / elapsed,
        time=elapsed,
        mem_stall_total=cf[:, 5],
        mem_accesses=ci[:, 4],
        counted=ci[:, 3],
        # Per-cell so the host-sharded path can report each shard's own
        # early-exit point (shards stop independently, no collectives).
        steps_run=jnp.broadcast_to(ck_end * _RNG_CHUNK, (G,)),
    )
    if has_lat:
        out["lat_hist"] = state[-2]
        out["lat_max"] = state[-1]
        out["missed"] = ci[:, 6]
    return out


_STATIC_GRID_ARGS = (
    "T_max", "P", "n_ssd", "steps", "unroll", "early_exit", "n_cores",
    "has_eps", "has_rho", "has_jitter", "has_rio", "has_bio", "has_bmem",
    "has_lock", "has_arr", "has_lat", "has_deadline", "has_degrade")

_run_grid = partial(jax.jit, static_argnames=_STATIC_GRID_ARGS)(_grid_body)


@lru_cache(maxsize=64)
def _run_grid_sharded(n_dev: int, **static):
    """Jitted ``shard_map`` wrapper of :func:`_grid_body` splitting the cell
    axis over ``n_dev`` host CPU devices (the caller pads G to a multiple).
    Each shard runs -- and early-exits -- independently: there are no
    collectives in the grid program."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((n_dev,), ("cells",),
                         devices=jax.devices("cpu")[:n_dev])
    cells, repl = P("cells"), P()
    fn = jax.shard_map(
        partial(_grid_body, **static), mesh=mesh,
        in_specs=(repl, repl, repl, repl, repl,      # trace columns, n_trace
                  cells, cells, cells,               # L_mem_g, nthr_g, warm_g
                  repl, repl, repl, cells,           # n_ops, dyn, key, streams
                  repl),                             # arrival timestamps
        out_specs=cells,
        # every output is cell-sharded and nothing is replicated across
        # shards, so the varying-axes check buys nothing here
        check_vma=False,
    )
    return jax.jit(fn)


def _lane_tiled() -> bool:
    """Whether a plane's per-step cost follows its 128-lane tiles rather
    than its element count: true on the TPU, where the thread-slot axis is
    the vector lanes and a scan step pays a fixed cost for its string of
    small ops, whatever the plane's width up to one tile.  It decides the
    cohort partition (:func:`_thread_bucket`, :func:`_cohorts`)."""
    return jax.default_backend() == "tpu"


def _thread_bucket(n_threads: int, n_cores: int) -> int:
    """The thread bucket of a candidate; cells of one bucket may share a
    thread plane.  On the CPU plane work scales with elements, so the
    bucket is the power-of-two ceiling of ``n_threads`` (a 16-thread cell
    in a 128-wide plane would do 8x the work it needs).  Lane-tiled (see
    :func:`_lane_tiled`), it is the number of 128-lane tiles the cell's
    ``n_cores * n_threads`` slots fill: padding inside a tile is free,
    and each cohort pays the per-step fixed cost once more."""
    if _lane_tiled():
        return -(-n_cores * n_threads // _LANES)
    return 1 if n_threads <= 1 else 1 << (n_threads - 1).bit_length()


def _cohorts(source: CompiledTrace, candidates: Sequence[int], n_lat: int,
             n_ops: int, warmup_ops: int | None, n_cores: int,
             bucket_threads: bool) -> list[tuple[list[int], int, int]]:
    """Partition candidate columns (``n_lat`` cells each) into scan
    cohorts: ``(cols, T_max, steps)`` groups sharing a thread bucket (on
    the CPU, a step bound too).

    The thread buckets are :func:`_thread_bucket`'s, which the platform
    decides.  On the CPU, within a bucket, candidates whose per-cell
    worst-case bound lands in a different ``_STEP_BUCKET`` split into
    their own cohort, so a cohort's early exit is never held open by a
    cell with a structurally larger bound (uneven warmups are the common
    case: warmup defaults to ``2 * threads * cores``).  Lane-tiled, a
    step's fixed cost outweighs the steps a cell idles at the end, so a
    bucket's columns share cohorts whatever their bounds (a cohort's
    bound is its largest), and merge, narrowest first, only while the
    cohort holds fewer than ``_MERGE_CELLS`` cells: past that a step's
    fixed cost is small beside its per-cell cost, and a wider cohort
    costs more than the ones it would replace.  Per-cell RNG purity makes
    any partition result-invariant; ``bucket_threads=False`` collapses
    everything into the single monolithic scan (one ``T_max``, one global
    bound)."""
    if not bucket_threads:
        T_max = max(candidates)
        warm = (warmup_ops if warmup_ops is not None
                else 2 * T_max * n_cores)
        steps = _steps_bound(source, n_ops, warm, T_max * n_cores)
        return [(list(range(len(candidates))), T_max, steps)]
    tiled = _lane_tiled()
    groups: dict[tuple[int, int], list[int]] = {}
    bound = []
    for j, c in enumerate(candidates):
        warm = warmup_ops if warmup_ops is not None else 2 * c * n_cores
        bound.append(_steps_bound(source, n_ops, warm, c * n_cores))
        key = (_thread_bucket(c, n_cores), 0 if tiled else bound[j])
        groups.setdefault(key, []).append(j)
    cohorts = []
    for _, ix in sorted(groups.items()):
        parts = [ix]
        if tiled:
            parts = [[]]
            for j in sorted(ix, key=lambda j: candidates[j]):
                if len(parts[-1]) * n_lat >= _MERGE_CELLS:
                    parts.append([])
                parts[-1].append(j)
        cohorts += [(cols, max(candidates[j] for j in cols),
                     max(bound[j] for j in cols)) for cols in parts]
    return cohorts


def sweep_grid(
    cfg: SimConfig,
    trace: CompiledTrace | TraceArrays,
    latencies: Sequence[float],
    thread_candidates: Sequence[int],
    n_ops: int = 5000,
    warmup_ops: int | None = None,
    *,
    unroll: int = 2,
    bucket_threads: bool = True,
    early_exit: bool = True,
    host_devices: int | None = None,
    arrivals: Sequence[float] | None = None,
    collect_percentiles: bool = False,
    deadline: float = 0.0,
) -> GridResult:
    """Run the full ``latencies x thread_candidates`` grid in one compiled
    call per cohort; see the module docstring for semantics and exactness.

    ``cfg`` supplies everything except ``L_mem``/``n_threads`` (the grid
    axes); ``n_threads`` is *per core*, and ``cfg.n_cores > 1`` replays
    the multi-core scheduler (per-core rings + prefetch windows, shared
    T_lock / SSD clocks).  Scalar latencies only; ``warmup_ops`` defaults
    per cell to ``2 * n_threads * n_cores``, like the loop backends.

    ``unroll`` unrolls the scan to amortize per-step dispatch.
    ``bucket_threads=True`` groups the candidates into cohorts by thread
    bucket (power-of-two on the CPU, 128-lane tiles on the TPU; see
    :func:`_thread_bucket`) and step bound;
    ``bucket_threads=False`` forces the single monolithic layout (all
    candidates padded to one ``T_max``, one global step bound);
    ``early_exit=False`` additionally scans every cohort to its full
    bound -- together they reproduce the pre-cohort behavior exactly
    (per-cell RNG purity makes all four combinations bit-identical).

    ``host_devices=N > 1`` shard_maps each cohort's cell axis over N XLA
    host CPU devices (export ``REPRO_JAX_HOST_DEVICES=N`` -- or set
    ``--xla_force_host_platform_device_count`` -- *before* jax
    initializes); shards early-exit independently.  It raises unless the
    default backend is CPU, so it can never move an accelerator's grid
    onto the host.

    ``arrivals`` (a monotone timestamp sequence, seconds -- see
    :func:`repro.core.sim.arrivals.generate_arrivals`) switches every
    cell to the open-loop driver: the SAME array drives all cells (each
    consumes its own prefix), so it must cover the worst cell's demand
    ``n_cores * n_threads + warmup + n_ops``.  ``collect_percentiles``
    accumulates measured sojourns into a per-cell log-histogram (error
    bound ``arrivals.HIST_REL_ERROR`` per percentile; the max is exact)
    and fills the ``GridResult`` tail planes; ``deadline`` (seconds,
    0 = off) counts sojourns above it as missed instead of recording
    them.
    """
    if cfg.n_cores < 1:
        raise ValueError(f"n_cores must be >= 1, got {cfg.n_cores}")
    if cfg.collect_load_hist:
        raise ValueError(
            "per-load stall histograms are not available from the jax "
            "backend; use backend='loop'")
    if cfg.n_ssd < 1:
        raise ValueError(f"n_ssd must be >= 1, got {cfg.n_ssd}")
    latencies = list(latencies)
    candidates = [int(n) for n in thread_candidates]
    if not latencies or not candidates:
        raise ValueError("empty sweep grid")
    if not all(isinstance(L, numbers.Real) for L in latencies):
        raise ValueError(
            "the jax backend replays scalar latencies only; "
            "sweep_latency(backend='jax') routes mixture points through "
            "the loop backend")
    if min(candidates) < 1:
        raise ValueError(f"thread candidates must be >= 1: {candidates}")

    from repro.kernels.sched_step import SPAN_SHIFT

    n_lat, n_cand = len(latencies), len(candidates)
    backend = jax.default_backend()
    n_dev = 1 if host_devices is None else int(host_devices)
    if n_dev < 1:
        raise ValueError(f"host_devices must be >= 1, got {host_devices}")
    if n_dev > 1:
        if backend != "cpu":
            raise ValueError(
                f"host_devices={n_dev} shards the grid over host CPU "
                f"devices, but the default backend is {backend}: drop "
                "host_devices to run the grid on the accelerator")
        avail = len(jax.devices("cpu"))
        if n_dev > avail:
            raise ValueError(
                f"host_devices={n_dev} but jax sees {avail} host CPU "
                "device(s); export REPRO_JAX_HOST_DEVICES (or set "
                "--xla_force_host_platform_device_count) before jax "
                "initializes")

    has_arr = arrivals is not None
    has_lat = bool(collect_percentiles)
    has_deadline = has_lat and deadline > 0.0
    if deadline < 0.0:
        raise ValueError(f"deadline must be >= 0, got {deadline}")
    arr_np = np.zeros(1, dtype=np.float64)
    if has_arr:
        arr_np = np.asarray(arrivals, dtype=np.float64)
        if arr_np.ndim != 1 or arr_np.size == 0:
            raise ValueError("arrivals must be a non-empty 1-D sequence")
        need = max(
            cfg.n_cores * c
            + (warmup_ops if warmup_ops is not None else 2 * c * cfg.n_cores)
            + n_ops
            for c in candidates)
        if arr_np.size < need:
            raise ValueError(
                f"arrivals has {arr_np.size} timestamps but the widest "
                f"cell consumes up to {need} "
                "(n_cores * n_threads + warmup + n_ops)")

    dyn = (
        cfg.T_sw, cfg.eps, cfg.rho, cfg.L_dram, cfg.L_io, cfg.L_io_jitter,
        1.0 / cfg.R_io if cfg.R_io > 0.0 else 0.0,
        cfg.A_io / cfg.B_io if cfg.B_io > 0.0 else 0.0,
        cfg.L_switch,
        cfg.A_mem / cfg.B_mem if cfg.B_mem > 0.0 else 0.0,
        cfg.T_lock,
        deadline,
        cfg.T_degrade,
        cfg.io_degrade,
    )

    shape = (n_lat, n_cand)
    thr = np.empty(shape)
    tim = np.empty(shape)
    stall = np.empty(shape)
    macc = np.empty(shape, dtype=np.int64)
    if has_lat:
        p50 = np.empty(shape)
        p90 = np.empty(shape)
        p99 = np.empty(shape)
        lmax = np.empty(shape)
        lcount = np.empty(shape, dtype=np.int64)
        lmiss = np.empty(shape, dtype=np.int64)
        lhist = np.empty(shape + (HIST_BINS,), dtype=np.int64)
    records = []
    span = jax.profiler.TraceAnnotation
    # The draws come from threefry in its non-partitionable bit layout: the
    # stream every tolerance contract, test bound and conformance-corpus
    # entry was measured on (JAX 0.5 made the partitionable layout the
    # default; per-cell draws never span devices, so its sharding benefit
    # does not apply here).
    with enable_x64(True), jax.threefry_partitionable(False):
        t_lower = time.perf_counter_ns()
        with span("grid_lower"):
            source = (trace if isinstance(trace, CompiledTrace)
                      else trace.to_trace())
            ta = (trace if isinstance(trace, TraceArrays)
                  else lower_trace(trace))
            if int(ta.op_ends[-1]) >= (1 << SPAN_SHIFT):
                raise ValueError(
                    f"trace has {int(ta.op_ends[-1])} suboperations; the "
                    f"step's span packing supports < 2**{SPAN_SHIFT}")
            cohorts = _cohorts(source, candidates, n_lat, n_ops,
                               warmup_ops, cfg.n_cores, bucket_threads)
            arr = jnp.asarray(arr_np)
        t_lowered = time.perf_counter_ns()
        for cols, T_max, steps in cohorts:
            cand_b = [candidates[j] for j in cols]
            nc = len(cand_b)
            G = n_lat * nc
            L_mem_g = np.repeat(np.asarray(latencies, dtype=np.float64), nc)
            nthr_g = np.tile(np.asarray(cand_b, dtype=np.int32), n_lat)
            warm_g = (np.full_like(nthr_g, warmup_ops)
                      if warmup_ops is not None
                      else 2 * nthr_g * cfg.n_cores)

            # Each cell's RNG stream is keyed by its (L_mem, n_threads)
            # VALUES, so a cell's result never depends on which other
            # cells -- or cohorts -- share the call (cache purity; see the
            # per-cell RNG comment in _grid_body).
            stream_ids = np.array(
                [zlib.crc32(struct.pack("<dq", L, n))
                 for L in np.asarray(latencies, dtype=np.float64)
                 for n in cand_b],
                dtype=np.uint32,
            )
            pad = (-G) % n_dev
            if pad:
                # Pad the cell axis to the device count by repeating the
                # last cell: same stream id -> identical results, sliced
                # off below.
                L_mem_g, nthr_g, warm_g, stream_ids = (
                    np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
                    for a in (L_mem_g, nthr_g, warm_g, stream_ids))
            static = dict(
                T_max=T_max, P=cfg.P, n_ssd=cfg.n_ssd, steps=steps,
                unroll=unroll, early_exit=early_exit, n_cores=cfg.n_cores,
                has_arr=has_arr, has_lat=has_lat,
                has_deadline=has_deadline, **_make_flags(cfg),
            )
            args = dict(cells=G, T_max=T_max, steps_bound=steps)
            t_dispatch = time.perf_counter_ns()
            with span("grid_dispatch", **args):
                run = (_run_grid_sharded(n_dev, **static) if n_dev > 1
                       else partial(_run_grid, **static))
                out = run(
                    ta.kinds, ta.durs, ta.op_starts, ta.op_ends,
                    jnp.int32(ta.n_ops),
                    jnp.asarray(L_mem_g), jnp.asarray(nthr_g),
                    jnp.asarray(warm_g),
                    jnp.float64(n_ops),
                    tuple(jnp.float64(d) for d in dyn),
                    jax.random.PRNGKey(cfg.seed),
                    jnp.asarray(stream_ids),
                    arr,
                )
            t_wait = time.perf_counter_ns()
            with span("grid_wait", **args):
                jax.block_until_ready(out)
            t_reduce = time.perf_counter_ns()
            with span("grid_reduce", **args):
                out = {k: np.asarray(v)[:G] for k, v in out.items()}
                if not np.all(out["counted"] >= n_ops):
                    short = int(out["counted"].min())
                    raise RuntimeError(
                        f"jax replay under-ran its step bound ({steps} "
                        f"steps, worst cell counted {short}/{n_ops} ops) "
                        "-- this is a bug in _steps_bound")
                bshape = (n_lat, nc)
                thr[:, cols] = out["throughput"].reshape(bshape)
                tim[:, cols] = out["time"].reshape(bshape)
                stall[:, cols] = out["mem_stall_total"].reshape(bshape)
                macc[:, cols] = out["mem_accesses"].reshape(bshape)
                if has_lat:
                    # Host-side percentile reduction, vectorized over
                    # cells: nearest-rank on the cumulative counts,
                    # exactly arrivals.summarize_hist per row.
                    cum = np.cumsum(out["lat_hist"], axis=1)
                    total = np.rint(cum[:, -1]).astype(np.int64)
                    empty = total == 0
                    for q, dest in ((0.5, p50), (0.9, p90), (0.99, p99)):
                        rank = np.ceil(q * np.maximum(total, 1))
                        b = np.minimum((cum < rank[:, None]).sum(axis=1),
                                       HIST_BINS - 1)
                        dest[:, cols] = np.where(
                            empty, np.nan, hist_bin_value(b)).reshape(bshape)
                    lmax[:, cols] = np.where(
                        empty, np.nan, out["lat_max"]).reshape(bshape)
                    lcount[:, cols] = total.reshape(bshape)
                    lmiss[:, cols] = out["missed"].astype(
                        np.int64).reshape(bshape)
                    lhist[:, cols, :] = np.rint(out["lat_hist"]).astype(
                        np.int64).reshape(bshape + (HIST_BINS,))
            records.append(CohortRecord(
                cells=G, T_max=T_max,
                thread_slots=n_lat * cfg.n_cores * sum(cand_b),
                steps_bound=steps,
                steps_run=int(out["steps_run"].max()),
                cell_steps_run=int(out["steps_run"].sum()),
                t_dispatch=t_dispatch, t_wait=t_wait, t_reduce=t_reduce,
                t_done=time.perf_counter_ns()))
    return GridResult(
        throughput=thr,
        time=tim,
        mem_stall_total=stall,
        mem_accesses=macc,
        ops=n_ops,
        record=GridRecord(t_lower, t_lowered, cfg.n_cores, tuple(records)),
        p50=p50 if has_lat else None,
        p90=p90 if has_lat else None,
        p99=p99 if has_lat else None,
        lat_max=lmax if has_lat else None,
        lat_count=lcount if has_lat else None,
        missed=lmiss if has_lat else None,
        lat_hist=lhist if has_lat else None,
    )

