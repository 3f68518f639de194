"""The vectorized jax sweep backend (``repro.core.sim.replay_jax``).

Guarantees, strongest first:

  1. Trace lowering is *lossless*: ``CompiledTrace`` -> device arrays ->
     decoded trace round-trips exactly, for every registered engine's
     default-pairing trace and for arbitrary (hypothesis-generated) op
     lists.
  2. The step's pieces are exact: the one-hot row read returns each
     slot's value bit for bit whatever the other slots hold, the row
     write changes one slot, the token-clock grant gates as the loops do,
     and every partition of the grid into cohorts gives the same bits.
  3. Per-cell throughput is *tolerance-equivalent* to the loop backends:
     the jax grid reproduces the loops' scheduling and device arithmetic
     but draws from a different RNG stream (threefry vs. Mersenne), so
     cells agree to sampling noise -- within 1% on the paper's default
     grid once cells are long enough to average the noise out
     (``n_ops=20_000``; at the default 5000 expect up to ~1.5%).  See
     docs/SIMULATION.md "When is each backend exact?".
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import workloads
from repro.core.conformance import P50_TOL, P99_TOL, jax_grid_tol
from repro.core.engines import LSMStore, available_engines, run_trace
from repro.core.experiment import (
    RunOptions,
    build_engine,
    default_scenario,
    run_scenario,
)
from repro.core.sim import SimConfig, simulate_compiled, sweep_latency
from repro.core.sim import replay_jax, sweep as sweep_mod
from repro.core.sim.replay_jax import TraceArrays, lower_trace, sweep_grid
from repro.core.trace_ir import CPU, MEM, POSTIO, PREIO, CompiledTrace, Op
from repro.kernels import sched_step as sk

from _hypothesis_support import given, settings, st  # optional-hypothesis shim

US = 1e-6

ENGINES = sorted({cls.engine_name for cls in available_engines().values()})


@pytest.fixture(scope="module")
def lsm_small():
    store = LSMStore(30_000)
    wl = workloads.zipf(30_000, 10_000, 0.99, (1, 0), seed=3)
    return run_trace(store, wl)


def _grid_vs_loop(cfg, trace, lats, cands, n_ops):
    """Max per-cell |rel. diff| of the jax grid vs. the compiled loop
    (bit-identical to the generic loop, per tests/test_sweep.py)."""
    grid = sweep_grid(cfg, trace, lats, cands, n_ops=n_ops)
    worst = 0.0
    for li, L in enumerate(lats):
        for ci, n in enumerate(cands):
            ref = simulate_compiled(
                dataclasses.replace(cfg, L_mem=L, n_threads=n), trace, n_ops)
            worst = max(worst, abs(grid.throughput[li, ci] - ref.throughput)
                        / ref.throughput)
    return worst, grid


# -- 1. lossless trace lowering ----------------------------------------------


class TestLowering:
    @pytest.mark.parametrize("engine", ENGINES)
    def test_default_pairing_trace_round_trips(self, engine):
        store, wl = build_engine(engine, 20_000, 6_000)
        trace = run_trace(store, wl).trace
        back = lower_trace(trace).to_trace()
        assert np.array_equal(back.kinds, trace.kinds)
        assert np.array_equal(back.durs, trace.durs)       # float64, exact
        assert np.array_equal(back.bounds, trace.bounds)

    def test_padding_is_invisible(self, lsm_small):
        ta = lower_trace(lsm_small.trace, bucket=4096)
        assert len(ta.kinds) % 4096 == 0
        assert ta.n_subops == lsm_small.trace.n_subops
        assert ta.to_trace().counts() == lsm_small.trace.counts()

    def test_sweep_grid_accepts_prelowered_arrays(self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        ta = lower_trace(lsm_small.trace)
        a = sweep_grid(cfg, ta, [5 * US], [24], n_ops=1000)
        b = sweep_grid(cfg, lsm_small.trace, [5 * US], [24], n_ops=1000)
        assert a.throughput[0, 0] == b.throughput[0, 0]

    @settings(max_examples=25, deadline=None)
    @given(ops=st.lists(
        st.lists(
            st.tuples(st.sampled_from([MEM, PREIO, POSTIO, CPU]),
                      st.floats(0.0, 1e-5, allow_nan=False)),
            min_size=1, max_size=7),
        min_size=1, max_size=40))
    def test_round_trip_property(self, ops):
        trace = CompiledTrace.from_ops([Op(tuple(sub)) for sub in ops])
        back = TraceArrays.from_trace(trace, bucket=64).to_trace()
        assert np.array_equal(back.kinds, trace.kinds)
        assert np.array_equal(back.durs, trace.durs)
        assert np.array_equal(back.bounds, trace.bounds)


# -- 2. the step's pieces -----------------------------------------------------


class TestStepPieces:
    def test_no_jitter_deterministic_exact_match(self, lsm_small):
        # Every stochastic device feature off -> zero uniforms consumed
        # per step (the n_u=0 edge of the scan's uniform feed); the replay
        # is then a deterministic function of the trace, and must agree
        # exactly with itself across calls.
        cfg = SimConfig(P=12, seed=7, L_io_jitter=0.0)
        assert cfg.eps == 0.0 and cfg.rho == 1.0
        ref = sweep_grid(cfg, lsm_small.trace, [1 * US, 8 * US], [8, 16],
                         n_ops=200)
        again = sweep_grid(cfg, lsm_small.trace, [1 * US, 8 * US], [8, 16],
                           n_ops=200)
        for fld in ("throughput", "time", "mem_stall_total",
                    "mem_accesses"):
            assert np.array_equal(getattr(ref, fld), getattr(again, fld)), fld

    def test_kernel_unit_grant_semantics(self):
        submit = np.array([[10.0], [20.0], [30.0]])
        devmask = np.array([[True, False], [False, True], [False, False]])
        tok = np.array([[12.0, 0.0], [0.0, 19.0], [99.0, 99.0]])
        bw = np.zeros((3, 2))
        jnp = jax.numpy
        with jax.enable_x64(True):
            svc, tok2, bw2 = sk._update(
                jnp.asarray(submit), jnp.asarray(devmask), jnp.asarray(tok),
                jnp.asarray(bw), jnp.float64(0.5), jnp.float64(0.0))
        svc, tok2, bw2 = np.asarray(svc)[:, 0], np.asarray(tok2), \
            np.asarray(bw2)
        assert svc[0] == 12.0 and tok2[0, 0] == 12.5   # gated by clock
        assert svc[1] == 20.0 and tok2[1, 1] == 20.5   # clock behind
        assert svc[2] == 30.0                          # masked row:
        assert np.all(tok2[2] == 99.0)                 # clocks untouched
        assert np.all(tok2[0, 1:] == 0.0) and tok2[1, 0] == 0.0
        assert np.all(bw2 == 0.0)                      # disabled limit

    # What the slots around the read one hold: the wake plane's +inf, the
    # stamp plane's BIG and INIT_KEY sentinels, or all of them and times.
    ROW_FILLERS = {"inf": [np.inf], "big": [sk.BIG],
                   "init_key": [sk.INIT_KEY],
                   "mix": [np.inf, sk.BIG, sk.INIT_KEY, 3.25e-6, 0.0]}
    # What the read slot holds, one value a row: every sentinel, zero,
    # times with all 52 mantissa bits in use, and integers near 2**52.
    ROW_VALUES = [np.inf, sk.BIG, sk.INIT_KEY, 0.0, 1e-7 / 3, 0.1,
                  2.0 ** 52 - 1.0, 123456.789012345, 7.5e-5]

    @staticmethod
    def _rows(ndim, where, fill):
        G, T, K = len(TestStepPieces.ROW_VALUES), 7, 3
        tid = np.full(G, 0 if where == "first" else T - 1, np.int32)
        shape = (G, T) if ndim == 2 else (G, T, K)
        plane = np.resize(np.asarray(fill, np.float64),
                          int(np.prod(shape))).reshape(shape)
        vals = np.asarray(TestStepPieces.ROW_VALUES)
        if ndim == 3:
            vals = np.stack([vals, -vals, vals / 7.0], axis=1)
        plane[np.arange(G), tid] = vals
        return plane, tid, vals

    @pytest.mark.parametrize("where", ["first", "last"])
    @pytest.mark.parametrize("ndim", [2, 3])
    @pytest.mark.parametrize("filler", sorted(ROW_FILLERS))
    def test_read_row_exact(self, filler, ndim, where):
        """The one-hot read is a row max over ``-inf`` elsewhere: it
        returns the slot's value bit for bit, ``+inf`` and the
        sentinels included, whatever the other slots hold."""
        plane, tid, vals = self._rows(ndim, where,
                                      self.ROW_FILLERS[filler])
        with jax.enable_x64(True):
            got = np.asarray(sk._read_row(jax.numpy.asarray(plane),
                                          jax.numpy.asarray(tid)))
        assert got.dtype == np.float64
        assert np.array_equal(got, vals)
        assert np.array_equal(got.view(np.int64), vals.view(np.int64))

    @pytest.mark.parametrize("where", ["first", "last"])
    @pytest.mark.parametrize("ndim", [2, 3])
    def test_write_row_changes_one_slot(self, ndim, where):
        plane, tid, _ = self._rows(ndim, where,
                                   self.ROW_FILLERS["mix"])
        new = np.arange(1, plane.shape[0] + 1, dtype=np.float64) / 3.0
        if ndim == 3:
            new = np.stack([new, -new, new * 5.0], axis=1)
        want = plane.copy()
        want[np.arange(plane.shape[0]), tid] = new
        with jax.enable_x64(True):
            got = np.asarray(sk._write_row(jax.numpy.asarray(plane),
                                           jax.numpy.asarray(tid),
                                           jax.numpy.asarray(new)))
        assert np.array_equal(got, want)
        changed = (got != plane).reshape(plane.shape[0], plane.shape[1],
                                         -1).any(axis=2)
        assert np.array_equal(changed.sum(axis=1),
                              np.ones(plane.shape[0], np.int64))


# -- 3. tolerance equivalence against the loop backends ----------------------


class TestGridEquivalence:
    def test_small_grid_close_to_loop(self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        worst, _ = _grid_vs_loop(cfg, lsm_small.trace,
                                 [1 * US, 5 * US], [24, 48], n_ops=5000)
        assert worst < jax_grid_tol(5000), f"{worst:.2%}"

    FEATURES = [
        dict(eps=0.05),
        dict(rho=0.9),
        dict(T_lock=0.1 * US),
        dict(A_mem=64, B_mem=64 / (0.5 * US)),
        dict(R_io=250e3),
        dict(n_ssd=2, R_io=250e3, B_io=400e6, L_switch=0.3 * US),
    ]

    @pytest.mark.parametrize("kw", FEATURES,
                             ids=[",".join(k) for k in FEATURES])
    def test_device_features_close_to_loop(self, lsm_small, kw):
        cfg = SimConfig(P=12, seed=7, **kw)
        # non-default device features add small systematic offsets on top
        # of the contract's sampling-noise scaling, hence the 1.25x slack
        worst, _ = _grid_vs_loop(cfg, lsm_small.trace,
                                 [1 * US, 5 * US], [24, 48], n_ops=5000)
        assert worst < jax_grid_tol(5000, slack=1.25), f"{kw}: {worst:.2%}"

    @pytest.mark.slow
    @pytest.mark.parametrize("engine", ENGINES)
    def test_paper_default_grid_within_1pct_per_engine(self, engine):
        """The acceptance criterion: every cell of the paper's default
        latency x threads grid within 1% of the loop backend, for every
        registered engine, with the default matrix device config.  Cells
        run n_ops=20_000 so RNG-stream sampling noise (~0.5% at the
        default 5000) averages below the bound; the grid axes are the
        scenario defaults."""
        sc = default_scenario(engine, n_keys=30_000, n_wl_ops=9_000)
        store = available_engines()[engine](sc.n_keys, **sc.engine_kwargs)
        wname, wkw = sc.resolved_workload()
        wl = workloads.create_workload(wname, sc.n_keys, sc.n_wl_ops, **wkw)
        trace = run_trace(store, wl).trace
        cfg = sc.sim_config()
        worst, _ = _grid_vs_loop(
            cfg, trace, [l * US for l in sc.latencies_us],
            list(sc.thread_candidates), n_ops=20_000)
        assert worst < jax_grid_tol(20_000), \
            f"{engine}: worst cell {worst:.2%}"

    def test_cell_results_independent_of_grid_composition(self, lsm_small):
        """Cache purity: a cell's numbers are a function of its own
        identity (config, latency, thread count, trace, n_ops) -- never of
        which other cells happen to share the batched call.  This is what
        lets the cell cache serve jax cells across differently-shaped
        sweeps (a partially-cached sweep re-runs only the missing cells in
        a smaller grid)."""
        cfg = SimConfig(P=12, seed=7)
        alone = sweep_grid(cfg, lsm_small.trace, [5 * US], [8], n_ops=400)
        batched = sweep_grid(cfg, lsm_small.trace, [0.1 * US, 5 * US],
                             [8, 16], n_ops=400)
        assert alone.throughput[0, 0] == batched.throughput[1, 0]
        assert alone.mem_stall_total[0, 0] == batched.mem_stall_total[1, 0]

    def test_partially_cached_sweep_matches_cold_sweep(self, lsm_small,
                                                       tmp_path):
        cfg = SimConfig(P=12, seed=7)
        lats = [1 * US, 5 * US]
        cold = sweep_latency(cfg, lsm_small, lats, (8, 16), n_ops=400,
                             backend="jax")
        # warm the cache with only the first latency, then sweep both:
        # the second latency's cells run in a smaller grid than cold's
        sweep_latency(cfg, lsm_small, lats[:1], (8, 16), n_ops=400,
                      backend="jax", cache_dir=tmp_path)
        mixed = sweep_latency(cfg, lsm_small, lats, (8, 16), n_ops=400,
                              backend="jax", cache_dir=tmp_path)
        for a, b in zip(cold, mixed):
            assert a.result.throughput == b.result.throughput

    def test_mem_counters_track_loop(self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        grid = sweep_grid(cfg, lsm_small.trace, [5 * US], [24], n_ops=5000)
        ref = simulate_compiled(
            dataclasses.replace(cfg, L_mem=5 * US, n_threads=24),
            lsm_small.trace, 5000)
        assert grid.ops == ref.ops == 5000
        assert abs(grid.mem_accesses[0, 0] - ref.mem_accesses) \
            / ref.mem_accesses < 0.01
        assert abs(grid.mem_stall_total[0, 0] - ref.mem_stall_total) \
            / ref.mem_stall_total < 0.05

    MULTICORE = [
        dict(n_cores=2),
        dict(n_cores=4),
        dict(n_cores=2, T_lock=0.1 * US),
        dict(n_cores=4, T_lock=0.05 * US),
    ]

    @pytest.mark.parametrize(
        "kw", MULTICORE,
        ids=[f"c{d['n_cores']}" + ("+lock" if "T_lock" in d else "")
             for d in MULTICORE])
    def test_multicore_grid_close_to_loop(self, lsm_small, kw):
        """n_cores > 1 runs natively in the grid (no loop fallback): the
        per-core run queues, the shared parked heap's global drain
        horizon, and the lock serialization point all tolerance-track the
        compiled loop (which is bit-identical to the generic loop)."""
        cfg = SimConfig(P=12, seed=7, **kw)
        worst, _ = _grid_vs_loop(cfg, lsm_small.trace,
                                 [1 * US, 5 * US], [8, 16], n_ops=6000)
        assert worst < jax_grid_tol(6000, slack=1.1), f"{kw}: {worst:.2%}"


class TestRingOrder:
    """The derived ring pops threads in the loops' FIFO order.

    A one-op trace whose first suboperation is CPU makes the replay
    draw-free (the random start offset and initial prefetch phase are
    never read), and dyadic durations keep every sum exact, so threads
    become ready at *identical* instants -- a wake-up at the very time a
    runner re-enters the ring, simultaneous IO completions.  Any pop out
    of the loops' order then shows in the cell's exact virtual time."""

    U = 2.0 ** -26        # time unit: sums of multiples stay exact in f64
    CASES = [dict(), dict(R_io=2.0 ** 26 / 64), dict(T_lock=4 * U),
             dict(n_cores=2)]

    @pytest.mark.parametrize("kw", CASES,
                             ids=["plain", "iops", "lock", "cores2"])
    def test_pop_order_matches_loop_exactly(self, kw):
        U = self.U
        trace = CompiledTrace.from_ops([Op((
            (CPU, 16 * U), (MEM, 8 * U), (PREIO, 8 * U), (POSTIO, 8 * U),
            (MEM, 8 * U)))])
        cfg = SimConfig(P=2, seed=7, T_sw=4 * U, L_io=256 * U,
                        L_io_jitter=0.0, **kw)
        lats, cands = [64 * U, 512 * U], [1, 3, 8]
        grid = sweep_grid(cfg, trace, lats, cands, n_ops=300)
        for li, L in enumerate(lats):
            for ci, n in enumerate(cands):
                ref = simulate_compiled(
                    dataclasses.replace(cfg, L_mem=L, n_threads=n),
                    trace, 300)
                assert grid.time[li, ci] == ref.time, (L / U, n)
                assert grid.mem_stall_total[li, ci] == ref.mem_stall_total
                assert grid.mem_accesses[li, ci] == ref.mem_accesses


class TestBackendGuards:
    """Off the CPU backend, the grid refuses what would silently leave the
    accelerator (host-device sharding) -- before anything is traced."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        monkeypatch.setattr(replay_jax.jax, "default_backend",
                            lambda: "tpu")

    def test_host_devices_raise_off_cpu(self, lsm_small, on_tpu):
        with pytest.raises(ValueError, match="host CPU devices"):
            sweep_grid(SimConfig(), lsm_small.trace, [1 * US], [8],
                       host_devices=2)


# -- 3b. cohorts, early exit, host sharding ----------------------------------


def _het_grids(trace, cfg, n_ops=300):
    """The same heterogeneous cells through the cohort early-exit layout
    and the monolithic single-scan layout (PR 6's shape: every cell padded
    to T_max, scanned to the one global bound)."""
    lats = [0.5 * US, 5 * US]
    cands = [4, 8, 24]            # three pow2 buckets, uneven warmups
    coh = sweep_grid(cfg, trace, lats, cands, n_ops=n_ops)
    mono = sweep_grid(cfg, trace, lats, cands, n_ops=n_ops,
                      bucket_threads=False, early_exit=False)
    return coh, mono


class TestCohortEarlyExit:
    @pytest.mark.parametrize("engine", ENGINES)
    def test_cohorts_bit_identical_to_monolithic_per_engine(self, engine):
        """Cell purity is the whole contract: regrouping cells into
        cohorts and cutting the scan short at the all-done point may not
        change a single bit of any cell, for any engine's suboperation
        mix."""
        sc = default_scenario(engine, n_keys=2_000, n_wl_ops=600)
        store = available_engines()[engine](sc.n_keys, **sc.engine_kwargs)
        wname, wkw = sc.resolved_workload()
        wl = workloads.create_workload(wname, sc.n_keys, sc.n_wl_ops, **wkw)
        trace = run_trace(store, wl).trace
        coh, mono = _het_grids(trace, sc.sim_config())
        for fld in ("throughput", "time", "mem_stall_total",
                    "mem_accesses"):
            assert np.array_equal(getattr(coh, fld),
                                  getattr(mono, fld)), (engine, fld)

    def test_cohorts_bit_identical_with_devices_and_cores(self, lsm_small):
        # skew from the device axis too: multi-SSD token clocks and a
        # multi-core thread split exercise the widest per-cell state
        cfg = SimConfig(P=12, seed=7, n_ssd=2, R_io=250e3,
                        L_switch=0.3 * US, n_cores=2)
        coh, mono = _het_grids(lsm_small.trace, cfg)
        assert np.array_equal(coh.throughput, mono.throughput)

    def test_early_exit_skips_steps_on_uneven_grids(self, lsm_small):
        """The perf claim in counter form: on a heterogeneous grid the
        executed steps stay strictly below the scheduled worst-case
        bound, and the monolithic layout schedules at least as much."""
        cfg = SimConfig(P=12, seed=7)
        coh, mono = _het_grids(lsm_small.trace, cfg, n_ops=500)
        assert 0 < coh.cell_steps_run < coh.cell_steps_bound
        assert mono.cell_steps_bound >= coh.cell_steps_bound
        # early_exit=False runs every scheduled step
        assert mono.cell_steps_run == mono.cell_steps_bound

    @pytest.mark.parametrize("backend, cands, n_lat, n_cores, n_ops, want", [
        # one 128-lane tile holds every candidate of the paper's grid
        ("tpu", [16, 24, 32, 48, 64], 6, 1, 100, [([0, 1, 2, 3, 4], 64)]),
        # past 128 slots a second tile starts a second cohort
        ("tpu", [8, 16, 32, 64, 128, 256], 2, 1, 100,
         [([0, 1, 2, 3, 4], 128), ([5], 256)]),
        # slots count every core: 96 and 128 slots share one tile
        ("tpu", [48, 64], 6, 2, 100, [([0, 1], 64)]),
        # one tile: the 128-thread cell's bound lands a step bucket up,
        # and the cohort takes it
        ("tpu", [16, 64, 128], 6, 1, 3900, [([0, 1, 2], 128)]),
        ("tpu", [40, 64], 6, 1, 3970, [([0, 1], 64)]),
        # a cohort that holds 128 cells takes no more columns, narrowest
        # columns first
        ("tpu", [32, 8, 64, 16], 64, 1, 100,
         [([1, 3], 16), ([0, 2], 64)]),
        ("tpu", [8, 16, 32, 64], 128, 1, 100,
         [([0], 8), ([1], 16), ([2], 32), ([3], 64)]),
        # on the CPU, power-of-two buckets as before, whatever the cells
        ("cpu", [16, 24, 32, 48, 64], 6, 1, 100,
         [([0], 16), ([1, 2], 32), ([3, 4], 64)]),
        ("cpu", [32, 8, 64, 16], 128, 1, 100,
         [([1], 8), ([3], 16), ([0], 32), ([2], 64)]),
        # on the CPU one power-of-two bucket splits where the bounds
        # land in different step buckets
        ("cpu", [40, 64], 6, 1, 3970, [([0], 40), ([1], 64)]),
    ])
    def test_thread_buckets_follow_the_platform(self, monkeypatch, backend,
                                                cands, n_lat, n_cores, n_ops,
                                                want):
        """The partition ``_cohorts`` returns, by platform.  Every op of
        the trace is one suboperation, so a cell's step bound is its
        ``warmup + n_ops + slots`` op window rounded up to 4096."""
        monkeypatch.setattr(replay_jax.jax, "default_backend",
                            lambda: backend)
        n = 50
        flat = CompiledTrace.from_columns(
            np.full(n, CPU, dtype=np.int8), np.full(n, 1e-6),
            np.arange(n + 1, dtype=np.int64))
        got = replay_jax._cohorts(flat, cands, n_lat, n_ops, None, n_cores,
                                  True)
        assert [(cols, T_max) for cols, T_max, _ in got] == want
        for cols, T_max, steps in got:
            assert steps == replay_jax._steps_bound(
                flat, n_ops, 2 * T_max * n_cores, T_max * n_cores)

    def test_lane_tiled_grid_bit_identical(self, lsm_small, monkeypatch):
        """The lane-tiled partition, run on the CPU, merges the three
        power-of-two cohorts into one 24-wide plane and changes no bit;
        the records count the padding it costs."""
        cfg = SimConfig(P=12, seed=7)
        lats, cands = [0.5 * US, 5 * US], [4, 8, 24]
        pow2 = sweep_grid(cfg, lsm_small.trace, lats, cands, n_ops=500)
        monkeypatch.setattr(replay_jax, "_lane_tiled", lambda: True)
        lane = sweep_grid(cfg, lsm_small.trace, lats, cands, n_ops=500)
        for fld in ("throughput", "time", "mem_stall_total",
                    "mem_accesses"):
            assert np.array_equal(getattr(lane, fld), getattr(pow2, fld)), fld
        assert [(c.cells, c.T_max, c.thread_slots)
                for c in pow2.record.cohorts] == [(2, 4, 8), (2, 8, 16),
                                                  (2, 24, 48)]
        (c,) = lane.record.cohorts
        assert (c.cells, c.T_max, c.thread_slots) == (6, 24, 72)
        assert pow2.record.slot_fill == 1.0
        assert lane.record.slot_fill == 72 / 144

    def test_host_devices_validation(self, lsm_small):
        with pytest.raises(ValueError, match="host_devices"):
            sweep_grid(SimConfig(), lsm_small.trace, [1 * US], [8],
                       host_devices=0)
        import jax as _jax
        avail = len(_jax.devices("cpu"))
        with pytest.raises(ValueError, match="host CPU"):
            sweep_grid(SimConfig(), lsm_small.trace, [1 * US], [8],
                       host_devices=avail + 1)

    @pytest.mark.slow
    def test_sharded_grid_bit_identical_in_subprocess(self, lsm_small):
        """host_devices=N shard_maps the cell axis over N XLA host CPU
        devices; per-cell purity makes the sharded grid bit-identical to
        the unsharded one.  The device count is fixed at jax init, so the
        comparison runs in a subprocess with XLA_FLAGS forcing 2 host
        devices."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent("""
            import numpy as np
            from repro.core import workloads
            from repro.core.engines import LSMStore, run_trace
            from repro.core.sim import SimConfig
            from repro.core.sim.replay_jax import sweep_grid
            US = 1e-6
            tr = run_trace(LSMStore(4_000),
                           workloads.zipf(4_000, 1_500, 0.99, (1, 0),
                                          seed=3)).trace
            cfg = SimConfig(P=12, seed=7)
            lats, cands = [1 * US, 5 * US], [8, 16, 24]
            one = sweep_grid(cfg, tr, lats, cands, n_ops=300)
            two = sweep_grid(cfg, tr, lats, cands, n_ops=300,
                             host_devices=2)
            for fld in ("throughput", "time", "mem_stall_total",
                        "mem_accesses"):
                assert np.array_equal(getattr(one, fld),
                                      getattr(two, fld)), fld
            print("SHARDED_OK")
        """)
        env = dict(os.environ,
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=2"
                              ).strip(),
                   PYTHONPATH=os.pathsep.join(
                       filter(None, [os.path.join(os.path.dirname(__file__),
                                                  os.pardir, "src"),
                              os.environ.get("PYTHONPATH", "")])))
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED_OK" in out.stdout


class TestCohortRecords:
    """Each sweep_grid call keeps one record per cohort: its shape, the
    scan steps it ran, and the host clock at the edges of its phases; the
    grid's step counters derive from those records."""

    @pytest.mark.parametrize("n_ops, cohorts_counts, monolithic_counts", [
        (300, (8192, 32768, 26624), (8192, 49152, 49152)),
        (500, (8192, 49152, 38912), (8192, 49152, 49152)),
    ])
    def test_derived_counters_keep_their_values(self, lsm_small, n_ops,
                                                cohorts_counts,
                                                monolithic_counts):
        """``(steps, cell_steps_bound, cell_steps_run)`` read what the
        three accumulators gave before they derived from the records, on
        _het_grids' cells in both layouts."""
        grids = _het_grids(lsm_small.trace, SimConfig(P=12, seed=7),
                           n_ops=n_ops)
        for g, counts in zip(grids, (cohorts_counts, monolithic_counts)):
            assert (g.steps, g.cell_steps_bound, g.cell_steps_run) == counts
            cohorts = g.record.cohorts
            assert g.steps == max(c.steps_bound for c in cohorts)
            assert g.cell_steps_bound == sum(c.steps_bound * c.cells
                                             for c in cohorts)
            assert g.cell_steps_run == sum(c.cell_steps_run
                                           for c in cohorts)

    def test_records_cover_the_grid_in_run_order(self, lsm_small):
        coh, mono = _het_grids(lsm_small.trace, SimConfig(P=12, seed=7))
        assert len(mono.record.cohorts) == 1
        assert len(coh.record.cohorts) > 1
        for g in (coh, mono):
            rec = g.record
            assert sum(c.cells for c in rec.cohorts) == g.throughput.size
            t = rec.t_lower
            assert rec.t_lowered >= t and rec.lower_ns >= 0
            t = rec.t_lowered
            for c in rec.cohorts:
                edges = (c.t_dispatch, c.t_wait, c.t_reduce, c.t_done)
                assert t <= edges[0] and list(edges) == sorted(edges)
                assert (c.dispatch_ns + c.wait_ns + c.reduce_ns
                        == c.t_done - c.t_dispatch)
                t = c.t_done

    def test_steps_run_whole_chunks_within_bound(self, lsm_small):
        coh, mono = _het_grids(lsm_small.trace, SimConfig(P=12, seed=7))
        for c in coh.record.cohorts + mono.record.cohorts:
            assert c.steps_run % replay_jax._RNG_CHUNK == 0
            assert 0 < c.steps_run <= c.steps_bound
            # one device: every cell of a cohort runs the same steps
            assert c.cell_steps_run == c.steps_run * c.cells
        # early_exit=False scans the monolithic cohort to its bound
        (m,) = mono.record.cohorts
        assert m.steps_run == m.steps_bound

    def test_profiler_trace_holds_the_spans(self, lsm_small, tmp_path):
        """The spans land in the profiler's trace: one grid_lower per
        call, one grid_dispatch / grid_wait / grid_reduce per cohort, each
        carrying its cohort's cells, T_max and steps_bound."""
        import glob
        import os

        from jax.profiler import ProfileData

        cfg = SimConfig(P=12, seed=7)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            g = sweep_grid(cfg, lsm_small.trace, [0.5 * US, 5 * US],
                           [4, 8, 24], n_ops=300)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins",
                                         "profile", "*", "*.xplane.pb"))
        names = ("grid_lower", "grid_dispatch", "grid_wait", "grid_reduce")
        found = {n: [] for n in names}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in found:
                        found[e.name].append(
                            (e.start_ns, dict(e.stats)))
        cohorts = g.record.cohorts
        assert len(found["grid_lower"]) == 1
        want = [(c.cells, c.T_max, c.steps_bound) for c in cohorts]
        for n in names[1:]:
            got = [(a["cells"], a["T_max"], a["steps_bound"])
                   for _, a in sorted(found[n], key=lambda x: x[0])]
            assert got == want, n


# -- 4. validation and API contracts -----------------------------------------


class TestValidation:
    def test_rejects_multicore_mixtures_and_empty(self, lsm_small):
        with pytest.raises(ValueError, match="n_cores"):
            sweep_grid(SimConfig(n_cores=0), lsm_small.trace, [1 * US], [8])
        with pytest.raises(ValueError, match="scalar latencies"):
            sweep_grid(SimConfig(), lsm_small.trace,
                       [[(5 * US, 1.0)]], [8])
        with pytest.raises(ValueError, match="histograms"):
            sweep_grid(SimConfig(collect_load_hist=True),
                       lsm_small.trace, [1 * US], [8])
        with pytest.raises(ValueError, match="empty"):
            sweep_grid(SimConfig(), lsm_small.trace, [], [8])

    def test_sweep_latency_backend_validation(self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        with pytest.raises(ValueError, match="backend must be one of"):
            sweep_latency(cfg, lsm_small, [1 * US], (8,), backend="numpy")
        with pytest.raises(ValueError, match="adaptive"):
            sweep_latency(cfg, lsm_small, [1 * US], (8,), backend="jax",
                          adaptive=True)
        with pytest.raises(ValueError, match="collection"):
            sweep_latency(cfg, lsm_small, [1 * US], (8,), backend="jax",
                          collect_latency=True)
        with pytest.raises(ValueError, match="callable"):
            sweep_latency(cfg, lambda rng: None, [1 * US], (8,),
                          backend="jax")


# -- 5. sweep_latency / experiment integration -------------------------------


class TestSweepIntegration:
    def test_jax_backend_returns_equivalent_points(self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        lats = [1 * US, 5 * US]
        loop = sweep_latency(cfg, lsm_small, lats, (24, 48), n_ops=5000,
                             processes=1)
        jaxp = sweep_latency(cfg, lsm_small, lats, (24, 48), n_ops=5000,
                             backend="jax")
        for a, b in zip(loop, jaxp):
            for n, thr in a.per_thread.items():
                assert abs(b.per_thread[n] - thr) / thr < 0.02
            assert b.result.ops == a.result.ops

    def test_mixture_points_fall_back_to_loop_bit_identically(
            self, lsm_small):
        cfg = SimConfig(P=12, seed=7)
        mix = [(5 * US, 0.9), (14 * US, 0.1)]
        (la, lb) = sweep_latency(cfg, lsm_small, [mix, 1 * US], (24,),
                                 n_ops=5000, processes=1)
        (ja, jb) = sweep_latency(cfg, lsm_small, [mix, 1 * US], (24,),
                                 n_ops=5000, backend="jax")
        assert ja.result.throughput == la.result.throughput   # loop-run cell
        assert jb.result.throughput != lb.result.throughput   # jax-run cell
        assert abs(jb.result.throughput - lb.result.throughput) \
            / lb.result.throughput < 0.02

    def test_loop_workers_never_import_jax(self):
        """Mixture cells on backend="jax" run in loop workers forked from
        a forkserver that preloads ``repro.core.sim.sweep``.  Neither that
        preload nor a worker's task may import jax: on an accelerator
        machine a worker that initialized a backend would contend for the
        chip with its parent."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent("""
            import pickle, sys
            import repro.core.sim.sweep as sweep
            from repro.core import workloads
            from repro.core.engines import LSMStore, run_trace
            from repro.core.sim import SimConfig
            tr = pickle.loads(pickle.dumps(run_trace(
                LSMStore(2_000),
                workloads.zipf(2_000, 600, 0.99, (1, 0), seed=3)).trace))
            sweep._worker_init(tr, None, 200, None, False)
            sweep._worker_run(SimConfig(P=12, seed=7, n_threads=8,
                                        L_mem=[(5e-6, 0.9), (14e-6, 0.1)]))
            assert "jax" not in sys.modules, "a loop worker imported jax"
            print("NO_JAX")
        """)
        src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            filter(None, [src, os.environ.get("PYTHONPATH", "")])))
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "NO_JAX" in out.stdout

    def test_experiment_runs_with_jax_backend(self):
        sc = default_scenario("hash-index", n_keys=8_000, n_wl_ops=3_000,
                              latencies_us=(0.1, 5), n_ops=1500,
                              thread_candidates=(16, 24))
        art_loop = run_scenario(sc)
        art_jax = run_scenario(sc, RunOptions(backend="jax"))
        assert art_jax.scenario == art_loop.scenario     # spec unchanged
        assert art_jax.S == art_loop.S                   # same trace
        for rl, rj in zip(art_loop.rows, art_jax.rows):
            assert abs(rj.throughput - rl.throughput) / rl.throughput < 0.03


# -- 6. the salted, backend-keyed cell cache ---------------------------------


class TestSweepCellCache:
    def test_backends_never_share_cells(self, lsm_small, tmp_path):
        cfg = SimConfig(P=12, seed=7)
        sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=1000,
                      processes=1, cache_dir=tmp_path)
        n_loop = len(list(tmp_path.glob("*.json")))
        jax1 = sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=1000,
                             cache_dir=tmp_path, backend="jax")
        assert len(list(tmp_path.glob("*.json"))) == 2 * n_loop
        # and a second jax sweep is served from its own cells
        jax2 = sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=1000,
                             cache_dir=tmp_path, backend="jax")
        assert jax2[0].result.throughput == jax1[0].result.throughput
        assert len(list(tmp_path.glob("*.json"))) == 2 * n_loop

    def test_code_salt_invalidates_cells(self, lsm_small, tmp_path,
                                         monkeypatch):
        """The ROADMAP regression: cells cached by an older revision of the
        simulator must not be served after the code changes."""
        cfg = SimConfig(P=12, seed=7)
        sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=1000,
                      processes=1, cache_dir=tmp_path)
        before = len(list(tmp_path.glob("*.json")))
        monkeypatch.setattr(sweep_mod, "_CODE_SALT", "pretend-new-code")
        sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=1000,
                      processes=1, cache_dir=tmp_path)
        after = len(list(tmp_path.glob("*.json")))
        assert after == 2 * before, "stale cells were served across code versions"

    def test_salt_is_derived_from_sources(self):
        salt = sweep_mod._code_salt()
        assert isinstance(salt, str) and len(salt) == 16
        assert salt == sweep_mod._code_salt()   # stable within a process

    def test_clear_sweep_cache(self, lsm_small, tmp_path):
        from repro.core.sim import clear_sweep_cache

        cfg = SimConfig(P=12, seed=7)
        sweep_latency(cfg, lsm_small, [1 * US, 5 * US], (24,), n_ops=800,
                      processes=1, cache_dir=tmp_path)
        n = len(list(tmp_path.glob("*.json")))
        assert n == 2
        # non-cell files sharing the directory are not ours to delete
        spec = tmp_path / "spec.json"
        spec.write_text("{}")
        art = tmp_path / "deadbeef.json"   # json, but not a sha1 cell name
        art.write_text("{}")
        assert clear_sweep_cache(tmp_path) == n
        assert spec.exists() and art.exists()
        assert sorted(p.name for p in tmp_path.glob("*.json")) == [
            "deadbeef.json", "spec.json"]
        assert clear_sweep_cache(tmp_path) == 0
        assert clear_sweep_cache(tmp_path / "nonexistent") == 0

    def test_cli_sweep_cache_clear(self, tmp_path, capsys, monkeypatch):
        import benchmarks.run as run_mod

        stale = tmp_path / ("ab" * 20 + ".json")   # a cell-shaped name
        stale.write_text("{}")
        keep = tmp_path / "spec.json"
        keep.write_text("{}")
        monkeypatch.setattr("sys.argv", [
            "benchmarks.run", "--only", "no_such_bench",
            "--sweep-cache", str(tmp_path), "--sweep-cache-clear"])
        run_mod.main()
        assert not stale.exists()
        assert keep.exists()
        assert "cleared 1 cell(s)" in capsys.readouterr().err

    def test_cli_clear_without_cache_dir_exits(self, capsys, monkeypatch):
        import benchmarks.run as run_mod

        monkeypatch.setattr("sys.argv",
                            ["benchmarks.run", "--sweep-cache-clear"])
        with pytest.raises(SystemExit, match="requires --sweep-cache"):
            run_mod.main()


class TestSweepCachePrune:
    """LRU-by-mtime eviction (``prune_sweep_cache``): cache hits refresh a
    cell's mtime, pruning removes the least-recently-used cells first."""

    @staticmethod
    def _cell(tmp_path, tag: str, size: int, age_s: float):
        """A cell-shaped file of ``size`` bytes last used ``age_s`` ago."""
        import hashlib
        import os
        import time

        name = hashlib.sha1(tag.encode()).hexdigest() + ".json"
        p = tmp_path / name
        p.write_text("x" * size)
        t = time.time() - age_s
        os.utime(p, (t, t))
        return p

    def test_prune_by_size_evicts_oldest_first(self, tmp_path):
        from repro.core.sim import prune_sweep_cache

        old = self._cell(tmp_path, "old", 100, age_s=300)
        mid = self._cell(tmp_path, "mid", 100, age_s=200)
        new = self._cell(tmp_path, "new", 100, age_s=100)
        assert prune_sweep_cache(tmp_path, max_bytes=150) == 2
        assert not old.exists() and not mid.exists()
        assert new.exists()

    def test_prune_by_age(self, tmp_path):
        from repro.core.sim import prune_sweep_cache

        stale = self._cell(tmp_path, "stale", 10, age_s=10 * 86400)
        fresh = self._cell(tmp_path, "fresh", 10, age_s=1 * 86400)
        assert prune_sweep_cache(tmp_path, max_age_days=5) == 1
        assert not stale.exists() and fresh.exists()

    def test_prune_leaves_fitting_caches_alone(self, tmp_path):
        from repro.core.sim import prune_sweep_cache

        kept = self._cell(tmp_path, "kept", 50, age_s=500)
        foreign = tmp_path / "spec.json"    # not a cell: never touched
        foreign.write_text("x" * 10_000)
        assert prune_sweep_cache(tmp_path, max_bytes=100,
                                 max_age_days=30) == 0
        assert kept.exists() and foreign.exists()
        assert prune_sweep_cache(tmp_path / "nonexistent",
                                 max_bytes=0) == 0

    def test_prune_validates_args(self, tmp_path):
        from repro.core.sim import prune_sweep_cache

        with pytest.raises(ValueError, match="max_bytes"):
            prune_sweep_cache(tmp_path, max_bytes=-1)
        with pytest.raises(ValueError, match="max_age_days"):
            prune_sweep_cache(tmp_path, max_age_days=-0.5)

    def test_cache_hit_refreshes_mtime(self, lsm_small, tmp_path):
        """A served cell is recently-used: ``_cache_load`` bumps its mtime
        so a later prune evicts cold cells before hot ones."""
        import os
        import time

        cfg = SimConfig(P=12, seed=7)
        sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=600,
                      processes=1, cache_dir=tmp_path)
        (cell,) = tmp_path.glob("*.json")
        past = time.time() - 9 * 86400
        os.utime(cell, (past, past))
        sweep_latency(cfg, lsm_small, [1 * US], (24,), n_ops=600,
                      processes=1, cache_dir=tmp_path)   # pure cache hit
        assert os.path.getmtime(cell) > past + 86400

    def test_cli_sweep_cache_prune(self, tmp_path, capsys, monkeypatch):
        import benchmarks.run as run_mod

        self._cell(tmp_path, "a", 100, age_s=300)
        survivor = self._cell(tmp_path, "b", 100, age_s=100)
        monkeypatch.setattr("sys.argv", [
            "benchmarks.run", "--only", "no_such_bench",
            "--sweep-cache", str(tmp_path),
            "--sweep-cache-prune", "0.0001"])    # 100-byte budget
        run_mod.main()
        assert "pruned 1 cell(s)" in capsys.readouterr().err
        assert list(tmp_path.glob("*.json")) == [survivor]

    def test_cli_prune_without_cache_dir_exits(self, monkeypatch):
        import benchmarks.run as run_mod

        monkeypatch.setattr("sys.argv", [
            "benchmarks.run", "--sweep-cache-prune-days", "7"])
        with pytest.raises(SystemExit, match="requires --sweep-cache"):
            run_mod.main()


# -- 8. open-loop arrivals + tail percentiles --------------------------------
#
# The other half of the cross-backend tail matrix (the generic-vs-compiled
# bit-identity half lives in tests/test_arrivals.py).  The jax grid shares
# the loops' arrival array but draws service latencies from a different
# RNG stream and reports quantiles as log-histogram bin midpoints, so the
# contract is tolerance equivalence: HIST_REL_ERROR (< 1.9%) of binning
# error plus cross-stream sampling noise.  Measured worst cases at
# n_ops=400 on these configs: P50 within 3.4%, P99 within 6.2%; the
# asserted bounds (P50_TOL/P99_TOL, 8% / 12%, imported from the contract
# table in repro.core.conformance) carry margin over that.

from repro.core.sim import (  # noqa: E402
    HIST_REL_ERROR,
    ArrivalSpec,
    generate_arrivals,
)

ARR_SPECS = {
    "poisson": ArrivalSpec(kind="poisson", rate=150e3, seed=5),
    "bursty": ArrivalSpec(kind="bursty", rate=150e3, seed=5,
                          on_fraction=0.3, period=0.002),
}


def _arrival_array(spec, cfg, cands, n_ops):
    need = max(3 * cfg.n_cores * c for c in cands) + n_ops + 1
    return generate_arrivals(spec, need)


@pytest.fixture(scope="module")
def hash_small():
    store = available_engines()["hash-index"](4_000)
    wl = workloads.zipf(4_000, 1_500, 0.99, (1, 0), seed=3)
    return run_trace(store, wl)


class TestOpenLoopGrid:
    LATS = [1 * US, 5 * US]
    CANDS = [8, 16]
    N_OPS = 400

    def _grid(self, cfg, trace, arr):
        return sweep_grid(cfg, trace, self.LATS, self.CANDS,
                          n_ops=self.N_OPS, arrivals=arr,
                          collect_percentiles=True)

    @pytest.mark.parametrize("mode", sorted(ARR_SPECS))
    def test_grid_tail_close_to_compiled_loop(self, hash_small, mode):
        cfg = SimConfig(P=12, seed=7)
        arr = _arrival_array(ARR_SPECS[mode], cfg, self.CANDS, self.N_OPS)
        grid = self._grid(cfg, hash_small.trace, arr)
        for li, L in enumerate(self.LATS):
            for ci, n in enumerate(self.CANDS):
                ref = simulate_compiled(
                    dataclasses.replace(cfg, L_mem=L, n_threads=n),
                    hash_small.trace, self.N_OPS, arrivals=arr,
                    collect_percentiles=True)
                g = grid.result(li, ci)
                assert g.throughput == pytest.approx(
                    ref.throughput, rel=0.02)
                gs, rs = g.latency_summary, ref.latency_summary
                assert gs.source == "hist" and rs.source == "exact"
                assert gs.count == rs.count == self.N_OPS
                assert gs.p50 == pytest.approx(rs.p50, rel=P50_TOL)
                assert gs.p99 == pytest.approx(rs.p99, rel=P99_TOL)

    @pytest.mark.parametrize("engine", ENGINES)
    def test_per_engine_poisson_tail(self, engine):
        # Load-normalized (60% of the engine's own capacity) so every
        # engine sits at the same utilization regardless of service time.
        store, wl = build_engine(engine, 4_000, 1_200)
        tr = run_trace(store, wl)
        cfg = SimConfig(P=12, seed=7)
        cell = dataclasses.replace(cfg, L_mem=3 * US, n_threads=16)
        cap = simulate_compiled(cell, tr.trace, self.N_OPS).throughput
        spec = ArrivalSpec(rate=0.6 * cap, seed=5)
        arr = _arrival_array(spec, cfg, [16], self.N_OPS)
        grid = sweep_grid(cfg, tr.trace, [3 * US], [16], n_ops=self.N_OPS,
                          arrivals=arr, collect_percentiles=True)
        ref = simulate_compiled(cell, tr.trace, self.N_OPS, arrivals=arr,
                                collect_percentiles=True)
        gs, rs = grid.result(0, 0).latency_summary, ref.latency_summary
        assert gs.p90 == pytest.approx(rs.p90, rel=P99_TOL)
        assert gs.p99 == pytest.approx(rs.p99, rel=P99_TOL)
        # Nearest-rank P50 is only comparable when the median is not on
        # a distributional cliff: two-tier-cache splits sojourns into a
        # DRAM-hit mode and a miss mode with ~half the mass each, so the
        # two backends' medians can legally land on opposite sides of
        # the gap (P90/P99 agree to ~3% there).  Gate on the spread.
        if rs.p90 < 1.5 * rs.p50:
            assert gs.p50 == pytest.approx(rs.p50, rel=P50_TOL)

    def test_closed_loop_percentiles_leave_throughput_identical(
            self, hash_small):
        cfg = SimConfig(P=12, seed=7)
        plain = sweep_grid(cfg, hash_small.trace, self.LATS, self.CANDS,
                           n_ops=self.N_OPS)
        with_p = sweep_grid(cfg, hash_small.trace, self.LATS, self.CANDS,
                            n_ops=self.N_OPS, collect_percentiles=True)
        assert np.array_equal(plain.throughput, with_p.throughput)
        s = with_p.result(0, 0).latency_summary
        assert s is not None and s.count == self.N_OPS
        assert plain.result(0, 0).latency_summary is None

    def test_deadline_misses_on_grid(self, hash_small):
        cfg = SimConfig(P=12, seed=7)
        spec = ArrivalSpec(kind="poisson", rate=400e3, seed=5,
                           deadline=150e-6)
        arr = _arrival_array(spec, cfg, [16], self.N_OPS)
        grid = sweep_grid(cfg, hash_small.trace, [5 * US], [16],
                          n_ops=self.N_OPS, arrivals=arr,
                          collect_percentiles=True, deadline=spec.deadline)
        r = grid.result(0, 0)
        s = r.latency_summary
        assert r.missed_ops == s.missed > 0
        assert s.count + s.missed == self.N_OPS
        if s.count:
            # reported quantiles are bin midpoints: a survivor's bin can
            # straddle the deadline, so allow one half-bin of overshoot
            assert s.p99 <= spec.deadline * (1 + 2 * HIST_REL_ERROR)

    def test_sweep_latency_jax_arrival_matches_loop(self, hash_small,
                                                    tmp_path):
        cfg = SimConfig(P=12, seed=7)
        spec = ARR_SPECS["poisson"]
        kw = dict(n_ops=self.N_OPS, arrival=spec,
                  collect_percentiles=True)
        loop = sweep_latency(cfg, hash_small, self.LATS, self.CANDS,
                             processes=1, **kw)
        jaxp = sweep_latency(cfg, hash_small, self.LATS, self.CANDS,
                             backend="jax", **kw)
        for a, b in zip(loop, jaxp):
            sa, sb = a.result.latency_summary, b.result.latency_summary
            assert sa.source == "exact" and sb.source == "hist"
            assert sb.p50 == pytest.approx(sa.p50, rel=P50_TOL)
            assert sb.p99 == pytest.approx(sa.p99, rel=P99_TOL)
        # and the jax cells cache + round-trip their summaries
        cached = sweep_latency(cfg, hash_small, self.LATS, self.CANDS,
                               backend="jax", cache_dir=str(tmp_path),
                               **kw)
        warm = sweep_latency(cfg, hash_small, self.LATS, self.CANDS,
                             backend="jax", cache_dir=str(tmp_path), **kw)
        for a, b in zip(cached, warm):
            assert a.throughput == b.throughput
            assert (a.result.latency_summary.to_dict()
                    == b.result.latency_summary.to_dict())

    def test_grid_arrival_validation(self, hash_small):
        cfg = SimConfig(P=12, seed=7)
        with pytest.raises(ValueError, match="arrivals"):
            sweep_grid(cfg, hash_small.trace, [1 * US], [8], n_ops=400,
                       arrivals=np.zeros(3))
        with pytest.raises(ValueError, match="deadline"):
            sweep_grid(cfg, hash_small.trace, [1 * US], [8], n_ops=400,
                       deadline=-1.0)


# -- 5. one step, any partition ----------------------------------------------

PARTITION_CASES = {
    **{engine: dict(engine=engine) for engine in ENGINES},
    "open_loop_deadline_pct": dict(engine="hash-index", open_loop=True),
    "ssd2_token_clocks": dict(engine="lsm", cfg=dict(
        n_ssd=2, R_io=250e3, L_switch=0.3 * US)),
    # one thread per core, long IOs: every core segment carries inactive
    # and ready slots at +inf on the wake plane, and starved cores
    # idle-skip to their parked thread's wake-up
    "cores4_inf_wake": dict(engine="lsm", cands=[1, 3], cfg=dict(
        n_cores=4, L_io=40 * US)),
}
PARTITION_FIELDS = ("throughput", "time", "mem_stall_total", "mem_accesses",
                    "p50", "p90", "p99", "lat_max", "lat_count", "missed",
                    "lat_hist")


@pytest.mark.parametrize("case", sorted(PARTITION_CASES))
def test_select_step_bit_identical_to_scatter_step(case, monkeypatch):
    """The TPU's partition (one cohort per 128-lane tile), forced on the
    CPU, gives every number of every cell the CPU's power-of-two
    partition (one cohort per thread bucket) gives, bit for bit: a cell's
    numbers do not depend on the plane width or the cells it shares a
    scan with.  The name is from when the CPU ran a gather/scatter form
    of the step; both sides now run the one select form."""
    spec = PARTITION_CASES[case]
    sc = default_scenario(spec["engine"], n_keys=2_000, n_wl_ops=600)
    store = available_engines()[spec["engine"]](sc.n_keys,
                                                **sc.engine_kwargs)
    wname, wkw = sc.resolved_workload()
    trace = run_trace(store, workloads.create_workload(
        wname, sc.n_keys, sc.n_wl_ops, **wkw)).trace
    cfg = dataclasses.replace(sc.sim_config(), **spec.get("cfg", {}))
    cands, n_ops = spec.get("cands", [4, 8]), 150
    lats = [1 * US, 5 * US]
    kw = {}
    if spec.get("open_loop"):
        arr_spec = ArrivalSpec(kind="poisson", rate=40e3, seed=5,
                               deadline=100e-6)
        kw = dict(arrivals=_arrival_array(arr_spec, cfg, cands, n_ops),
                  collect_percentiles=True, deadline=arr_spec.deadline)

    def grid():
        return sweep_grid(cfg, trace, lats, cands, n_ops=n_ops, **kw)

    pow2 = grid()
    monkeypatch.setattr(replay_jax, "_lane_tiled", lambda: True)
    lane = grid()
    n_lat, C = len(lats), cfg.n_cores
    assert [(c.cells, c.T_max, c.thread_slots)
            for c in pow2.record.cohorts] == [
        (n_lat, n, n_lat * C * n) for n in cands]
    assert [(c.cells, c.T_max, c.thread_slots)
            for c in lane.record.cohorts] == [
        (n_lat * len(cands), max(cands), n_lat * C * sum(cands))]
    for fld in PARTITION_FIELDS:
        a, b = getattr(pow2, fld), getattr(lane, fld)
        assert (a is None) == (b is None), fld
        if a is not None:
            assert np.array_equal(a, b, equal_nan=True), fld
    if spec.get("open_loop"):      # both sides of the deadline are hit
        assert pow2.lat_count.sum() > 0 and pow2.missed.sum() > 0
