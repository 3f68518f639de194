"""The check that decides ``correct``: a sound run passes, the
lower-precision control fails, and a run whose timed path is broken
underneath comes out not correct.  Small cells, on the CPU."""
import contextlib
import dataclasses
import math

import pytest

import run as bench
from harness import check
from test_reference import small


def _run(cell, fault=None):
    """A whole benchmark run of ``cell`` with ``fault`` (a context
    manager) active in every sweep after the warm-up."""
    def hook(sweeper):
        plain = sweeper.run

        def broken(i):
            if i < 0 or fault is None:
                return plain(i)
            with fault():
                return plain(i)
        sweeper.run = broken
        return sweeper

    args = bench.parse(["--workload", cell.name, "--seed", "2718281828459",
                        "--seconds", "0", "--trace", "0"])
    return bench.run(args, require_tpu=False, sweeper_hook=hook, cell=cell)


@contextlib.contextmanager
def _patched(module, name, wrap):
    orig = getattr(module, name)
    setattr(module, name, wrap(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _answer_altered():
    """One cell's throughput altered where the grid produces it."""
    from repro.core.sim import replay_jax

    def wrap(orig):
        def sweep_grid(*a, **k):
            res = orig(*a, **k)
            res.throughput[0, 0] *= 1.0 + 1e-5
            return res
        return sweep_grid
    return _patched(replay_jax, "sweep_grid", wrap)


def _half_left_out():
    """Half of the grid's cells left out of the sweep's answer."""
    from repro.core.sim import replay_jax

    def wrap(orig):
        def sweep_grid(*a, **k):
            res = orig(*a, **k)
            res.throughput[: res.throughput.shape[0] // 2] = math.nan
            return res
        return sweep_grid
    return _patched(replay_jax, "sweep_grid", wrap)


def _tail_plane_altered(plane, change):
    """One cell's entry of a tail plane (``missed``, ``lat_count`` or a
    percentile) altered where the grid produces it."""
    from repro.core.sim import replay_jax

    def wrap(orig):
        def sweep_grid(*a, **k):
            res = orig(*a, **k)
            arr = getattr(res, plane)
            arr[-1, 0] = change(arr[-1, 0])
            return res
        return sweep_grid
    return lambda: _patched(replay_jax, "sweep_grid", wrap)


def _trace_altered():
    """One suboperation of the engine trace altered where it is
    recorded."""
    import repro.core.experiment as exp
    from repro.core.engines.trace import TraceResult
    from repro.core.trace_ir import CompiledTrace

    def wrap(orig):
        def run_trace(*a, **k):
            tr = orig(*a, **k)
            durs = tr.trace.durs.copy()
            durs[len(durs) // 2] *= 1.5
            return TraceResult(
                CompiledTrace(tr.trace.kinds, durs, tr.trace.bounds),
                tr.mem_per_op, tr.io_per_op, tr.hit_stats)
        return run_trace
    return _patched(exp, "run_trace", wrap)


CLOSED, OPEN = "hash_uniform_2ssd.paper_grid", "lsm_zipf099.open_loop"


@pytest.mark.parametrize("name", [CLOSED, OPEN])
def test_sound_run_is_correct(name):
    cell = small(name)
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] == cell.n_grid_cells


@pytest.mark.parametrize("name,fault", [
    (CLOSED, _answer_altered),
    (CLOSED, _half_left_out),
    (CLOSED, _trace_altered),
    (OPEN, _tail_plane_altered("missed", lambda v: v + 1)),
    (OPEN, _tail_plane_altered("lat_count", lambda v: v - 1)),
    (OPEN, _tail_plane_altered("p99", lambda v: v * 10.0 ** (1 / 64))),
], ids=["answer", "half", "trace", "missed", "count", "p99_bin"])
def test_broken_timed_path_is_not_correct(name, fault):
    res = _run(small(name), fault)
    assert not res["correct"], res["check"]


def test_deadline_binds():
    """The open-loop traffic's deadline is missed in some compared cells
    and met in others, so the deadline plane does work."""
    cell = small(OPEN).for_seed(31)
    ref = check.reference_outputs(
        cell, 41, 43, [(float(L), 32) for L in cell.traffic["latencies_us"]])
    missed = [t["missed"] for t in ref["tail"].values()]
    assert min(missed) == 0 < max(missed), missed


@pytest.mark.parametrize("name", ["lsm_zipf099.paper_grid",
                                  "hash_uniform_2ssd.paper_grid",
                                  "lsm_zipf099.wide_grid",
                                  "lsm_zipf099.open_loop"])
def test_float32_control_fails(name):
    """The reference computed in float32, put in the program's place,
    fails the limit of every number that measures time or throughput (a
    float32 sojourn stays in its histogram bin, and crosses the deadline
    only where it lies within rounding of it)."""
    cell = small(name)
    limits = check.limits_for(cell)
    picked = sorted((float(L), int(n))
                    for L in cell.traffic["latencies_us"]
                    for n in cell.traffic["thread_candidates"])
    ref = check.reference_outputs(cell, 123456789, 5, picked)
    ctl = check.reference_outputs(cell, 123456789, 5, picked, f32=True)
    nums = check.numbers(ctl, ref)
    assert not check.verdict(nums, limits)
    timed = ("trace_diff", "thr_med", "thr_max", "sojourn_max")
    assert all(nums[k] > limits[k] for k in timed if k in nums), nums
