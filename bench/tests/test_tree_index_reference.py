"""The tree-index configuration against its plain reference on the CPU, at
small sizes: the same trace and the jax grid's throughput to rounding, for
the read-only mix the cell runs and for a 1:1 mix that takes the write
path and its flush IO; the loop bit for bit with the Mersenne draws; a
whole benchmark run correct; and the float32 control failing."""
import dataclasses

import pytest

from harness import check
from test_check import _run
from test_reference import program, small
from test_reference import test_mersenne_draws_match_the_loop as _mersenne

NAME = "tree_uniform.paper_grid"


def _with_mix(cell, read_write):
    sc = cell.config["scenario"]
    wkw = dict(sc["workload_kwargs"], read_write=read_write)
    return dataclasses.replace(cell, config=dict(
        cell.config, scenario=dict(sc, workload_kwargs=wkw)))


@pytest.mark.parametrize("read_write", [[1, 0], [1, 1]], ids=["1:0", "1:1"])
def test_grid_matches_reference(read_write):
    cell = _with_mix(small(NAME), read_write).for_seed(2**40 + 7)
    _, art = program(cell, 2**31 - 9, 99)
    got = check.program_outputs(art)
    ref = check.reference_outputs(cell, 2**31 - 9, 99, sorted(got["thr"]))
    assert len(ref["kinds"]) > 0
    if read_write[1]:      # the flush IO is in the compared trace
        assert ref["durs"].count(3.5e-6) > 0
    nums = check.numbers(got, ref)
    assert nums["trace_diff"] == 0
    assert nums["thr_max"] < 1e-12


def test_mersenne_draws_match_the_loop():
    _mersenne(NAME)


def test_sound_run_is_correct():
    cell = small(NAME)
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] == cell.n_grid_cells


def test_float32_control_fails():
    """The reference computed in float32, put in the program's place,
    fails every limit of the cell."""
    cell = small(NAME)
    limits = check.limits_for(cell)
    picked = sorted((float(L), int(n))
                    for L in cell.traffic["latencies_us"]
                    for n in cell.traffic["thread_candidates"])
    ref = check.reference_outputs(cell, 123456789, 5, picked)
    ctl = check.reference_outputs(cell, 123456789, 5, picked, f32=True)
    nums = check.numbers(ctl, ref)
    assert all(nums[k] > limits[k] for k in limits), nums
