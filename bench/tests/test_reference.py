"""The plain reference against the program on the CPU, at small sizes:
the same trace, the loop simulator's results bit for bit with the
Mersenne draws, and the jax grid's to rounding with the threefry draws."""
import dataclasses

import pytest

from harness import check
from harness.cells import load_cell
from reference import draws, sim, trace

CELLS = ["lsm_zipf099.paper_grid", "hash_uniform_2ssd.paper_grid",
         "lsm_zipf099.open_loop"]


def small(name):
    c = load_cell(name)
    t = dict(c.traffic, latencies_us=[1, 10],
             thread_candidates=c.traffic["thread_candidates"][:2],
             n_ops=400)
    cfg = dict(c.config, scenario=dict(c.config["scenario"], n_keys=20000,
                                       n_wl_ops=4000))
    return dataclasses.replace(c, traffic=t, config=cfg)


def program(cell, sim_seed, arrival_seed):
    from repro.core.experiment import Experiment, RunOptions, Scenario

    sc = Scenario.from_dict(cell.scenario_dict(sim_seed, arrival_seed))
    return sc, Experiment(sc, RunOptions(
        backend="jax",
        collect_percentiles=cell.traffic["collect_percentiles"])).run()


def test_draw_layout_is_the_programs():
    """The reference draws in the grid's layout; a program that changes
    it (the chunk of uniforms, the threefry bit layout, the fold-in
    indices) fails ``correct`` on every run until a benchmark change
    follows it."""
    from repro.core.sim import replay_jax

    assert replay_jax._RNG_CHUNK == draws._CHUNK, (
        f"the grid draws its uniforms in chunks of "
        f"{replay_jax._RNG_CHUNK} steps, the benchmark's reference in "
        f"{draws._CHUNK} (bench/reference/draws.py)")


@pytest.mark.parametrize("name", CELLS)
def test_grid_matches_reference(name):
    cell = small(name).for_seed(2**40 + 3)
    _, art = program(cell, 2**31 - 5, 99)
    got = check.program_outputs(art)
    ref = check.reference_outputs(cell, 2**31 - 5, 99, sorted(got["thr"]))
    nums = check.numbers(got, ref)
    assert nums["trace_diff"] == 0
    assert nums["thr_max"] < 1e-12
    assert nums.get("sojourn_max", 0.0) < 1e-12


@pytest.mark.parametrize("name", CELLS)
def test_mersenne_draws_match_the_loop(name):
    from repro.core.engines import run_trace
    from repro.core.experiment import Experiment
    from repro.core.sim.arrivals import generate_arrivals
    from repro.core.sim.engine_loop import simulate_compiled

    cell = small(name)
    sc_dict = cell.scenario_dict(17, 18)
    from repro.core.experiment import Scenario

    sc = Scenario.from_dict(sc_dict)
    store, wl = Experiment(sc).build()
    ct = run_trace(store, wl, sc.warmup_frac).trace
    tr = trace.record(sc_dict)
    dev = check.device_of(cell.config)
    arrivals = None
    if sc.arrival:
        arrivals = generate_arrivals(sc.arrival_spec(),
                                     check.arrival_count(cell.traffic))
    for L_us in sc.latencies_us:
        for n in sc.thread_candidates:
            cfg = dataclasses.replace(sc.sim_config(), L_mem=L_us * 1e-6,
                                      n_threads=n)
            want = simulate_compiled(
                cfg, ct, sc.n_ops, arrivals=arrivals,
                collect_percentiles=arrivals is not None,
                deadline=sc.arrival.get("deadline", 0.0) if arrivals
                is not None else 0.0)
            got = sim.simulate(
                tr, dev, L_us * 1e-6, n, sc.n_ops, draws.Mersenne(17),
                arrivals=None if arrivals is None else arrivals.tolist(),
                deadline=sc.arrival.get("deadline", 0.0))
            assert got["throughput"] == want.throughput
            if arrivals is not None:
                s = want.latency_summary
                assert (got["p99"], got["max"], got["missed"]) == (
                    s.p99, s.max, s.missed)
