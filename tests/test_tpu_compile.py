"""The jax sweep grid compiles for a TPU v5e chip that is described, not
attached.

Each case drives one ``chip_smoke.py`` phase through
``Experiment.run(backend="jax")`` on the CPU with the grid program stubbed
out, records the arguments of its widest cohort, and compiles
``replay_jax._grid_body`` for one described v5e chip at exactly those
shapes.  What the TPU compiler refuses fails here instead of on the chip.
The grid is partitioned and its step built as on the chip
(``replay_jax._lane_tiled`` forced on).  Every case also asserts that the
lowered program holds no 64-bit ``bitcast_convert`` (XLA:TPU emulates
float64 and cannot rewrite one), and that the compiled scan step -- the
inner ``while`` body -- holds no ``scatter`` in it or in any computation
it calls, and no ``conditional``.  The CPU twin of that census compiles
each benchmark cell's grid for the CPU, partitioned as on the CPU.

The topology is described inside a fixture, never at import time, so every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import dataclasses
import importlib.util
import re
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core.experiment import (  # noqa: E402
    Experiment,
    RunOptions,
    Scenario,
)
from repro.core.sim import replay_jax  # noqa: E402
from repro.core.sim.arrivals import HIST_BINS  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _load(name, path):
    """The module at ``ROOT / path``, imported once as ``name``."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, ROOT / path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def _phase(label):
    """The chip_smoke scenario with this label, full size."""
    for _, name, sc in _load("chip_smoke", "chip_smoke.py")._scenarios(
            tiny=False):
        if name == label:
            return sc
    raise KeyError(label)


def _open_loop():
    # The smoke test derives its rate from a closed-loop run; the rate
    # does not change any shape, so a fixed one stands in for it here.
    return dataclasses.replace(_phase("lsm_open_loop"), arrival={
        "kind": "poisson", "rate": 100e3, "seed": 11, "deadline": 1e-3})


CASES = {
    "closed_1ssd": lambda: _phase("lsm"),
    "io_clocks_2ssd": lambda: _phase("hash_index_2ssd"),
    "open_loop_pct_deadline": _open_loop,
    "cores4": lambda: _phase("lsm_4core"),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _bench_cell(name):
    """The scenario one sweep of the benchmark cell ``name`` runs."""
    cell = _load("bench_cells", "bench/harness/cells.py").load_cell(name)
    return Scenario.from_dict(cell.scenario_dict(7, 11))


BENCH_CELLS = ("lsm_zipf099.paper_grid", "hash_uniform_2ssd.paper_grid",
               "lsm_zipf099.wide_grid", "lsm_zipf099.open_loop",
               "tree_uniform.paper_grid")


def _record_grid_calls(monkeypatch, sc, lane_tiled=True):
    """Run ``sc`` on the jax backend with the grid program replaced by a
    recorder, partitioned as on the chip (``lane_tiled``) or as on the
    CPU; returns ``[(args, static), ...]``, one per cohort."""
    calls = []

    def record(*args, **static):
        calls.append((args, static))
        G = args[5].shape[0]
        n_ops = float(args[8])
        out = dict(
            throughput=np.full(G, 1e5), time=np.ones(G),
            mem_stall_total=np.zeros(G), mem_accesses=np.zeros(G, np.int64),
            counted=np.full(G, n_ops), steps_run=np.zeros(G, np.int64))
        if static["has_lat"]:
            hist = np.zeros((G, HIST_BINS))
            hist[:, HIST_BINS // 2] = n_ops
            out.update(lat_hist=hist, lat_max=np.full(G, 1e-4),
                       missed=np.zeros(G, np.int32))
        return out

    monkeypatch.setattr(replay_jax, "_run_grid", record)
    if lane_tiled:
        monkeypatch.setattr(replay_jax, "_lane_tiled", lambda: True)
    Experiment(sc, RunOptions(backend="jax",
                              collect_percentiles=bool(sc.arrival))).run()
    return calls


_BITCAST64 = re.compile(r"bitcast_convert.*(f64|i64|ui64)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations"
                     r"|true_computation|false_computation)"
                     r"=(?:\{([^}]*)\}|(%[\w.\-]+))")
_OPCODE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([a-z][a-z0-9\-]*)\(")


def _scan_step_opcodes(hlo_text):
    """``(body, reached)``: the opcodes of the compiled inner ``while`` body
    (the scan over a chunk's steps), and those of every computation it
    reaches through ``calls=``, ``to_apply=``, branches and loops -- the
    fusions it launches included."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    bodies = [re.search(r"body=%?([\w.\-]+)", line).group(1)
              for lines in comps.values() for line in lines
              if " while(" in line
              and 'op_name="jit(_grid_body)/while/body/while"' in line]
    assert len(bodies) == 1, f"inner scan while bodies: {bodies}"

    def opcodes(names):
        return [m.group(1) for n in names for line in comps[n]
                if (m := _OPCODE.match(line))]

    seen, todo = set(), [bodies[0]]
    while todo:
        n = todo.pop()
        if n not in seen:
            seen.add(n)
            todo += [c.strip().lstrip("%") for line in comps[n]
                     for m in _CALLED.finditer(line)
                     for c in (m.group(1) or m.group(2)).split(",")
                     if c.strip()]
    return opcodes(bodies), opcodes(seen)


@pytest.mark.parametrize("case", sorted(CASES))
def test_grid_compiles_for_v5e(case, one_chip, no_persistent_cache,
                               monkeypatch):
    run_grid = replay_jax._run_grid
    calls = _record_grid_calls(monkeypatch, CASES[case]())
    assert calls, "the jax backend never reached the grid program"
    args, static = max(calls, key=lambda c: c[1]["T_max"])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=one_chip), args)
    with jax.enable_x64(True), jax.threefry_partitionable(False):
        lowered = run_grid.lower(*shapes, **static)
    hlo = lowered.as_text()
    assert not _BITCAST64.findall(hlo), "64-bit bitcast in the grid program"
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    body, reached = _scan_step_opcodes(compiled.as_text())
    assert "fusion" in body, "the inner while body was not found whole"
    assert "conditional" not in body, "a run-time branch in the scan step"
    assert "scatter" not in reached, "a scatter in the scan step"


@pytest.mark.parametrize("case", [*BENCH_CELLS, "cores4"])
def test_grid_step_census_on_cpu(case, monkeypatch):
    """The CPU runs the chip's step: each benchmark cell's widest cohort
    (and the four-core phase's), partitioned as on the CPU and compiled
    for it, has no ``scatter`` and no ``conditional`` in its scan step."""
    run_grid = replay_jax._run_grid
    sc = CASES[case]() if case in CASES else _bench_cell(case)
    calls = _record_grid_calls(monkeypatch, sc, lane_tiled=False)
    assert calls, "the jax backend never reached the grid program"
    args, static = max(calls, key=lambda c: c[1]["T_max"])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)
    with jax.enable_x64(True), jax.threefry_partitionable(False):
        compiled = run_grid.lower(*shapes, **static).compile()
    body, reached = _scan_step_opcodes(compiled.as_text())
    assert "fusion" in body, "the inner while body was not found whole"
    assert "conditional" not in reached, "a run-time branch in the scan step"
    assert "scatter" not in reached, "a scatter in the scan step"
