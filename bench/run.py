#!/usr/bin/env python3
"""Benchmark of the jax sweep grid on the chip: simulated ops per second.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  Set-up turns on the persistent compilation
cache, builds the cell's scenario from ``BENCHMARK.json``, its
configuration file and its traffic file, and runs one warm-up sweep of the
cell's exact shape, which compiles each cohort program or loads it from
the cache.  The window then repeats ``Experiment(scenario,
RunOptions(backend="jax", ...)).run()``, each sweep with its own
simulation seed (and arrival seed) derived from ``--seed``, and stops at
the end of the first whole sweep past ``--seconds``.  The engine's and
the workload's seeds are derived from ``--seed`` once for the run, so the
engine trace, and so every compiled program, is the same in every sweep
of the run and the warm-up sweep compiles the programs the window uses.

``sim_ops_per_s`` is the simulated ops the window's sweeps completed
(grid cells x ops per cell, over whole sweeps) over the wall time of those
sweeps; ``setup_s`` runs from process start to the end of the warm-up
sweep.  ``--trace 1`` runs the window's first sweep under the profiler,
with the benchmark's spans around the program's ``run_trace`` and
``sweep_latency``, and reports the per-layer metrics of ``metrics/``.

After the window one sweep, drawn from ``--seed``, is checked against the
plain reference (``harness/check.py``); ``correct`` says whether every
compared number is within its limit.  The last line of standard output is
the result as JSON.  Without a TPU, or without the program beside the
benchmark, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import check, trace_reduce  # noqa: E402
from harness.cells import derive_seed, load_cell, load_spec  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def import_program():
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise RuntimeError(f"the program is not beside the benchmark "
                           f"({src / 'repro'} is missing)")
    sys.path.insert(0, str(src))
    import repro.core.experiment  # noqa: F401


class Spans:
    """The benchmark's host spans around the program's layers, written
    into the profiler's trace (on only in the traced run)."""

    def __init__(self, on: bool):
        import jax

        self.on = on
        self._ann = jax.profiler.TraceAnnotation
        if on:
            import repro.core.experiment as exp

            for name in ("run_trace", "sweep_latency"):
                setattr(exp, name, self._wrap(name, getattr(exp, name)))

    def _wrap(self, name, fn):
        def spanned(*a, **k):
            with self._ann(name):
                return fn(*a, **k)
        return spanned

    def __call__(self, name):
        return self._ann(name) if self.on else contextlib.nullcontext()


class Sweeper:
    """Runs one sweep of the cell per call and counts its failed cells."""

    def __init__(self, cell, seed: int, spans: Spans):
        from repro.core.experiment import Experiment, RunOptions, Scenario

        self.cell, self.seed, self.spans = cell, seed, spans
        self._exp, self._scenario = Experiment, Scenario
        self._opts = RunOptions(
            backend="jax",
            collect_percentiles=bool(cell.traffic["collect_percentiles"]))
        t = cell.traffic
        self.expected = {(float(L), int(n)) for L in t["latencies_us"]
                         for n in t["thread_candidates"]}

    def seeds(self, i: int) -> tuple[int, int]:
        return (derive_seed(self.seed, "sim", i),
                derive_seed(self.seed, "arrival", i))

    def run(self, i: int):
        """``(artifact or None, failed cells)`` of sweep ``i``."""
        with self.spans("bench_sweep"):
            sc = self._scenario.from_dict(
                self.cell.scenario_dict(*self.seeds(i)))
            try:
                with self.spans("experiment"):
                    art = self._exp(sc, self._opts).run()
            except Exception:
                traceback.print_exc()
                return None, len(self.expected)
        got = {(float(r.L_us), int(n)): v
               for r in art.rows for n, v in r.per_thread}
        bad = sum(1 for c in self.expected
                  if not (math.isfinite(got.get(c, math.nan))
                          and got[c] > 0.0))
        return art, bad


def traced_sweep(jax, sweeper):
    """The window's first sweep under the profiler (one sweep: a scan
    emits millions of operation events a second), and its trace."""
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        art, bad = sweeper.run(0)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    trace = trace_reduce.extract(path) if path else None
    shutil.rmtree(log_dir, ignore_errors=True)
    return art, bad, trace


def compile_counter():
    import jax.monitoring

    count = {"n": 0}

    def listen(event, duration, **kw):
        if event == COMPILE_EVENT:
            count["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def read_metrics(spec: dict, cell_name: str, ctx: dict) -> dict:
    out = {}
    for m in spec["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        path = BENCH / "metrics" / f"{m['name']}.py"
        s = importlib.util.spec_from_file_location(f"metric_{m['name']}",
                                                   path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(args, require_tpu: bool = True, sweeper_hook=None,
        cell=None) -> dict:
    """One benchmark run; returns the result line as a dict.

    ``require_tpu=False``, ``sweeper_hook`` (which may wrap the sweeper to
    break what it returns) and ``cell`` (a smaller cell) exist for the
    benchmark's own tests."""
    spec = load_spec()
    cell = (cell or load_cell(args.workload)).for_seed(args.seed)
    import_program()
    from repro.compile_cache import use_compile_cache

    use_compile_cache(CHECKOUT)
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU, found {devs[0].platform} "
                           f"({devs[0].device_kind})")
    if len(devs) < cell.chips:
        raise RuntimeError(f"the cell asks for {cell.chips} chips, "
                           f"JAX sees {len(devs)}")

    spans = Spans(bool(args.trace))
    sweeper = Sweeper(cell, args.seed, spans)
    if sweeper_hook is not None:
        sweeper = sweeper_hook(sweeper)
    _, bad = sweeper.run(-1)                  # warm-up: compile or load
    if bad:
        raise RuntimeError("the warm-up sweep failed")
    setup_s = time.perf_counter() - T_START

    compiles = compile_counter()
    arts, failed, attempted, trace = [], 0, 0, None
    t0 = time.perf_counter()
    ends = [t0]
    while True:
        if args.trace and not arts:
            art, bad, trace = traced_sweep(jax, sweeper)
        else:
            art, bad = sweeper.run(len(arts))
        ends.append(time.perf_counter())
        arts.append(art)
        failed += bad
        attempted += cell.n_grid_cells
        if ends[-1] - t0 >= args.seconds:
            break
    wall = ends[-1] - t0
    print("sweeps_s " + " ".join(f"{b - a:.4f}" for a, b in
                                 zip(ends, ends[1:])), file=sys.stderr)
    n_compiles = compiles["n"]
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devs[:cell.chips])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        ctx = {"trace": trace, "compiles_in_window": n_compiles,
               "traced_ops": cell.ops_per_sweep}
        result["metrics"] = read_metrics(spec, cell.name, ctx)
        win = trace_reduce.window_of(trace) if trace else None
        if win and trace["device_modules"]:
            busy = trace_reduce.covered(trace_reduce.busy(trace), *win)
            device.update(busy_s=busy * 1e-9,
                          window_s=(win[1] - win[0]) * 1e-9)
            result["breakdown"] = trace_reduce.breakdown(trace)
    else:
        result["metrics"] = {
            "sim_ops_per_s": {"value": len(arts) * cell.ops_per_sweep / wall,
                              "unit": "ops/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device

    k = derive_seed(args.seed, "compare") % len(arts)
    limits = check.limits_for(cell)
    nums = {}
    if arts[k] is not None:
        got = check.program_outputs(arts[k])
        arts.clear()
        grid_cells = check.pick_cells(cell, got["thr"],
                                      derive_seed(args.seed, "cells"))
        ref = check.reference_outputs(cell, *sweeper.seeds(k), grid_cells)
        nums = check.numbers(got, ref)
    result["correct"] = (bool(nums) and failed == 0
                         and check.verdict(nums, limits))
    result["check"] = {n: {"value": v, "limit": limits.get(n)}
                       for n, v in nums.items()}
    result["check"]["failed_cells"] = {"value": failed, "limit": 0}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except Exception as e:          # no result line on any failure
        traceback.print_exc()
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
