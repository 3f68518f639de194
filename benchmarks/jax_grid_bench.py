#!/usr/bin/env python
"""Benchmark: the jax sweep backend vs. the forked-process loop pipeline.

Times the same latency x threads grids through both ``sweep_latency``
backends and records the measurements as JSON (schema
``repro.jax_grid_bench/v1``; validated by ``tools/check_bench.py``).
Three suites:

``default``
    The paper's default scenario grid (6 latencies x 5 thread
    candidates, one LSM default-pairing trace).  The loop side is the
    real forked worker pipeline; the acceptance bar is warm jax >= 1x.
``mega``
    The scale story: 4 engines x n_ssd {1,2} x 128 latencies x
    {8,16,32,64} threads -- 4096 cells, each engine x device point
    swept as one jitted grid call (the 2-SSD half uses the matrix
    device config with IO token clocks).  The loop side runs the
    identical cells through the same pipeline (this is the slow part
    of the bench: minutes).  Acceptance bar: warm jax >= 5x.
``het``
    The cohort story: one engine, 64 latencies x a maximally *uneven*
    thread axis (8..128) -- the monolithic single-scan layout (every
    cell padded to 128 threads, all scanned to the global worst-case
    step bound) against the cohort early-exit scan that buckets cells
    by thread width and step bound.  Records ``jax_mono_warm_s`` /
    ``mono_speedup`` (cohort vs. monolithic on identical cells) and the
    wasted-step counters (``cell_steps_bound`` vs ``cell_steps_run``).
    Acceptance bar: cohort >= 1.5x monolithic.
``smoke``
    A seconds-scale slice (one small trace, 8 cells) for CI: same
    schema, compared against the checked-in baseline ratio by the
    perf-smoke job with a generous threshold (machine-to-machine noise
    is expected; a real regression is 5-10x, not 20%).

The checked-in ``BENCH_jax_grid.json`` is produced by::

    PYTHONPATH=src python benchmarks/jax_grid_bench.py \
        --suite default,mega,het,smoke --out BENCH_jax_grid.json

Cold timings include jit compilation; warm is the best of ``--reps``
repetitions.  Every loop grid is timed before jax is first imported, so
the pipeline keeps its plain-fork worker start method (see
``sweep._pick_context``).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

SCHEMA = "repro.jax_grid_bench/v1"
US = 1e-6

# The mega suite's axes: every registered engine family with a distinct
# suboperation mix, an n_ssd axis (plain single-SSD config vs. the
# matrix 2-SSD device config with IO token clocks), a fine latency
# axis, and the pow2 thread candidates that bucket into one (G, 64)
# grid call per engine x device point.
MEGA_ENGINES = ("lsm", "hash-index", "tree-index", "two-tier-cache")
MEGA_N_SSD = (1, 2)
MEGA_N_LATS = 128
MEGA_CANDS = (8, 16, 32, 64)
MEGA_N_OPS = 2000

# The het suite's axes: a deliberately uneven thread spread (16x between
# the narrowest and widest cell, straddling five pow2 buckets) so the
# monolithic layout's padding-to-T_max and global step bound are maximally
# wasteful -- the structure the cohort scan exists to avoid.
HET_N_LATS = 64
HET_CANDS = (8, 16, 24, 32, 48, 64, 96, 128)
HET_N_OPS = 2000


def _timed(fn, *a, **kw) -> float:
    t0 = time.perf_counter()
    fn(*a, **kw)
    return time.perf_counter() - t0


def _trace(engine: str, n_keys: int, n_wl_ops: int):
    """The engine's default-pairing zipf trace (compiled)."""
    from repro.core import workloads
    from repro.core.engines import available_engines, run_trace

    store = available_engines()[engine](n_keys)
    wl = workloads.zipf(n_keys, n_wl_ops, 0.99, (1, 0), seed=3)
    return run_trace(store, wl).trace


def _suite_specs(suite: str, args):
    """The grids of one suite: (name, engine, dev_kwargs, trace_params,
    lats, cands, n_ops) tuples."""
    from repro.core.experiment import Scenario

    if suite == "default":
        sc = Scenario(engine="lsm")
        return [("default", "lsm", {},
                 (args.n_keys, args.n_wl_ops),
                 [l * US for l in sc.latencies_us],
                 list(sc.thread_candidates), args.n_ops)]
    if suite == "mega":
        lats = [float(l) * US for l in
                np.round(np.linspace(0.1, 10.0, MEGA_N_LATS), 4)]
        devs = {1: {},
                2: dict(n_ssd=2, R_io=250e3, L_switch=0.3 * US)}
        return [(f"mega:{eng}:ssd{n_ssd}", eng, devs[n_ssd],
                 (args.n_keys, args.n_wl_ops),
                 lats, list(MEGA_CANDS), MEGA_N_OPS)
                for eng in MEGA_ENGINES for n_ssd in MEGA_N_SSD]
    if suite == "het":
        lats = [float(l) * US for l in
                np.round(np.linspace(0.1, 10.0, HET_N_LATS), 4)]
        return [("het:lsm", "lsm", {},
                 (args.n_keys, args.n_wl_ops),
                 lats, list(HET_CANDS), HET_N_OPS)]
    if suite == "smoke":
        return [("smoke", "hash-index", {}, (4_000, 1_500),
                 [l * US for l in (0.5, 2, 5, 9)], [8, 16], 800)]
    raise SystemExit(f"unknown suite {suite!r} "
                     "(valid: default, mega, het, smoke)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="default",
                    help="comma-separated: default, mega, smoke")
    ap.add_argument("--out", default=None, metavar="OUT.json",
                    help="write the measurement JSON here (default: "
                         "print to stdout)")
    ap.add_argument("--reps", type=int, default=3,
                    help="warm-run repetitions (best is reported)")
    ap.add_argument("--n-ops", type=int, default=5000,
                    help="measured ops per cell (default suite)")
    ap.add_argument("--n-keys", type=int, default=30_000)
    ap.add_argument("--n-wl-ops", type=int, default=10_000)
    args = ap.parse_args()

    from pathlib import Path

    from repro.compile_cache import use_compile_cache
    from repro.core.sim import SimConfig
    from repro.core.sim.sweep import sweep_latency

    specs = []
    for suite in args.suite.split(","):
        specs.extend(_suite_specs(suite.strip(), args))

    traces = {}
    for _name, eng, _dev, (nk, nw), *_rest in specs:
        if (eng, nk, nw) not in traces:
            traces[(eng, nk, nw)] = _trace(eng, nk, nw)

    # Loop side first, before jax is ever imported (keeps the pipeline's
    # plain-fork workers).  The pipeline is timed end to end, exactly as
    # a user would run it.
    entries = []
    for name, eng, dev, (nk, nw), lats, cands, n_ops in specs:
        cfg = SimConfig(P=12, seed=7, **dev)
        tr = traces[(eng, nk, nw)]
        t_loop = _timed(sweep_latency, cfg, tr, lats, cands, n_ops=n_ops)
        entries.append({
            "name": name, "engine": eng, "n_ssd": cfg.n_ssd,
            "n_latencies": len(lats), "n_threads": len(cands),
            "cells": len(lats) * len(cands), "n_ops": n_ops,
            "loop_s": round(t_loop, 4), "loop_mode": "pipeline",
        })
        print(f"# {name}: loop pipeline {t_loop:.2f}s "
              f"({len(lats) * len(cands)} cells)", file=sys.stderr,
              flush=True)

    use_compile_cache(Path(__file__).resolve().parents[1])
    for entry, (name, eng, dev, (nk, nw), lats, cands, n_ops) \
            in zip(entries, specs):
        cfg = SimConfig(P=12, seed=7, **dev)
        tr = traces[(eng, nk, nw)]
        t_cold = _timed(sweep_latency, cfg, tr, lats, cands, n_ops=n_ops,
                        backend="jax")
        t_warm = min(
            _timed(sweep_latency, cfg, tr, lats, cands, n_ops=n_ops,
                   backend="jax")
            for _ in range(args.reps))
        entry["jax_cold_s"] = round(t_cold, 4)
        entry["jax_warm_s"] = round(t_warm, 4)
        entry["warm_speedup"] = round(entry["loop_s"] / t_warm, 3)
        print(f"# {name}: jax cold {t_cold:.2f}s warm {t_warm:.2f}s "
              f"-> {entry['warm_speedup']:.2f}x", file=sys.stderr,
              flush=True)

        if name.startswith("het"):
            # Cohort vs. monolithic on identical cells, both through
            # sweep_grid directly so the comparison excludes the (shared,
            # tiny) sweep_latency wrapper.  bucket_threads=False +
            # early_exit=False is exactly the pre-cohort single-scan
            # layout: one T_max-wide plane, one global step bound.
            from repro.core.sim.replay_jax import sweep_grid

            g = sweep_grid(cfg, tr, lats, cands, n_ops=n_ops)
            t_coh = min(_timed(sweep_grid, cfg, tr, lats, cands,
                               n_ops=n_ops) for _ in range(args.reps))
            _timed(sweep_grid, cfg, tr, lats, cands, n_ops=n_ops,
                   bucket_threads=False, early_exit=False)  # mono compile
            t_mono = min(_timed(sweep_grid, cfg, tr, lats, cands,
                                n_ops=n_ops, bucket_threads=False,
                                early_exit=False)
                         for _ in range(args.reps))
            entry["jax_cohort_warm_s"] = round(t_coh, 4)
            entry["jax_mono_warm_s"] = round(t_mono, 4)
            entry["mono_speedup"] = round(t_mono / t_coh, 3)
            entry["cell_steps_bound"] = int(g.cell_steps_bound)
            entry["cell_steps_run"] = int(g.cell_steps_run)
            saved = 1.0 - g.cell_steps_run / max(g.cell_steps_bound, 1)
            entry["steps_saved_frac"] = round(saved, 4)
            print(f"# {name}: cohort {t_coh:.2f}s vs monolithic "
                  f"{t_mono:.2f}s -> {entry['mono_speedup']:.2f}x "
                  f"(early exit saved {saved:.1%} of bounded steps)",
                  file=sys.stderr, flush=True)

    import jax

    def _agg(prefix):
        sel = [e for e in entries if e["name"].startswith(prefix)]
        if not sel:
            return None
        loop = sum(e["loop_s"] for e in sel)
        warm = sum(e["jax_warm_s"] for e in sel)
        return {"cells": sum(e["cells"] for e in sel),
                "loop_s": round(loop, 4), "jax_warm_s": round(warm, 4),
                "warm_speedup": round(loop / warm, 3)}

    doc = {
        "schema": SCHEMA,
        "host": {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "cpu_count": os.cpu_count(),
            "python": platform.python_version(),
            "jax": jax.__version__,
        },
        "entries": entries,
        "summary": {k: v for k, v in (
            ("default", _agg("default")),
            ("mega", _agg("mega:")),
            ("het", _agg("het")),
            ("smoke", _agg("smoke")),
        ) if v is not None},
    }
    het_sel = [e for e in entries if e["name"].startswith("het")]
    if het_sel:
        coh = sum(e["jax_cohort_warm_s"] for e in het_sel)
        mono = sum(e["jax_mono_warm_s"] for e in het_sel)
        bound = sum(e["cell_steps_bound"] for e in het_sel)
        run = sum(e["cell_steps_run"] for e in het_sel)
        doc["summary"]["het"].update(
            jax_cohort_warm_s=round(coh, 4),
            jax_mono_warm_s=round(mono, 4),
            mono_speedup=round(mono / coh, 3),
            steps_saved_frac=round(1.0 - run / max(bound, 1), 4),
        )
    text = json.dumps(doc, indent=2) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"# wrote {args.out}", file=sys.stderr)
    else:
        print(text, end="")


if __name__ == "__main__":
    main()
