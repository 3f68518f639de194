#!/usr/bin/env python3
"""Smoke run of the jax sweep grid on one TPU chip.

Every phase runs a scenario through ``Experiment(scenario,
RunOptions(backend="jax")).run()`` -- the jnp scan of the scheduler grid,
compiled for the chip -- and the same scenario through the loop backend,
the reference.  Each phase prints one JSON line: the scenario, its cells,
``n_ops``, the cold and warm wall time of the jax run, the loop's wall
time, the worst relative error against the loop and the conformance
tolerance it was held to.  A phase that raises, or any error beyond its
tolerance, ends the script with a non-zero exit code.

Phases:

  a  the paper's default scenario (100 000 keys, 30 000-op workload,
     6 latencies x 5 thread candidates) for each registered engine at
     ``n_ops=20_000``, the size the 1% per-cell contract is stated at;
  b  ``examples/scenarios/hash_index_2ssd.json`` (per-device IO clocks);
  c  ``lsm`` under Poisson open-loop arrivals at 50% of its closed-loop
     capacity, with percentiles and an SLA deadline;
  d  ``examples/scenarios/cluster_hot_shard.json`` (a 4-node fleet);
  e  ``lsm`` with ``n_cores=4``.

The first line probes what float64 means on the device.  The last line is
``{"ok": true, "device": {...}}``, printed only on a TPU.  Everything runs
in this one process, which alone holds the chip; the loop reference runs
serially, so no worker process is started.

Usage::

    python chip_smoke.py                            # on a TPU machine
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal, small sizes

``--tiny`` shrinks the scenarios and admits a CPU backend so the whole
path can be rehearsed without a chip; off a TPU it still exits non-zero
and prints no result.  The compile cache is ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` beside this script.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))


def _scenarios(tiny: bool):
    """``(phase, label, scenario)`` for every phase but (c), whose rate is
    derived from a closed-loop run (see :func:`_open_loop`)."""
    from repro.core.engines import available_engines
    from repro.core.experiment import Scenario, default_scenario

    def sized(sc):
        if not tiny:
            return sc
        # Small enough for a CPU, large enough for the contract's
        # 1/sqrt(n_ops) noise law to hold (it is stated from 5000 ops up).
        return dataclasses.replace(
            sc, n_keys=min(sc.n_keys, 30_000),
            n_wl_ops=min(sc.n_wl_ops, 10_000), n_ops=min(sc.n_ops, 5_000),
            latencies_us=sc.latencies_us[:2],
            thread_candidates=sc.thread_candidates[:2])

    ex = ROOT / "examples" / "scenarios"
    engines = sorted({c.engine_name for c in available_engines().values()})
    out = [("a", eng, sized(default_scenario(eng, n_ops=20_000)))
           for eng in engines]
    out.append(("b", "hash_index_2ssd", sized(
        Scenario.from_json((ex / "hash_index_2ssd.json").read_text()))))
    out.append(("c", "lsm_open_loop", sized(default_scenario(
        "lsm", n_ops=20_000, latencies_us=(1, 5, 10),
        thread_candidates=(32,)))))
    out.append(("d", "cluster_hot_shard", Scenario.from_json(
        (ex / "cluster_hot_shard.json").read_text())))
    out.append(("e", "lsm_4core", sized(default_scenario(
        "lsm", n_cores=4, n_ops=20_000))))
    return out


def _open_loop(sc, load: float = 0.5, deadline_s: float = 1e-3):
    """``sc`` under Poisson arrivals at ``load`` times its closed-loop
    capacity (the loop backend's throughput at its slowest latency point,
    so no point runs above that load), with an SLA deadline."""
    from repro.core.experiment import Experiment, RunOptions

    closed = Experiment(sc, RunOptions(processes=1)).run()
    cap = min(row.throughput for row in closed.rows)
    return dataclasses.replace(sc, arrival={
        "kind": "poisson", "rate": load * cap, "seed": 11,
        "deadline": deadline_s})


def _tolerances(sc, tiny: bool) -> dict:
    """The conformance contract's bounds at this scenario's ``n_ops``.

    ``--tiny`` scenarios lie outside the grids the contract was measured
    on, so they get the fuzzer's slack, as conformance fuzzing does."""
    from repro.core import conformance as cf

    n = sc.n_ops
    cl = sc.cluster_spec()
    slack = cf.FUZZ_SLACK if tiny else 1.0
    tail_slack = cf.FUZZ_TAIL_SLACK if tiny else 1.0
    if cl is not None and cl.n_nodes > 1:
        c = cf.CONTRACTS["cluster-jax-vs-loop"]
        thr = cf.jax_grid_tol(n, base=c.throughput_tol, ref_ops=c.ref_ops,
                              slack=slack)
    else:
        c = cf.CONTRACTS["jax-vs-loop"]
        thr = cf.jax_grid_tol(n, slack=slack)
    return {
        "contract": c.name,
        "throughput": thr,
        "p50": cf.tail_tol(n, base=c.p50_tol, ref_ops=c.tail_ref_ops,
                           slack=tail_slack),
        "p99": cf.tail_tol(n, base=c.p99_tol, ref_ops=c.tail_ref_ops,
                           slack=tail_slack),
    }


def _rel(ref: float, got: float) -> float:
    return abs(got - ref) / abs(ref) if ref else math.inf


def _compare(ref, got, tol: dict) -> dict:
    """Worst relative errors of the jax artifact ``got`` against the loop
    artifact ``ref``: every (latency, threads) cell's throughput, and the
    winning cell's percentiles where both backends picked the same thread
    count (p50 only on unimodal sojourns, as the contract states).  A
    percentile that was never compared stays ``None``."""
    from repro.core.conformance import P50_BIMODAL_GATE

    worst = {"throughput": 0.0, "p50": None, "p99": None}

    def note(key, ref_v, got_v):
        r = _rel(ref_v, got_v)
        worst[key] = r if worst[key] is None else max(worst[key], r)

    tails = 0
    for rr, jr in zip(ref.rows, got.rows, strict=True):
        ra, ja = dict(rr.per_thread), dict(jr.per_thread)
        if set(ra) != set(ja):
            raise RuntimeError(f"{rr.label()}: thread candidates differ")
        for n in ra:
            note("throughput", ra[n], ja[n])
        if rr.tail is None or rr.n_threads != jr.n_threads:
            continue
        if jr.tail is None:
            raise RuntimeError(f"{rr.label()}: the jax run has no tail")
        tails += 1
        a, b = rr.tail, jr.tail
        if a["p50_us"] is None or b["p50_us"] is None:
            raise RuntimeError(f"{rr.label()}: every op missed its deadline")
        for fld in ("p90_us", "p99_us"):
            note("p99", a[fld], b[fld])
        if a["p90_us"] < P50_BIMODAL_GATE * a["p50_us"]:
            note("p50", a["p50_us"], b["p50_us"])
    ok = all(v is None or v <= tol[k] for k, v in worst.items())
    return {"worst_rel": worst, "tails_compared": tails, "ok": ok}


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t


def run_phase(phase: str, label: str, sc, tiny: bool) -> dict:
    """Loop reference, then a cold and a warm jax run; the phase's line."""
    from repro.core.experiment import Experiment, RunOptions

    pct = bool(sc.arrival) or sc.cluster_spec() is not None
    ref, loop_s = _timed(lambda: Experiment(sc, RunOptions(
        processes=1, collect_percentiles=pct)).run())
    opts = RunOptions(backend="jax", collect_percentiles=pct)
    cold, cold_s = _timed(lambda: Experiment(sc, opts).run())
    warm, warm_s = _timed(lambda: Experiment(sc, opts).run())
    if [r.per_thread for r in cold.rows] != [r.per_thread for r in warm.rows]:
        raise RuntimeError(f"{label}: cold and warm jax runs disagree")
    tol = _tolerances(sc, tiny)
    cmp = _compare(ref, warm, tol)
    return {
        "phase": phase, "scenario": label,
        "cells": len(sc.latencies_us) * len(sc.thread_candidates),
        "n_ops": sc.n_ops, "cold_s": cold_s, "warm_s": warm_s,
        "loop_s": loop_s, **cmp, "tol": tol,
    }


def f64_probe() -> dict:
    """What the device makes of float64: exponent range, flush-to-zero,
    and rounding against numpy on the host.  The scheduler needs only
    magnitudes inside float32's range and ~1e-13 relative rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.enable_x64(True):
        mul = jax.jit(jnp.multiply)
        one = jnp.float64(1.0)
        tiny = np.finfo(np.float64).tiny
        x = np.random.default_rng(0).random(4096) * 10.0
        y = np.random.default_rng(1).random(4096) + 0.5
        rel = {}
        for name, jop, nop in (("add", jnp.add, np.add),
                               ("mul", jnp.multiply, np.multiply),
                               ("div", jnp.divide, np.divide)):
            dev = np.asarray(jax.jit(jop)(x, y))
            rel[name] = float(np.max(np.abs(dev - nop(x, y))
                                     / np.abs(nop(x, y))))
        return {
            "phase": "f64_probe",
            "f64_tiny_survives": float(mul(jnp.float64(tiny), one)) != 0.0,
            "min_normal_2^-126_survives":
                float(mul(jnp.float64(2.0 ** -126), one)) != 0.0,
            "2^-127_survives":
                float(mul(jnp.float64(2.0 ** -127), one)) != 0.0,
            "1e38*100_finite":
                bool(np.isfinite(float(mul(jnp.float64(1e38),
                                           jnp.float64(100.0))))),
            "max_rel_err": rel,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal: small scenarios, any backend, no result")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache(ROOT)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: needs a TPU, found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(json.dumps({**f64_probe(), "compile_cache": cache}), flush=True)

    failed = []
    for phase, label, sc in _scenarios(args.tiny):
        if phase == "c":
            sc = _open_loop(sc)
        line = run_phase(phase, label, sc, args.tiny)
        print(json.dumps(line), flush=True)
        if not line["ok"]:
            failed.append(label)
    if failed:
        print(f"chip_smoke: beyond tolerance: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if dev.platform != "tpu":
        print("chip_smoke: rehearsal passed on "
              f"{dev.platform}; no result off a TPU", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
