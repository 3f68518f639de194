#!/usr/bin/env python
"""Benchmark: sharded-fleet scenarios through ``repro.core.cluster``.

The single-host benches measure one node.  This bench runs the three
fleet situations a sharded SSD-backed KV service actually meets, on the
cluster pipeline (``Scenario.cluster`` -> ``sweep_cluster``), and records
per-node *and* fleet-wide tails under open-loop load:

``hot_shard`` / ``hot_shard_drift``
    Zipf mass concentrates on whichever shard owns the hottest keys (the
    drift variant sharpens the skew across the op stream via the
    ``drifting-zipf`` workload).  Replication 2 with the ``spread`` read
    policy shows replicas absorbing part of the hot shard's read load.
``degraded_node``
    One node's SSD clocks slow mid-run (``io_degrade`` onset at
    ``T_degrade_us``): its tail detaches from the healthy nodes' while
    the fleet percentiles blend both populations.
``migration``
    A shard handover under load: at ``at_frac`` of the op stream, shard
    0's ops start executing on node 2, which then serves two shards.

Protocol, per scenario: a closed-loop capacity probe (lowest-latency
fleet throughput at the suite thread count) fixes ``C``; one open-loop
Poisson sweep at ``LOAD_FRAC x C`` with ``collect_percentiles=True``
produces the entries.  Both phases run through the public
:class:`~repro.core.experiment.Experiment` API, so this bench also
exercises the ``Scenario.cluster`` wiring end to end.

Measurements land in JSON (schema ``repro.cluster_bench/v1``; validated
by ``tools/check_bench.py``: fleet and per-node achieved <= offered,
ordered fleet percentiles, shares summing to 1, and the degraded-node
entry present).  The checked-in ``BENCH_cluster.json`` is produced by::

    PYTHONPATH=src python benchmarks/cluster_bench.py --out BENCH_cluster.json

``--smoke`` shrinks traces and op counts to a seconds-scale CI slice
(same schema); ``--scenario NAME`` restricts to one scenario;
``--backend jax`` replays the per-node cells on the vectorized grid
(fleet tails then come from merged log-histograms, ``source: "hist"``).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

SCHEMA = "repro.cluster_bench/v1"
US = 1e-6

#: Offered load as a fraction of the probed fleet capacity.
LOAD_FRAC = 0.7

FULL_SIZE = dict(n_keys=30_000, n_wl_ops=12_000, n_ops=4000,
                 latencies_us=(0.5, 2.0, 5.0, 8.0),
                 thread_candidates=(8, 16))
SMOKE_SIZE = dict(n_keys=4_000, n_wl_ops=2_000, n_ops=800,
                  latencies_us=(1.0, 5.0), thread_candidates=(16,))

#: Every fleet scenario routes through a 4-node hash-partitioned cluster
#: behind a 5 us router hop; the scenarios differ in workload skew and
#: per-node state.
N_NODES = 4
L_ROUTE_US = 5.0


def _scenario(name: str, smoke: bool, workload: str, workload_kwargs: dict,
              cluster_extra: dict | None = None):
    from repro.core.experiment import Scenario

    size = SMOKE_SIZE if smoke else FULL_SIZE
    cluster = dict(n_nodes=N_NODES, partition="hash",
                   L_route_us=L_ROUTE_US, **(cluster_extra or {}))
    return Scenario(
        engine="hash-index", engine_kwargs={"seed": 6},
        workload=workload, workload_kwargs=workload_kwargs,
        cluster=cluster, name=name, seed=7, P=12, **size)


def hot_shard(smoke: bool):
    """Static Zipf skew; replication 2 + spread reads shave the hot shard."""
    return _scenario(
        "hot_shard", smoke, "zipf",
        {"exponent": 1.2, "read_write": (1, 0), "seed": 3},
        {"replication": 2, "replica_policy": "spread"})


def hot_shard_drift(smoke: bool):
    """Skew sharpening over the op stream (drifting-zipf), primary reads."""
    return _scenario(
        "hot_shard_drift", smoke, "drifting-zipf",
        {"exponent0": 0.6, "exponent1": 1.4, "read_write": (1, 0),
         "seed": 3})


def degraded_node(smoke: bool):
    """Node 1's SSD clocks slow 4x partway into each cell's virtual run."""
    t_degrade_us = 1_000.0 if smoke else 4_000.0
    return _scenario(
        "degraded_node", smoke, "uniform",
        {"read_write": (1, 0), "seed": 2},
        {"node_overrides": {
            "1": {"io_degrade": 4.0, "T_degrade_us": t_degrade_us}}})


def migration(smoke: bool):
    """Shard 0 hands over to node 2 at 50% of the op stream, under load."""
    return _scenario(
        "migration", smoke, "zipf",
        {"exponent": 1.1, "read_write": (1, 0), "seed": 3},
        {"migrate": {"shard": 0, "to": 2, "at_frac": 0.5}})


#: name -> builder(smoke) for every fleet scenario this bench ships (also
#: the registry behind ``benchmarks.run --list-cluster-scenarios``).
SCENARIOS = {
    "hot_shard": hot_shard,
    "hot_shard_drift": hot_shard_drift,
    "degraded_node": degraded_node,
    "migration": migration,
}


def _degraded_nodes(scenario) -> set[int]:
    return {int(k) for k, ov in scenario.cluster.get(
        "node_overrides", {}).items()
        if float(ov.get("io_degrade", 1.0)) != 1.0}


def _tail_us(tail: dict, field: str) -> float | None:
    v = tail[field]
    return None if v is None else round(v, 3)


def run_scenario(name: str, smoke: bool, backend: str) -> dict:
    import dataclasses

    from repro.core.experiment import Experiment, RunOptions

    scenario = SCENARIOS[name](smoke)
    probe = Experiment(scenario, RunOptions(backend=backend)).run()
    capacity = float(probe.rows[0].throughput)
    rate = LOAD_FRAC * capacity
    print(f"# {name}: fleet capacity {capacity / 1e3:.1f} kops/s at "
          f"L={scenario.latencies_us[0]}us -> offering {LOAD_FRAC:.0%}",
          file=sys.stderr, flush=True)

    open_sc = dataclasses.replace(
        scenario, arrival={"kind": "poisson", "rate": rate, "seed": 11})
    art = Experiment(
        open_sc, RunOptions(backend=backend, collect_percentiles=True),
    ).run()

    degraded = _degraded_nodes(scenario)
    migrate = bool(scenario.cluster.get("migrate"))
    entries = []
    for row in art.rows:
        t = row.tail
        # Fleet achieved load = completed ops / fleet makespan (the fleet
        # is done when its slowest node is).  The artifact's fleet
        # throughput sums per-node rates, which overstates the open-loop
        # rate when migration time-concentrates a node's window.
        active = [nd for nd in row.nodes if nd["n_ops"] > 0]
        achieved = (sum(nd["n_ops"] for nd in active)
                    / max(nd["time"] for nd in active))
        nodes = []
        for nd in row.nodes:
            nt = nd["tail"]
            nodes.append({
                "node": nd["node"],
                "share": round(nd["share"], 6),
                "degraded": nd["node"] in degraded,
                "n_ops": nd["n_ops"],
                "offered_load": round(nt["offered_load"], 1),
                "achieved_load": round(nd["throughput"], 1),
                "p50_us": _tail_us(nt, "p50_us"),
                "p90_us": _tail_us(nt, "p90_us"),
                "p99_us": _tail_us(nt, "p99_us"),
                "max_us": _tail_us(nt, "max_us"),
                "count": nt["count"], "missed": nt["missed"],
            })
        entries.append({
            "name": name, "engine": scenario.engine, "backend": backend,
            "n_nodes": N_NODES, "L_us": row.L_us,
            "n_threads": row.n_threads, "n_ops": scenario.n_ops,
            "migrate": migrate,
            "offered_frac": LOAD_FRAC,
            "offered_load": round(rate, 1),
            "achieved_load": round(achieved, 1),
            "p50_us": _tail_us(t, "p50_us"),
            "p90_us": _tail_us(t, "p90_us"),
            "p99_us": _tail_us(t, "p99_us"),
            "max_us": _tail_us(t, "max_us"),
            "count": t["count"], "missed": t["missed"],
            "miss_rate": round(t["miss_rate"], 6),
            "source": t["source"],
            "nodes": nodes,
        })
    lo, hi = entries[0], entries[-1]
    hot = max(entries[0]["nodes"], key=lambda n: n["share"])
    print(f"# {name}: fleet P99 {lo['p99_us']:.1f}us @ {lo['L_us']}us ... "
          f"{hi['p99_us']:.1f}us @ {hi['L_us']}us "
          f"(hottest shard: node {hot['node']} at {hot['share']:.0%})",
          file=sys.stderr, flush=True)
    return {
        "capacity": round(capacity, 1),
        "entries": entries,
        "summary": {
            "capacity": round(capacity, 1),
            "offered_frac": LOAD_FRAC,
            "n_points": len(entries),
            "n_nodes": N_NODES,
            "hottest_share": hot["share"],
            "degraded_nodes": sorted(degraded),
            "migrate": migrate,
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI slice (small traces, 800 ops)")
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="run one fleet scenario (default: all)")
    ap.add_argument("--backend", default="loop",
                    choices=("loop", "generic", "jax"),
                    help="per-node cell backend (default loop: exact "
                         "fleet percentiles; jax merges log-histograms)")
    ap.add_argument("--out", default=None, metavar="OUT.json",
                    help="write the measurement JSON here (default: "
                         "print to stdout)")
    args = ap.parse_args()

    if args.backend == "jax":
        from pathlib import Path

        from repro.compile_cache import use_compile_cache

        use_compile_cache(Path(__file__).resolve().parents[1])

    names = [args.scenario] if args.scenario else sorted(SCENARIOS)
    entries, summary = [], {}
    for name in names:
        res = run_scenario(name, args.smoke, args.backend)
        entries += res["entries"]
        summary[name] = res["summary"]

    doc = {
        "schema": SCHEMA,
        "host": {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "cpu_count": os.cpu_count(),
            "python": platform.python_version(),
        },
        "backend": args.backend,
        "smoke": bool(args.smoke),
        "entries": entries,
        "summary": summary,
    }
    text = json.dumps(doc, indent=2) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"# wrote {args.out}", file=sys.stderr)
    else:
        print(text, end="")


if __name__ == "__main__":
    main()
