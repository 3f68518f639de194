#!/usr/bin/env python
"""Benchmark: tail latency vs. memory latency under open-loop load.

The paper's Eq. 14 story is about *mean* throughput: slow memory inflates
per-op work, the thread pool hides it until the device or CPU cap bites.
This bench replays the same apparatus open-loop -- a Poisson arrival
process offers a fixed load while the memory latency sweeps -- and records
where the *tail* (P50/P99/max sojourn: arrival -> completion, queueing
included) lands at each operating point.  At low offered load the tail
tracks the service time and barely moves with memory latency; near
capacity the queue amplifies every extra microsecond of memory latency
into many microseconds of P99.  That is the Eq.-14-at-the-tail figure.

Protocol, per suite:

1. *Capacity probe*: a closed-loop sweep over the memory-latency axis at
   the suite's fixed thread count; the lowest-latency point's throughput
   is the capacity ``C``.
2. *Open-loop grid*: for each offered-load fraction (0.5 x C, 0.9 x C)
   and each memory latency, one open-loop Poisson sweep cell
   (``sweep_latency`` with an :class:`~repro.core.sim.ArrivalSpec`,
   ``collect_percentiles=True``) on the loop backend -- the exact-sorted
   percentile path, no histogram error.

Measurements land in JSON (schema ``repro.tail_latency_bench/v1``;
validated by ``tools/check_bench.py``: achieved <= offered, P99 >= P50,
>= 2 distinct offered loads).  The checked-in ``BENCH_tail_latency.json``
is produced by::

    PYTHONPATH=src python benchmarks/tail_latency_bench.py \
        --out BENCH_tail_latency.json

``--smoke`` shrinks the trace and op counts to a seconds-scale slice for
CI (same schema); ``--fig tail.png`` additionally renders the P99-vs-L
curves per offered load (matplotlib, Agg).
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys

SCHEMA = "repro.tail_latency_bench/v1"
US = 1e-6

LOAD_FRACS = (0.5, 0.9)

# Full suite: the default hash-index pairing trace, one fixed pool of 16
# threads, the paper's memory-latency axis.  Smoke: the jax_grid_bench
# smoke trace (4k keys) and a 4-point latency axis.
FULL = dict(name="tail", engine="hash-index", n_keys=30_000,
            n_wl_ops=10_000, n_ops=4000, threads=16,
            lats_us=(0.1, 0.5, 1.0, 2.0, 3.0, 5.0, 8.0, 10.0))
SMOKE = dict(name="tail-smoke", engine="hash-index", n_keys=4_000,
             n_wl_ops=1_500, n_ops=800, threads=16,
             lats_us=(0.5, 2.0, 5.0, 9.0))


def _trace(engine: str, n_keys: int, n_wl_ops: int):
    from repro.core import workloads
    from repro.core.engines import available_engines, run_trace

    store = available_engines()[engine](n_keys)
    wl = workloads.zipf(n_keys, n_wl_ops, 0.99, (1, 0), seed=3)
    return run_trace(store, wl).trace


def run_suite(suite: dict, backend: str) -> dict:
    from repro.core.sim import ArrivalSpec, SimConfig, sweep_latency

    cfg = SimConfig(P=12, seed=7)
    tr = _trace(suite["engine"], suite["n_keys"], suite["n_wl_ops"])
    lats = [l * US for l in suite["lats_us"]]
    cands = [suite["threads"]]
    n_ops = suite["n_ops"]

    closed = sweep_latency(cfg, tr, lats, cands, n_ops=n_ops,
                           backend=backend)
    capacity = float(closed[0].throughput)
    print(f"# {suite['name']}: capacity {capacity / 1e3:.1f} kops/s at "
          f"L={suite['lats_us'][0]}us x {suite['threads']} threads",
          file=sys.stderr, flush=True)

    entries = []
    for frac in LOAD_FRACS:
        rate = frac * capacity
        spec = ArrivalSpec(kind="poisson", rate=rate, seed=11)
        pts = sweep_latency(cfg, tr, lats, cands, n_ops=n_ops,
                            backend=backend, arrival=spec,
                            collect_percentiles=True)
        for l_us, pt in zip(suite["lats_us"], pts):
            s = pt.result.latency_summary
            entries.append({
                "name": suite["name"], "engine": suite["engine"],
                "L_us": l_us, "n_threads": pt.n_threads, "n_ops": n_ops,
                "offered_frac": frac,
                "offered_load": round(rate, 1),
                "achieved_load": round(float(pt.throughput), 1),
                "p50_us": round(s.p50 / US, 3),
                "p90_us": round(s.p90 / US, 3),
                "p99_us": round(s.p99 / US, 3),
                "max_us": round(s.max / US, 3),
                "count": s.count, "missed": s.missed,
                "miss_rate": round(s.miss_rate, 6),
                "source": s.source,
            })
        lo, hi = entries[-len(lats)], entries[-1]
        print(f"# {suite['name']}: load {frac:.0%} -> P99 "
              f"{lo['p99_us']:.1f}us @ {lo['L_us']}us ... "
              f"{hi['p99_us']:.1f}us @ {hi['L_us']}us",
              file=sys.stderr, flush=True)
    return {"capacity": round(capacity, 1), "entries": entries}


def render_fig(entries: list[dict], path: str) -> None:
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, ax = plt.subplots(figsize=(6, 4))
    fracs = sorted({e["offered_frac"] for e in entries})
    for frac in fracs:
        sel = sorted((e for e in entries if e["offered_frac"] == frac),
                     key=lambda e: e["L_us"])
        ax.plot([e["L_us"] for e in sel], [e["p99_us"] for e in sel],
                marker="o", label=f"P99 @ {frac:.0%} load")
        ax.plot([e["L_us"] for e in sel], [e["p50_us"] for e in sel],
                marker=".", linestyle="--", label=f"P50 @ {frac:.0%} load")
    ax.set_xlabel("memory latency L (us)")
    ax.set_ylabel("sojourn latency (us)")
    ax.set_yscale("log")
    ax.set_title("Open-loop tail vs. memory latency (Eq. 14 at the tail)")
    ax.legend(fontsize=8)
    ax.grid(True, alpha=0.3)
    fig.tight_layout()
    fig.savefig(path, dpi=110)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI slice (small trace, 800 ops)")
    ap.add_argument("--backend", default="loop", choices=("loop", "jax"),
                    help="sweep backend (default loop: exact percentiles; "
                         "jax uses the log-histogram path)")
    ap.add_argument("--out", default=None, metavar="OUT.json",
                    help="write the measurement JSON here (default: "
                         "print to stdout)")
    ap.add_argument("--fig", default=None, metavar="OUT.png",
                    help="also render the P50/P99-vs-latency figure")
    args = ap.parse_args()

    if args.backend == "jax":
        from pathlib import Path

        from repro.compile_cache import use_compile_cache

        use_compile_cache(Path(__file__).resolve().parents[1])

    suite = SMOKE if args.smoke else FULL
    res = run_suite(suite, args.backend)

    doc = {
        "schema": SCHEMA,
        "host": {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "cpu_count": os.cpu_count(),
            "python": platform.python_version(),
        },
        "backend": args.backend,
        "entries": res["entries"],
        "summary": {
            suite["name"]: {
                "capacity": res["capacity"],
                "offered_fracs": list(LOAD_FRACS),
                "n_points": len(res["entries"]),
            },
        },
    }
    text = json.dumps(doc, indent=2) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"# wrote {args.out}", file=sys.stderr)
    else:
        print(text, end="")
    if args.fig:
        render_fig(res["entries"], args.fig)


if __name__ == "__main__":
    main()
