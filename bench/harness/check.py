"""What decides ``correct``: the sweep the window produced, against the
plain reference (:mod:`reference`), cell by cell.

The reference records the configuration's trace itself and simulates each
compared grid cell with the same draws as the program (``draws.Threefry``),
so a sound program reproduces it to rounding.  The numbers compared:

``trace_diff``
    suboperations whose kind or CPU time differ, plus operation
    boundaries that differ, between the program's recorded trace and the
    reference's (exact: a sound program reads 0);
``thr_med`` / ``thr_max``
    the median and the largest relative gap of a compared cell's
    throughput;
``sojourn_max``
    with arrivals, the largest relative gap of a compared cell's longest
    sojourn (the one tail statistic the grid keeps exactly);
``missed_diff`` / ``count_diff``
    with arrivals, the operations that missed the deadline and those the
    percentiles were taken over, summed absolute differences (exact);
``pct_bin_diff``
    with arrivals, how far the bin of the log-histogram that the program
    reports for p50, p90 and p99 lies from the bin that holds the
    reference's exact nearest-rank value, summed over the three and the
    compared cells (exact).

The grid compares ``COMPARE_CELLS`` of its cells, or all where it has
fewer.  Each number has a limit in ``limits/<cell>.json``; ``PERF.md``
gives the readings each limit was set from.
"""
from __future__ import annotations

import importlib
import json
import math
import random
import statistics

from .cells import BENCH, Cell

US = 1e-6
COMPARE_CELLS = 12
QUANTILES = ("p50", "p90", "p99")

# The program's sojourn histogram: bins at ratio 10**(1/64) from 0.1 us,
# 512 of them; a percentile is reported as its bin's geometric midpoint.
HIST_LO = 1e-7
HIST_BINS_PER_DECADE = 64
HIST_BINS = 512
_INV_LN_RATIO = HIST_BINS_PER_DECADE / math.log(10.0)


def hist_bin(seconds: float) -> int:
    """The histogram bin that holds a sojourn (-1 for none)."""
    if not math.isfinite(seconds):
        return -1
    b = math.floor(math.log(max(seconds, HIST_LO) / HIST_LO)
                   * _INV_LN_RATIO)
    return min(max(b, 0), HIST_BINS - 1)


def reported_bin(seconds: float) -> int:
    """The bin whose midpoint the program reports as ``seconds``."""
    if not math.isfinite(seconds):
        return -1
    return round(math.log(seconds / HIST_LO) * _INV_LN_RATIO - 0.5)


def limits_for(cell: Cell) -> dict:
    return json.loads((BENCH / "limits" / f"{cell.name}.json").read_text())


def device_of(config: dict):
    from reference.sim import Device

    sc, a = config["scenario"], config["assumed"]
    unsupported = {k: sc.get(k) for k in ("B_io", "T_lock_us")
                   if sc.get(k, 0)}
    if sc.get("n_cores", 1) != 1 or sc.get("cluster") or unsupported:
        raise ValueError("the reference models one core, no bandwidth cap, "
                         f"no lock and no cluster: {sc}")
    return Device(
        P=sc["P"], T_sw=sc["T_sw_us"] * US, L_io=a["L_io_us"] * US,
        jitter=a["L_io_jitter"], R_io=sc["R_io"], n_ssd=sc["n_ssd"],
        L_switch=sc["L_switch_us"] * US if sc["n_ssd"] > 1 else 0.0)


def arrival_count(traffic: dict) -> int:
    """Timestamps a sweep draws: the widest cell's threads, its warm-up
    (two ops a thread) and its measured ops, plus one."""
    return max(3 * c for c in traffic["thread_candidates"]) \
        + traffic["n_ops"] + 1


def pick_cells(cell: Cell, thr: dict, seed: int) -> list:
    """The compared grid cells of the traffic's grid: the one the program
    gave the lowest throughput (the longest in simulated time; a cell it
    left out comes first) and a sample drawn from ``seed``."""
    t = cell.traffic
    keys = [(float(L), int(n)) for L in t["latencies_us"]
            for n in t["thread_candidates"]]
    k = min(COMPARE_CELLS, len(keys))
    longest = min(keys, key=lambda c: thr.get(c, -math.inf))
    rest = [c for c in keys if c != longest]
    return [longest] + random.Random(seed).sample(rest, k - 1)


def program_outputs(art) -> dict:
    """The program's answers from one ``RunArtifact``."""
    from repro.core import trace_ir

    names = {trace_ir.MEM: "mem", trace_ir.PREIO: "preio",
             trace_ir.POSTIO: "postio", trace_ir.CPU: "cpu"}
    tr = art.trace_result.trace
    out = {"kinds": [names[int(k)] for k in tr.kinds],
           "durs": tr.durs.tolist(), "bounds": tr.bounds.tolist(),
           "thr": {}, "max_us": {}, "tail": {}}
    for row in art.rows:
        for n, v in row.per_thread:
            out["thr"][(float(row.L_us), int(n))] = float(v)
        t = row.tail
        if t is not None:
            c = (float(row.L_us), int(row.n_threads))
            out["max_us"][c] = (math.nan if t["max_us"] is None
                                else float(t["max_us"]))
            out["tail"][c] = {"count": int(t["count"]),
                              "missed": int(t["missed"])}
            for q in QUANTILES:
                v = t[f"{q}_us"]
                out["tail"][c][q] = reported_bin(
                    math.nan if v is None else v * US)
    return out


def reference_outputs(cell: Cell, sim_seed: int, arrival_seed: int,
                      grid_cells: list, f32: bool = False) -> dict:
    """The reference's answers for ``grid_cells`` ((L_us, threads) pairs);
    ``f32`` computes them in float32 (the check's control)."""
    from reference import draws, sim, trace

    sc = cell.scenario_dict(sim_seed, arrival_seed)
    tr = trace.record(sc)
    if f32:
        r = sim._to_f32
        tr = trace.Trace(tr.kinds, [r(d) for d in tr.durs], tr.starts,
                         tr.ends, tr.hops, tr.ios)
    dev = device_of(cell.config)
    arrivals, deadline = None, 0.0
    if sc["arrival"]:
        mod = importlib.import_module(
            f"reference.arrivals.{sc['arrival']['kind']}")
        arrivals = mod.timestamps(sc["arrival"],
                                  arrival_count(cell.traffic))
        deadline = sc["arrival"].get("deadline", 0.0)
    out = {"kinds": tr.kinds, "durs": tr.durs,
           "bounds": tr.starts + tr.ends[-1:], "thr": {}, "max_us": {},
           "tail": {}}
    for L_us, n in grid_cells:
        L = L_us * US
        res = sim.simulate(tr, dev, L, n, cell.traffic["n_ops"],
                           draws.Threefry(sim_seed, L, n),
                           arrivals=arrivals, deadline=deadline, f32=f32)
        out["thr"][(L_us, n)] = res["throughput"]
        if arrivals is not None:
            out["max_us"][(L_us, n)] = res["max"] / US
            out["tail"][(L_us, n)] = dict(
                count=res["count"], missed=res["missed"],
                **{q: hist_bin(res[q]) for q in QUANTILES})
    return out


def _rel(ref: float, got: float) -> float:
    if not (math.isfinite(ref) and math.isfinite(got)) or ref == 0.0:
        return math.inf
    return abs(got - ref) / abs(ref)


def numbers(got: dict, ref: dict) -> dict:
    """The compared numbers of ``got`` (the program, or the control in its
    place) against ``ref``, over the grid cells ``ref`` holds."""
    a, b = got["kinds"], ref["kinds"]
    diff = sum(x != y for x, y in zip(a, b))
    diff += sum(x != y for x, y in zip(got["durs"], ref["durs"]))
    diff += sum(x != y for x, y in zip(got["bounds"], ref["bounds"]))
    diff += abs(len(a) - len(b))
    diff += abs(len(got["bounds"]) - len(ref["bounds"]))
    rels = [_rel(v, got["thr"].get(c, math.nan))
            for c, v in ref["thr"].items()]
    out = {"trace_diff": float(diff),
           "thr_med": statistics.median(rels),
           "thr_max": max(rels)}
    if ref["max_us"]:
        out["sojourn_max"] = max(_rel(v, got["max_us"].get(c, math.nan))
                                 for c, v in ref["max_us"].items())
        absent = {"count": -1, "missed": -1,
                  **{q: -HIST_BINS for q in QUANTILES}}
        tails = [(t, got["tail"].get(c, absent))
                 for c, t in ref["tail"].items()]
        for name, keys in (("missed_diff", ["missed"]),
                           ("count_diff", ["count"]),
                           ("pct_bin_diff", QUANTILES)):
            out[name] = float(sum(abs(g[k] - t[k])
                                  for t, g in tails for k in keys))
    return out


def verdict(nums: dict, limits: dict) -> bool:
    """Every number at or under its limit (a NaN or a missing limit
    fails)."""
    return all(k in limits and v <= limits[k] for k, v in nums.items())
