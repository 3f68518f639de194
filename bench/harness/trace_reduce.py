"""From a profiler trace to device busy time, idle gaps and span times.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps, as plain data that can be saved and reduced again:

* ``device_modules``: every program execution on a ``/device:`` plane
  (its ``XLA Modules`` line), as ``[name, start_ns, end_ns]``;
* ``device_op_totals``: the device seconds of each operation on the
  ``XLA Ops`` lines, summed by the operation's HLO name, and
  ``device_op_events``, how many such events the trace holds;
* ``spans``: the host spans the benchmark opened
  (``jax.profiler.TraceAnnotation``), by name, as ``[start_ns, end_ns]``.

Busy time is the union of the module executions.  The operation lines are
only summed: a scan of tens of thousands of steps emits millions of
operation events, and the profiler drops trace buffers past its limit, so
an operation line can be incomplete where the module line is whole.  Host
and device events share the trace's clock.  The reductions below work on
the extracted data only, so ``tests/`` checks them on a recorded trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

#: The spans the benchmark opens, innermost first: the label an idle gap
#: gets is the innermost of these that covers it.
SPANS = ("run_trace", "sweep_latency", "experiment", "bench_sweep")
GAP_LABELS = {"run_trace": "engine_trace",
              "sweep_latency": "sweep_dispatch_and_reduction",
              "experiment": "experiment_host",
              "bench_sweep": "between_sweeps"}


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, totals, n_ops = [], defaultdict(float), 0
    spans = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.extend([e.name, e.start_ns, e.end_ns]
                                   for e in line.events)
                elif line.name == "XLA Ops":
                    for e in line.events:
                        # "%fusion.12 = f32[..] fusion(...)": keep the name
                        totals[e.name.split(" = ", 1)[0]] += (
                            e.duration_ns * 1e-9)
                        n_ops += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans[e.name].append([e.start_ns, e.end_ns])
    return {"device_modules": modules, "device_op_totals": dict(totals),
            "device_op_events": n_ops, "spans": dict(spans)}


def union(intervals) -> list:
    """Merge ``[start, end]`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged: list, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged: list, lo: float, hi: float) -> list:
    """The uncovered pieces of ``[lo, hi]``."""
    out, t = [], lo
    for s, e in merged:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append([t, min(s, hi)])
        t = max(t, e)
    if t < hi:
        out.append([t, hi])
    return out


def label(t: float, spans: dict) -> str:
    for name in SPANS:
        if any(s <= t <= e for s, e in spans.get(name, ())):
            return GAP_LABELS[name]
    return "outside_sweeps"


def window_of(tr: dict):
    """The traced window: first sweep's start to the last sweep's end."""
    sw = tr["spans"].get("bench_sweep", [])
    if not sw:
        return None
    return min(s for s, _ in sw), max(e for _, e in sw)


def busy(tr: dict) -> list:
    """Disjoint intervals in which a program ran on the device."""
    return union([s, e] for _, s, e in tr["device_modules"])


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps in the traced window with what the host was doing in each
    (seconds)."""
    ops = sorted(tr["device_op_totals"].items(), key=lambda kv: -kv[1])
    idle = []
    win = window_of(tr)
    if win is not None:
        g = sorted(gaps(busy(tr), *win), key=lambda x: x[0] - x[1])
        idle = [[label((s + e) / 2, tr["spans"]), (e - s) * 1e-9]
                for s, e in g[:top]]
    return {"device_ops": [[n, v] for n, v in ops[:top]],
            "idle_gaps": idle}
