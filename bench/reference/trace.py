"""The engine trace: each operation's suboperations, as the engines emit them.

An engine executes each key of the workload against its own data
structures and records what one operation costs: ``mem`` for a hop on
slow memory (with the compute attached to it), ``io`` for an SSD access
(a submission and a completion check), and ``cpu`` for plain compute that
does not yield.  The first ``warmup_frac`` of the key stream only warms
the structures up; the rest is the trace that the scheduler replays.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass

__all__ = ["MEM", "PREIO", "POSTIO", "CPU", "US", "Times", "Trace",
           "Recorder", "record"]

MEM, PREIO, POSTIO, CPU = "mem", "preio", "postio", "cpu"
US = 1e-6


@dataclass(frozen=True)
class Times:
    """CPU seconds of the engines' suboperations (the paper's calibration:
    0.1 us of compute per slow-memory hop, 1.5 us to submit an IO and
    0.2 us to complete it, 0.05 us for a DRAM-side probe and 0.3 us to
    (de)serialize a value)."""

    t_mem: float = 0.10 * US
    t_io_pre: float = 1.5 * US
    t_io_post: float = 0.2 * US
    t_probe: float = 0.05 * US
    t_value: float = 0.3 * US


@dataclass(frozen=True)
class Trace:
    kinds: list      # one of MEM / PREIO / POSTIO / CPU per suboperation
    durs: list       # CPU seconds per suboperation
    starts: list     # flat index of each operation's first suboperation
    ends: list       # flat index one past its last
    hops: int        # slow-memory hops in the trace
    ios: int         # SSD accesses in the trace


class Recorder:
    def __init__(self, times: Times):
        self.t = times
        self.kinds: list = []
        self.durs: list = []
        self.bounds = [0]
        self.hops = 0
        self.ios = 0

    def mem(self, n: int = 1) -> None:
        self.kinds += [MEM] * n
        self.durs += [self.t.t_mem] * n
        self.hops += n

    def cpu(self, t: float) -> None:
        if t > 0.0:
            self.kinds.append(CPU)
            self.durs.append(t)

    def io(self, pre_extra: float = 0.0, post_extra: float = 0.0) -> None:
        self.kinds += [PREIO, POSTIO]
        self.durs += [self.t.t_io_pre + pre_extra,
                      self.t.t_io_post + post_extra]
        self.ios += 1

    def end_op(self) -> None:
        if self.bounds[-1] == len(self.kinds):   # an op is never empty
            self.cpu(self.t.t_probe)
        self.bounds.append(len(self.kinds))


def _module(kind: str, name: str):
    return importlib.import_module(
        f"reference.{kind}.{name.replace('-', '_')}")


def record(scenario: dict) -> Trace:
    """The trace of a configuration's ``scenario`` block: its key stream
    (``reference/workloads/<workload>.py``) run through its engine
    (``reference/engines/<engine>.py``)."""
    n_keys, n_ops = scenario["n_keys"], scenario["n_wl_ops"]
    keys, writes = _module("workloads", scenario["workload"]).stream(
        n_keys, n_ops, **scenario["workload_kwargs"])
    times = Times()
    store = _module("engines", scenario["engine"]).Store(
        n_keys, times, **scenario["engine_kwargs"])
    n_warm = int(n_ops * scenario["warmup_frac"])
    warm = Recorder(times)
    for k, w in zip(keys[:n_warm], writes[:n_warm]):
        store.op(k, w, warm)
    rec = Recorder(times)
    for k, w in zip(keys[n_warm:], writes[n_warm:]):
        store.op(k, w, rec)
    b = rec.bounds
    return Trace(rec.kinds, rec.durs, b[:-1], b[1:], rec.hops, rec.ios)
