"""A cell of ``BENCHMARK.json`` as data: its configuration and its traffic.

A configuration is ``configs/<config>.json``; its ``scenario`` block is a
plain ``repro.core.experiment.Scenario`` in JSON, and the rest says where
the deployment comes from, what was cut and what was assumed.  A traffic
mix is ``traffic/<traffic>.json``: the sweep axes, the simulated ops per
grid cell, the arrival process (empty for a closed loop) and whether tails
are collected.  Nothing here knows any particular cell.

A run's data comes from its ``--seed``: :meth:`Cell.for_seed` replaces
every ``seed`` that the configuration's scenario gives its engine or its
workload (the key stream, the table's layout) with one derived from it.
"""
from __future__ import annotations

import json
import zlib
from dataclasses import dataclass, replace
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
CHECKOUT = BENCH.parent

TRAFFIC_KEYS = {"why", "latencies_us", "thread_candidates", "n_ops",
                "arrival", "collect_percentiles"}


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict       # the configuration file
    traffic: dict      # the traffic file

    def for_seed(self, seed: int) -> "Cell":
        """This cell with the engine's and the workload's seeds derived
        from the run's ``seed``."""
        sc = dict(self.config["scenario"])
        for part in ("engine_kwargs", "workload_kwargs"):
            kw = sc.get(part) or {}
            if "seed" in kw:
                sc[part] = dict(kw, seed=derive_seed(seed, part))
        return replace(self, config=dict(self.config, scenario=sc))

    def scenario_dict(self, sim_seed: int, arrival_seed: int) -> dict:
        """The scenario one sweep runs: the configuration's scenario with
        the traffic's axes, and the seeds of this sweep."""
        sc = dict(self.config["scenario"])
        t = self.traffic
        sc.update(latencies_us=t["latencies_us"],
                  thread_candidates=t["thread_candidates"],
                  n_ops=t["n_ops"], seed=sim_seed,
                  arrival=(dict(t["arrival"], seed=arrival_seed)
                           if t["arrival"] else {}))
        return sc

    @property
    def n_grid_cells(self) -> int:
        return (len(self.traffic["latencies_us"])
                * len(self.traffic["thread_candidates"]))

    @property
    def ops_per_sweep(self) -> int:
        return self.n_grid_cells * self.traffic["n_ops"]


def load_spec(root: Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic {w['traffic']!r}: unknown keys "
                         f"{sorted(unknown)}")
    return Cell(name, int(w["chips"]), config, traffic)


def derive_seed(seed: int, *tags) -> int:
    """A 31-bit seed for one use of the run's ``--seed`` (any integer)."""
    text = ":".join(str(x) for x in (seed, *tags))
    return zlib.crc32(text.encode()) & 0x7FFFFFFF
