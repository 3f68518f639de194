#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out FILE]

In one process that holds the chip: for each of ``--seeds``, the cell
with its data drawn from that seed as in ``run.py``, one sweep through
the timed path (compiling the seed's programs where they are new), compared
with the reference exactly as a run compares it (the lower readings), and
for each of ``--control-seeds`` the control: the reference computed in
float32 put in the program's place (the upper readings).  Prints one JSON
line per reading.  It is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench  # noqa: E402
from harness import check  # noqa: E402
from harness.cells import derive_seed, load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    bench.import_program()
    from repro.compile_cache import use_compile_cache

    use_compile_cache(bench.CHECKOUT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    out = open(args.out, "a") if args.out else sys.stdout
    spans = bench.Spans(False)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds:
        sweeper = bench.Sweeper(cell.for_seed(seed), seed, spans)
        art, bad = sweeper.run(0)
        got = check.program_outputs(art)
        picked = check.pick_cells(sweeper.cell, got["thr"],
                                  derive_seed(seed, "cells"))
        t = time.perf_counter()
        ref = check.reference_outputs(sweeper.cell, *sweeper.seeds(0),
                                      picked)
        line = {"cell": cell.name, "seed": seed, "kind": "program",
                "failed": bad, "reference_s": time.perf_counter() - t,
                "numbers": check.numbers(got, ref)}
        print(json.dumps(line), file=out, flush=True)
        if seed in controls:
            ctl = check.reference_outputs(sweeper.cell, *sweeper.seeds(0),
                                          picked, f32=True)
            line = {"cell": cell.name, "seed": seed, "kind": "control",
                    "numbers": check.numbers(ctl, ref)}
            print(json.dumps(line), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
