"""BENCHMARK.json read as data: every cell resolves, names and units keep
to the allowed characters, and the command refuses to run off a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from harness.cells import BENCH, CHECKOUT, TRAFFIC_KEYS, load_cell, load_spec

SPEC = load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|experts_per_token")


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        body = json.loads((CHECKOUT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in body["scenario"] and key in body["reduced"]


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert e2e == {"sim_ops_per_s", "setup_s"}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.0 < m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] == "sim_ops_per_s"
        assert _line(m["layer"])
        assert set(m["workloads"]) <= set(CELLS)
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    from repro.core.experiment import Scenario

    cell = load_cell(name)
    assert set(cell.traffic) == TRAFFIC_KEYS
    sc = Scenario.from_dict(cell.scenario_dict(11, 12))
    assert sc.seed == 11 and len(sc.latencies_us) * len(
        sc.thread_candidates) == cell.n_grid_cells
    assert (BENCH / "limits" / f"{name}.json").is_file()
    assert cell.chips == 1


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "4294967297", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    last = p.stdout.strip().splitlines()[-1:] or [""]
    return p.returncode != 0 and not last[0].startswith("{")


def test_refuses_without_tpu():
    assert _no_result(_run(CHECKOUT, {"JAX_PLATFORMS": "cpu"}))


def test_refuses_without_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_run(tmp_path, {"JAX_PLATFORMS": "cpu"}))
