"""The unified experiment API: workload registry, scenario specs,
run artifacts, CLI discovery flags, and matrix-sweep equivalence."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import workloads
from repro.core.engines import get_engine, run_trace
from repro.core.experiment import (
    ENGINE_DEFAULTS,
    Experiment,
    RunArtifact,
    RunOptions,
    Scenario,
    build_engine,
    default_scenario,
    run_scenario,
)
from repro.core.sim import SimConfig, sweep_latency
from repro.core.workloads import (
    available_workloads,
    create_workload,
    get_workload,
)

US = 1e-6
GOLDEN = Path(__file__).parent.parent / "examples/scenarios/hash_index_2ssd.json"

# One cheap scenario reused across tests (hash-index is the fastest tracer).
SMALL = dict(n_keys=20_000, n_wl_ops=8_000, latencies_us=(0.1, 5),
             thread_candidates=(16, 24), n_ops=1500)


class TestWorkloadRegistry:
    def test_canonical_names_and_aliases(self):
        reg = available_workloads()
        assert reg["uniform"] is workloads.uniform
        assert reg["zipf"] is workloads.zipf
        assert reg["zipfian"] is workloads.zipf
        assert reg["gaussian"] is workloads.gaussian
        assert reg["normal"] is workloads.gaussian
        assert reg["graph-cache-leader"] is workloads.graph_cache_leader
        assert reg["gcl"] is workloads.graph_cache_leader

    def test_canonical_name_stamped(self):
        assert workloads.zipf.workload_name == "zipf"
        assert get_workload("gcl").workload_name == "graph-cache-leader"

    def test_underscore_lookup(self):
        assert get_workload("graph_cache_leader") is workloads.graph_cache_leader

    def test_unknown_raises_with_known_list(self):
        with pytest.raises(KeyError, match="unknown workload"):
            get_workload("nope")

    def test_create_matches_direct_call(self):
        via_registry = create_workload("zipf", 5000, 2000, exponent=0.9,
                                       read_write=(1, 0), seed=3)
        direct = workloads.zipf(5000, 2000, 0.9, (1, 0), seed=3)
        np.testing.assert_array_equal(via_registry.keys, direct.keys)
        np.testing.assert_array_equal(via_registry.is_write, direct.is_write)


class TestScenario:
    def test_json_round_trip(self):
        s = default_scenario("lsm", n_ssd=2)
        assert Scenario.from_json(s.to_json()) == s

    def test_mixture_latency_round_trip(self):
        s = Scenario(
            engine="lsm",
            latencies_us=(0.1, ((5, 0.9), (14, 0.099), (48, 0.001)), 10),
        )
        s2 = Scenario.from_json(s.to_json())
        assert s2 == s
        assert s2.latencies_us[1] == ((5, 0.9), (14, 0.099), (48, 0.001))
        # seconds conversion keeps the scalar-or-mixture shape
        lats = s2.latencies_sec()
        assert lats[0] == pytest.approx(0.1 * US)
        assert lats[1][1] == (pytest.approx(14 * US), 0.099)

    def test_list_inputs_normalize_to_tuples(self):
        # a hand-written JSON spec (lists everywhere) equals the
        # Python-constructed scenario (tuples everywhere)
        from_lists = Scenario(engine="lsm", latencies_us=[0.1, 5],
                              thread_candidates=[16, 24],
                              workload_kwargs={"read_write": [1, 0]})
        from_tuples = Scenario(engine="lsm", latencies_us=(0.1, 5),
                               thread_candidates=(16, 24),
                               workload_kwargs={"read_write": (1, 0)})
        assert from_lists == from_tuples

    def test_unknown_field_rejected(self):
        with pytest.raises(ValueError, match="unknown Scenario field"):
            Scenario.from_dict({"engine": "lsm", "lateencies_us": [1]})

    def test_empty_axes_rejected(self):
        with pytest.raises(ValueError, match="non-empty"):
            Scenario(engine="lsm", latencies_us=())
        with pytest.raises(ValueError, match="non-empty"):
            Scenario(engine="lsm", thread_candidates=[])
        with pytest.raises(ValueError, match="n_ssd"):
            Scenario(engine="lsm", n_ssd=0)
        with pytest.raises(ValueError, match="n_ops"):
            Scenario(engine="lsm", n_ops=0)

    def test_workload_defaults_resolve_from_pairing(self):
        s = Scenario(engine="rocksdb-like")   # alias, no workload named
        wname, wkw = s.resolved_workload()
        assert s.canonical_engine == "lsm"
        assert (wname, wkw["exponent"]) == ("zipf", 0.99)
        # explicit workload wins outright
        s = Scenario(engine="lsm", workload="gcl")
        assert s.resolved_workload()[0] == "graph-cache-leader"

    def test_engine_pairings_cover_registry(self):
        for engine in ("tree-index", "lsm", "two-tier-cache", "hash-index",
                       "slab-cache"):
            assert engine in ENGINE_DEFAULTS
            kw, wname, wkw = ENGINE_DEFAULTS[engine]
            assert get_workload(wname)  # name resolves

    def test_switch_hop_only_with_multiple_ssds(self):
        one = default_scenario("hash-index", n_ssd=1).sim_config()
        two = default_scenario("hash-index", n_ssd=2).sim_config()
        assert one.L_switch == 0.0
        assert two.L_switch == pytest.approx(0.3 * US)

    def test_host_spec_round_trips_and_reaches_sim_config(self):
        """n_cores / T_lock_us are part of the device spec: they survive
        the JSON round trip and land in SimConfig (T_lock in seconds)."""
        s = Scenario(engine="lsm", n_cores=4, T_lock_us=0.1)
        s2 = Scenario.from_json(s.to_json())
        assert s2 == s and s2.n_cores == 4 and s2.T_lock_us == 0.1
        cfg = s2.sim_config()
        assert cfg.n_cores == 4
        assert cfg.T_lock == pytest.approx(0.1 * US)
        # defaults stay single-core / lock-free
        base = Scenario(engine="lsm").sim_config()
        assert base.n_cores == 1 and base.T_lock == 0.0

    def test_host_spec_validation(self):
        with pytest.raises(ValueError, match="n_cores"):
            Scenario(engine="lsm", n_cores=0)
        with pytest.raises(ValueError, match="T_lock_us"):
            Scenario(engine="lsm", T_lock_us=-0.1)
        from repro.core.sim import SimConfig

        with pytest.raises(ValueError, match="n_cores"):
            SimConfig(n_cores=0)
        with pytest.raises(ValueError, match="n_threads"):
            SimConfig(n_threads=0)
        with pytest.raises(ValueError, match="T_lock"):
            SimConfig(T_lock=-1.0)


class TestGoldenScenario:
    def test_file_matches_default_scenario(self):
        s = Scenario.from_json(GOLDEN.read_text())
        assert s == default_scenario("hash-index", n_ssd=2,
                                     name="hash_index_2ssd")

    def test_file_is_valid_json_with_canonical_names(self):
        d = json.loads(GOLDEN.read_text())
        assert d["engine"] == "hash-index"
        assert d["workload"] == "uniform"


class TestRunArtifact:
    @pytest.fixture(scope="class")
    def artifact(self):
        return run_scenario(default_scenario("hash-index", n_ssd=2, **SMALL))

    def test_fields(self, artifact):
        assert artifact.engine == "hash-index"
        assert artifact.workload == "uniform"
        assert artifact.S == pytest.approx(1.0)   # every get hits the SSD
        assert artifact.M > 0
        assert len(artifact.rows) == 2
        for row in artifact.rows:
            assert row.throughput > 0
            assert row.model_throughput > 0
            assert dict(row.per_thread).keys() == {16, 24}
            assert row.mean_op_latency_us is None   # not collected
        assert artifact.normalized()[0] == pytest.approx(1.0)

    def test_json_round_trip_is_equal(self, artifact):
        again = RunArtifact.from_json(artifact.to_json())
        assert again == artifact
        # live handles are process-local, not serialized
        assert again.points is None and again.trace_result is None
        assert artifact.points is not None

    def test_newer_schema_rejected(self, artifact):
        d = artifact.to_dict()
        d["schema_version"] = 99
        with pytest.raises(ValueError, match="schema_version"):
            RunArtifact.from_dict(d)

    def test_csv_export(self, artifact):
        lines = artifact.to_csv().strip().splitlines()
        assert lines[0].startswith("L_us,n_threads,throughput_ops")
        assert len(lines) == 1 + len(artifact.rows)
        first = lines[1].split(",")
        assert float(first[0]) == pytest.approx(0.1)
        assert float(first[4]) == pytest.approx(1.0)    # normalized base

    def test_op_params_round_trip(self, artifact):
        p = artifact.op_params()
        assert p.M == pytest.approx(artifact.M)
        assert p.T_mem == pytest.approx(artifact.T_mem_us * US)

    def test_model_column_respects_device_iops_cap(self):
        # hash-index on one 250 kIOPS SSD is IOPS-bound (S=1): the model
        # column must carry the Eq. 14 cap, not the uncapped curve
        capped = run_scenario(default_scenario("hash-index", n_ssd=1, **SMALL))
        assert capped.rows[0].model_throughput == pytest.approx(250e3)
        # the sim agrees the cap binds (sanity that the fix matters)
        assert capped.rows[0].throughput == pytest.approx(250e3, rel=0.05)
        # with two devices the aggregate cap (500k) no longer binds
        free = run_scenario(default_scenario("hash-index", n_ssd=2, **SMALL))
        assert free.rows[0].model_throughput > capped.rows[0].model_throughput
        # uncapped scenario: no R_io, pure probabilistic model
        un = run_scenario(default_scenario("hash-index", n_ssd=1, R_io=0.0,
                                           **SMALL))
        assert un.rows[0].model_throughput > 250e3

    def test_collect_latency_option(self):
        art = run_scenario(
            default_scenario("hash-index", n_ssd=2, **SMALL),
            RunOptions(collect_latency=True),
        )
        for row in art.rows:
            assert row.mean_op_latency_us is not None
            assert row.mean_op_latency_us > 0
        assert RunArtifact.from_json(art.to_json()) == art

    def test_mixture_rows_serialize(self):
        spec = dict(SMALL)
        spec["latencies_us"] = (0.1, ((5, 0.9), (14, 0.099), (48, 0.001)))
        art = run_scenario(default_scenario("hash-index", n_ssd=2, **spec))
        assert art.rows[1].L_us == ((5, 0.9), (14, 0.099), (48, 0.001))
        assert art.rows[1].mean_latency_us == pytest.approx(5.934)
        assert "Lmix" in art.rows[1].label()
        assert RunArtifact.from_json(art.to_json()) == art

    def test_loop_run_has_no_grid_records(self, artifact):
        assert artifact.grid_records == ()

    @pytest.mark.parametrize("cluster", [None, {"n_nodes": 2}])
    def test_jax_run_returns_grid_records(self, cluster):
        """Experiment.run() hands over the record of every grid call of
        the run (one per fleet node), outside equality and JSON."""
        spec = dict(SMALL)
        if cluster:
            spec["cluster"] = cluster
        sc = default_scenario("hash-index", n_ssd=2, **spec)
        art = run_scenario(sc, RunOptions(backend="jax"))
        n_cells = len(sc.latencies_us) * len(sc.thread_candidates)
        assert len(art.grid_records) == (cluster or {}).get("n_nodes", 1)
        for rec in art.grid_records:
            assert sum(c.cells for c in rec.cohorts) == n_cells
            assert 0 < rec.cell_steps_run <= rec.cell_steps_bound
        assert set(art.to_dict()) == {
            "schema_version", "scenario", "engine", "workload", "S", "M",
            "T_mem_us", "T_io_pre_us", "T_io_post_us", "hit_stats", "rows"}
        assert (art.to_dict()
                == dataclasses.replace(art, grid_records=()).to_dict())
        again = RunArtifact.from_json(art.to_json())
        assert again == art and again.grid_records == ()

    @pytest.mark.parametrize("engine", ["hash-index", "tree-index"])
    @pytest.mark.parametrize("backend", ["loop", "jax"])
    def test_engine_record(self, engine, backend):
        """The engine layer's edges are ordered and its counters are the
        recorded trace's, on either backend, outside equality and JSON."""
        art = run_scenario(default_scenario(engine, **SMALL),
                           RunOptions(backend=backend))
        rec, tr = art.engine_record, art.trace_result
        assert 0 < rec.t_build < rec.t_record < rec.t_done
        assert rec.n_ops == tr.trace.n_ops
        assert rec.n_mem / rec.n_ops == tr.mem_per_op
        assert rec.n_io / rec.n_ops == tr.io_per_op
        assert rec.max_op_subops == max(
            len(op.subops) for op in tr.trace.to_ops())
        assert "engine_record" not in art.to_dict()
        assert RunArtifact.from_json(art.to_json()) == art

    def test_loop_run_leaves_jax_unloaded(self):
        """The engine layer's profiler spans exist on the jax backend
        only: a loop run (and so every sweep worker) never imports jax."""
        code = textwrap.dedent("""
            import sys
            from repro.core.experiment import (Experiment, RunOptions,
                                               default_scenario)
            sc = default_scenario("tree-index", n_keys=4000, n_wl_ops=1500,
                                  latencies_us=(0.1, 5),
                                  thread_candidates=(16,), n_ops=300)
            art = Experiment(sc, RunOptions(processes=1)).run()
            assert art.engine_record.n_ops > 0
            print("jax" in sys.modules)
        """)
        env = dict(os.environ,
                   PYTHONPATH=str(Path(__file__).parent.parent / "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-4000:]
        assert out.stdout.strip().splitlines()[-1] == "False"

    def test_run_options_cache_dir(self, tmp_path):
        sc = default_scenario("hash-index", n_ssd=2, **SMALL)
        a = run_scenario(sc, RunOptions(cache_dir=str(tmp_path)))
        n_cells = len(sc.latencies_us) * len(sc.thread_candidates)
        assert len(list(tmp_path.glob("*.json"))) == n_cells
        b = run_scenario(sc, RunOptions(cache_dir=str(tmp_path)))
        assert a == b


class TestMatrixEquivalence:
    """The acceptance criterion: Experiment.run() on the golden scenario
    reproduces the legacy matrix-sweep protocol cell for cell."""

    def test_golden_scenario_reproduces_manual_protocol(self):
        """Bit-for-bit against a hand-rolled pre-redesign sweep (engine +
        workload built by hand, device config + sweep_latency called
        directly) -- the guarantee is real, not shim-circular."""
        sc = Scenario.from_json(GOLDEN.read_text())
        art = Experiment(sc).run()

        cls = get_engine("hash-index")
        store = cls(100_000, seed=6)
        wl = workloads.uniform(100_000, 30_000, (1, 0), seed=2)
        tr = run_trace(store, wl)
        cfg = SimConfig(n_ssd=2, R_io=250e3, L_switch=0.3 * US, P=12, seed=7)
        pts = sweep_latency(cfg, tr.trace,
                            [l * US for l in (0.1, 1, 3, 5, 8, 10)],
                            (16, 24, 32, 48, 64), n_ops=5000)

        assert art.S == tr.io_per_op and art.M == tr.mem_per_op
        assert len(art.rows) == len(pts)
        for row, pt in zip(art.rows, pts):
            assert row.throughput == pt.throughput       # bit-for-bit
            assert row.n_threads == pt.n_threads
            assert dict(row.per_thread) == pt.per_thread

    def test_matrix_sweep_shim_delegates_identically(self):
        """benchmarks.common.matrix_sweep (the deprecation-era shim) and the
        public API return the same points for the same spec."""
        from benchmarks import common

        kw = dict(l_us_list=(0.1, 5), candidates=(16, 24), nk=20_000,
                  nops=8_000, n_ops=1500)
        tr, pts = common.matrix_sweep("hash-index", n_ssd=2, **kw)
        art = Experiment(default_scenario(
            "hash-index", n_ssd=2, latencies_us=(0.1, 5),
            thread_candidates=(16, 24), n_keys=20_000, n_wl_ops=8_000,
            n_ops=1500)).run()
        assert [p.throughput for p in pts.values()] == \
            [r.throughput for r in art.rows]
        assert tr.io_per_op == art.S

    def test_engine_defaults_shim_warns_with_migration_map(self):
        from benchmarks import common

        with pytest.warns(DeprecationWarning, match="migration map"):
            legacy = common.ENGINE_DEFAULTS
        kwargs, factory = legacy["lsm"]
        wl = factory(5000, 2000)
        direct = workloads.zipf(5000, 2000, 0.99, (1, 0), seed=3)
        np.testing.assert_array_equal(wl.keys, direct.keys)

    def test_legacy_mutation_registration_still_works(self):
        # pre-redesign engine-author pattern: mutate common.ENGINE_DEFAULTS
        # to pair a new (or existing) engine with a custom default workload
        from benchmarks import common

        with pytest.warns(DeprecationWarning):
            table = common.ENGINE_DEFAULTS
        saved = table["lsm"]
        try:
            table["lsm"] = (dict(), lambda nk, nops: workloads.uniform(
                nk, nops, (1, 0), seed=42))
            with pytest.warns(DeprecationWarning):
                assert common.ENGINE_DEFAULTS["lsm"][1] is table["lsm"][1]
            _, wl = common.build_engine("lsm", 5000, 2000)
            direct = workloads.uniform(5000, 2000, (1, 0), seed=42)
            np.testing.assert_array_equal(wl.keys, direct.keys)
            # ... and matrix_sweep honors the mutated pairing too (it ran
            # through common.build_engine pre-redesign)
            tr, pts = common.matrix_sweep("lsm", l_us_list=(0.1,),
                                          candidates=(16,), nk=5000,
                                          nops=2000, n_ops=400)
            tr_direct = run_trace(
                common.build_engine("lsm", 5000, 2000)[0], direct)
            assert tr.mem_per_op == tr_direct.mem_per_op
            assert tr.io_per_op == tr_direct.io_per_op
        finally:
            table["lsm"] = saved
        # restored table: matrix_sweep is back on the scenario path
        tr2, _ = common.matrix_sweep("lsm", l_us_list=(0.1,),
                                     candidates=(16,), nk=5000, nops=2000,
                                     n_ops=400)
        assert tr2.mem_per_op != tr.mem_per_op


class TestBuildEngine:
    def test_any_registry_name(self):
        store, wl = build_engine("hash_index", 5000, 2000)
        assert type(store).engine_name == "hash-index"
        assert wl.name == "uniform" and len(wl) == 2000

    def test_unknown_engine_lists_known(self):
        with pytest.raises(KeyError, match="unknown engine"):
            build_engine("nope")


class TestCLI:
    def _main(self, argv, capsys, monkeypatch):
        import benchmarks.run as run_mod

        monkeypatch.setattr("sys.argv", ["benchmarks.run", *argv])
        run_mod.main()
        return capsys.readouterr().out

    def test_list_engines_canonical_only(self, capsys, monkeypatch):
        out = self._main(["--list-engines"], capsys, monkeypatch).split()
        assert "tree-index" in out and "hash-index" in out
        assert "aerospike-like" not in out    # aliases omitted

    def test_list_workloads_canonical_only(self, capsys, monkeypatch):
        out = self._main(["--list-workloads"], capsys, monkeypatch).split()
        assert out == ["drifting-zipf", "gaussian", "graph-cache-leader",
                       "uniform", "zipf"]

    def test_scenario_flag_runs_spec(self, capsys, monkeypatch, tmp_path):
        spec = tmp_path / "tiny.json"
        spec.write_text(default_scenario(
            "hash-index", n_ssd=2, name="tiny", **SMALL).to_json())
        art_out = tmp_path / "artifact.json"
        out = self._main(["--scenario", str(spec), "--artifact",
                          str(art_out)], capsys, monkeypatch)
        assert "scenario/tiny/L0.1us" in out
        assert "scenario/tiny/summary" in out
        art = RunArtifact.from_json(art_out.read_text())
        assert art.scenario.name == "tiny" and len(art.rows) == 2

    def test_cores_flag_reaches_scenario(self, capsys, monkeypatch):
        """--cores N is device-spec sugar like --devices: it lands in the
        scenario (and so in every cell's SimConfig) and the CSV prefix."""
        import benchmarks.run as run_mod

        seen = {}

        def fake_run(scenario, *a, prefix=None, **kw):
            seen["scenario"], seen["prefix"] = scenario, prefix

        monkeypatch.setattr(run_mod, "run_scenario_cmd", fake_run)
        self._main(["--engine", "hash-index", "--cores", "2"],
                   capsys, monkeypatch)
        assert seen["scenario"].n_cores == 2
        assert seen["scenario"].sim_config().n_cores == 2
        assert seen["prefix"].endswith("/cores2")

    def test_cores_flag_validates(self, capsys, monkeypatch):
        with pytest.raises(SystemExit, match="--cores must be >= 1"):
            self._main(["--engine", "hash-index", "--cores", "0"],
                       capsys, monkeypatch)

    def test_bad_scenario_spec_exits_with_message(self, capsys, monkeypatch,
                                                  tmp_path):
        spec = tmp_path / "bad.json"
        spec.write_text('{"engine": "lsm", "bogus_field": 1}')
        with pytest.raises(SystemExit, match="bad scenario spec"):
            self._main(["--scenario", str(spec)], capsys, monkeypatch)

    def test_unknown_engine_in_spec_exits_with_known_list(self, capsys,
                                                          monkeypatch,
                                                          tmp_path):
        # engine resolution is lazy: the spec parses, the run must still
        # exit cleanly with the registry listing (like --engine does)
        spec = tmp_path / "unknown.json"
        spec.write_text('{"engine": "hash-idx"}')
        with pytest.raises(SystemExit, match="unknown engine"):
            self._main(["--scenario", str(spec)], capsys, monkeypatch)

    def test_missing_spec_file_exits_cleanly(self, capsys, monkeypatch):
        with pytest.raises(SystemExit, match="cannot read scenario spec"):
            self._main(["--scenario", "/no/such/spec.json"], capsys,
                       monkeypatch)

    def test_engine_sugar_artifact_uses_matrix_prefix(self, capsys,
                                                      monkeypatch, tmp_path):
        art_out = tmp_path / "a.json"
        import benchmarks.run as run_mod

        monkeypatch.setattr("sys.argv", [
            "benchmarks.run", "--engine", "hash_index", "--devices", "2",
            "--artifact", str(art_out)])
        monkeypatch.setattr(
            "repro.core.experiment.default_scenario",
            lambda engine, n_ssd=1, **kw: default_scenario(
                engine, n_ssd=n_ssd, **{**SMALL, **kw}))
        run_mod.main()
        err = capsys.readouterr().err
        assert "matrix/hash_index/ssd2/artifact" in err
