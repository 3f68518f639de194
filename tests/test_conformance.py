"""``repro.core.conformance`` -- the differential fuzzer and its
contract table.

Three layers, cheapest first:

1. The contract table and tolerance scaling laws are pure data/math --
   checked exhaustively (the table is what ``tests/test_replay_jax.py``
   and ``tests/test_cluster.py`` import their bounds from, so its
   internal consistency is itself a contract).
2. Scenario sampling and shrinking are deterministic plumbing -- checked
   with a stubbed ``check_scenario`` so no simulation runs.
3. The checked-in corpus under ``examples/conformance/`` must parse,
   stay within the sampler's size budget, and (slow) replay green
   through the real differential checks -- the same gate CI's nightly
   fuzz job enforces.
"""
import json
import random
from pathlib import Path

import pytest

from repro.core import conformance
from repro.core.conformance import (
    CHECK_NAMES,
    CONTRACTS,
    ConformanceFailure,
    jax_grid_tol,
    sample_scenario,
    scenario_for_seed,
    shrink_scenario,
    tail_tol,
    write_repro,
    replay_corpus,
)
from repro.core.experiment import Scenario

ROOT = Path(__file__).resolve().parent.parent
CORPUS = ROOT / "examples" / "conformance"


# -- 1. contract table + tolerance scaling -----------------------------------


class TestContractTable:
    def test_keys_match_contract_names(self):
        for key, c in CONTRACTS.items():
            assert key == c.name

    def test_bit_identical_contracts_carry_no_tolerances(self):
        for c in CONTRACTS.values():
            if c.bit_identical:
                assert c.throughput_tol is None
                assert c.p50_tol is None and c.p99_tol is None

    def test_tolerance_contracts_fully_specified(self):
        for c in CONTRACTS.values():
            if not c.bit_identical:
                assert c.throughput_tol and c.ref_ops
                assert c.p50_tol and c.p99_tol and c.tail_ref_ops
                assert c.p50_tol <= c.p99_tol   # medians are tighter

    def test_every_contract_documents_why(self):
        assert all(c.why for c in CONTRACTS.values())

    def test_all_backend_pairs_covered(self):
        # every distinct execution path pairs off against a reference
        flat = " ".join(part for c in CONTRACTS.values() for part in c.pair)
        for backend in ("simulate", "simulate_compiled", "sweep_grid",
                        "sweep_cluster"):
            assert backend in flat

    def test_jax_grid_tol_is_base_at_and_above_ref(self):
        c = CONTRACTS["jax-vs-loop"]
        assert jax_grid_tol(c.ref_ops) == pytest.approx(c.throughput_tol)
        assert jax_grid_tol(10 * c.ref_ops) == pytest.approx(
            c.throughput_tol)

    def test_tolerance_scales_as_inverse_sqrt_below_ref(self):
        # quartering the sample doubles the allowed noise
        assert jax_grid_tol(5_000) == pytest.approx(2 * jax_grid_tol(20_000))
        assert tail_tol(100, base=0.12) == pytest.approx(
            2 * tail_tol(400, base=0.12))

    def test_slack_is_multiplicative(self):
        assert jax_grid_tol(5_000, slack=1.25) == pytest.approx(
            1.25 * jax_grid_tol(5_000))

    def test_existing_test_literals_map_onto_the_law(self):
        # the historical per-test bounds are points on one curve
        assert jax_grid_tol(5_000) == pytest.approx(0.02)
        assert jax_grid_tol(20_000) == pytest.approx(0.01)
        assert jax_grid_tol(5_000, slack=1.25) == pytest.approx(0.025)


# -- 2. sampling + shrinking (no simulation) ---------------------------------


class TestSampling:
    def test_seed_determinism(self):
        for seed in (0, 7, 41):
            assert scenario_for_seed(seed) == scenario_for_seed(seed)

    def test_seeds_explore_the_space(self):
        scs = [scenario_for_seed(s) for s in range(30)]
        assert len({sc.to_json() for sc in scs}) == 30
        assert len({sc.engine for sc in scs}) >= 5
        assert any(sc.cluster for sc in scs)
        assert any(sc.arrival for sc in scs)
        assert any(not sc.arrival and not sc.cluster for sc in scs)

    def test_samples_stay_within_the_size_budget(self):
        # the documented budget that keeps a differential pass cheap
        for seed in range(30):
            sc = scenario_for_seed(seed)
            assert sc.n_keys <= 3_000 and sc.n_wl_ops <= 1_000
            assert sc.n_ops <= 600
            assert len(sc.latencies_us) * len(sc.thread_candidates) <= 4
            if sc.cluster:
                assert sc.cluster["n_nodes"] <= 4

    def test_samples_round_trip_through_json(self):
        for seed in range(10):
            sc = scenario_for_seed(seed)
            assert Scenario.from_json(sc.to_json()) == sc

    def test_sample_scenario_consumes_rng(self):
        rng = random.Random(1)
        a = sample_scenario(rng, 0)
        b = sample_scenario(rng, 0)
        assert a != b                       # stream advances


class TestShrinker:
    @staticmethod
    def _fails_if(pred):
        def stub(sc, checks=CHECK_NAMES):
            if pred(sc):
                return [ConformanceFailure("jax", "stub", "fail", sc)]
            return []
        return stub

    def test_shrinks_to_minimal_failing_spec(self, monkeypatch):
        # failure depends only on n_ops >= 300: the shrinker must keep
        # halving while the failure persists and stop at the boundary
        monkeypatch.setattr(conformance, "check_scenario",
                            self._fails_if(lambda sc: sc.n_ops >= 300))
        sc = scenario_for_seed(2)
        assert sc.n_ops >= 300
        small, evals = shrink_scenario(sc)
        assert small.n_ops == 300
        assert 0 < evals <= 40
        assert small.name.endswith("-shrunk")
        # everything irrelevant to the failure was stripped
        assert not small.cluster and not small.arrival
        assert len(small.latencies_us) == 1
        assert len(small.thread_candidates) == 1

    def test_budget_bounds_evaluations(self, monkeypatch):
        monkeypatch.setattr(conformance, "check_scenario",
                            self._fails_if(lambda sc: True))
        _, evals = shrink_scenario(scenario_for_seed(2), budget=5)
        assert evals <= 5

    def test_unshrinkable_failure_keeps_the_spec(self, monkeypatch):
        # a failure that vanishes under ANY reduction cannot be shrunk
        full = scenario_for_seed(2)
        monkeypatch.setattr(conformance, "check_scenario",
                            self._fails_if(lambda sc: sc == full))
        small, _ = shrink_scenario(full)
        assert small == full


class TestReproEmission:
    def test_write_repro_round_trips(self, tmp_path):
        sc = scenario_for_seed(3)
        path = write_repro(sc, "jax", tmp_path)
        assert path.name == f"repro_jax_{sc.name}.json"
        assert Scenario.from_json(path.read_text()) == sc

    def test_replay_corpus_empty_dir_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            replay_corpus(tmp_path)

    def test_replay_corpus_runs_every_file(self, tmp_path, monkeypatch):
        seen = []
        monkeypatch.setattr(
            conformance, "check_scenario",
            lambda sc, checks=CHECK_NAMES: seen.append(sc.name) or [])
        for seed in (1, 2):
            write_repro(scenario_for_seed(seed), "jax", tmp_path)
        assert replay_corpus(tmp_path) == []
        assert len(seen) == 2


# -- 3. the checked-in corpus ------------------------------------------------


def _corpus_paths():
    return sorted(CORPUS.glob("*.json"))


class TestCorpus:
    def test_corpus_is_nonempty_and_parses(self):
        paths = _corpus_paths()
        assert len(paths) >= 8
        names = set()
        for path in paths:
            sc = Scenario.from_json(path.read_text())
            names.add(sc.name)
            # corpus specs obey the sampler's size budget: replay stays
            # cheap enough to run on every CI push
            assert sc.n_ops <= 600 and sc.n_keys <= 3_000
        assert len(names) == len(paths)

    def test_corpus_covers_the_fuzz_axes(self):
        scs = [Scenario.from_json(p.read_text()) for p in _corpus_paths()]
        kinds = {dict(sc.arrival).get("kind", "closed") if sc.arrival
                 else "closed" for sc in scs}
        assert {"closed", "poisson", "bursty", "diurnal"} <= kinds
        assert sum(1 for sc in scs if sc.cluster) >= 3
        assert len({sc.engine for sc in scs}) >= 6

    def test_cheapest_corpus_entry_replays_green(self):
        # tier-1 smoke: the smallest single-host spec through the full
        # differential pass (compiled + jax)
        scs = [(p, Scenario.from_json(p.read_text()))
               for p in _corpus_paths()]
        path, sc = min(
            ((p, s) for p, s in scs if not s.cluster),
            key=lambda ps: ps[1].n_ops * ps[1].n_wl_ops)
        fails = conformance.check_scenario(sc)
        assert not fails, f"{path.name}: {[str(f) for f in fails]}"

    @pytest.mark.slow
    def test_full_corpus_replays_green(self):
        fails = replay_corpus(CORPUS)
        assert not fails, [str(f) for f in fails]
