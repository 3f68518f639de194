"""KV-store engines: semantics, traces, and model agreement (O4)."""
import numpy as np
import pytest
from _hypothesis_support import given, settings, st  # optional-hypothesis shim

from repro.core import workloads
from repro.core.kvstore import (
    EngineTimes,
    LSMStore,
    Recorder,
    TreeIndexStore,
    TwoTierCacheStore,
    run_trace,
)
from repro.core.latency_model import US, theta_mask_inv, theta_prob_inv
from repro.core.simulator import MEM, PREIO, SimConfig, simulate, trace_source

NK = 50_000
NOPS = 20_000


@pytest.fixture(scope="module")
def tree_trace():
    store = TreeIndexStore(NK, seed=1)
    wl = workloads.uniform(NK, NOPS, (1, 0), seed=2)
    return store, run_trace(store, wl)


class TestTreeIndexStore:
    def test_all_keys_found(self):
        store = TreeIndexStore(1000, seed=0)
        rec = Recorder(store.times)
        for k in range(0, 1000, 37):
            assert store._walk(k, rec)

    def test_absent_keys_not_found(self):
        store = TreeIndexStore(1000, seed=0)
        rec = Recorder(store.times)
        for k in range(1000, 1100):
            assert not store._walk(k, rec)

    def test_depth_is_logarithmic(self, tree_trace):
        _, tr = tree_trace
        # random BST expected depth ~1.39 log2(n/sprigs); n/sprig ~ 195
        expect = 1.39 * np.log2(NK / 256)
        assert 0.5 * expect < tr.mem_per_op - 1 < 1.8 * expect

    def test_one_io_per_read(self, tree_trace):
        _, tr = tree_trace
        assert tr.io_per_op == pytest.approx(1.0, abs=0.01)


def _sequential_build(n_keys: int, n_sprigs: int, seed: int):
    """Plain reference: insert the keys one at a time, as the engine's draws
    order them, into per-sprig BSTs (nodes numbered by insertion order)."""
    order = np.random.default_rng(seed).permutation(n_keys)
    sprig_of = (
        (np.arange(n_keys, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15))
        % np.uint64(n_sprigs)
    ).astype(np.int64)
    key = np.empty(n_keys, dtype=np.int64)
    left = np.full(n_keys, -1, dtype=np.int64)
    right = np.full(n_keys, -1, dtype=np.int64)
    root = [-1] * n_sprigs
    for i, k in enumerate(order.tolist()):
        key[i] = k
        s = int(sprig_of[k])
        cur = root[s]
        if cur < 0:
            root[s] = i
            continue
        while True:
            side = left if k < key[cur] else right
            if side[cur] < 0:
                side[cur] = i
                break
            cur = side[cur]
    return key, left, right, root


class TestTreeBulkLoad:
    """The numpy bulk load builds the trees that one insert per key builds."""

    @pytest.mark.parametrize("seed", [0, 1, 12345])
    @pytest.mark.parametrize(
        "n_keys,n_sprigs",
        [(1, 256), (7, 1), (1000, 256), (300, 4096), (100_000, 256)],
    )
    def test_equals_sequential_insert(self, n_keys, n_sprigs, seed):
        store = TreeIndexStore(n_keys, n_sprigs=n_sprigs, seed=seed)
        key, left, right, root = _sequential_build(n_keys, n_sprigs, seed)
        np.testing.assert_array_equal(store.key, key)
        np.testing.assert_array_equal(store.left, left)
        np.testing.assert_array_equal(store.right, right)
        assert store.root == root
        assert all(type(r) is int for r in store.root)
        # every node but the roots has exactly one parent
        children = np.concatenate([store.left, store.right])
        n_parents = np.bincount(children[children >= 0], minlength=n_keys)
        is_root = np.zeros(n_keys, dtype=bool)
        is_root[[r for r in store.root if r >= 0]] = True
        np.testing.assert_array_equal(n_parents, np.where(is_root, 0, 1))
        # an in-order walk of each sprig yields its keys, sorted
        for s, r in enumerate(store.root):
            walked, stack, cur = [], [], r
            while stack or cur >= 0:
                while cur >= 0:
                    stack.append(cur)
                    cur = int(store.left[cur])
                cur = stack.pop()
                walked.append(int(store.key[cur]))
                cur = int(store.right[cur])
            np.testing.assert_array_equal(
                walked, np.flatnonzero(store.sprig_of == s)
            )

    @pytest.mark.parametrize("read_write", [(1, 0), (1, 1)])
    def test_trace_equals_sequential_insert(self, read_write):
        n_keys, seed = 10_000, 1
        wl = workloads.uniform(n_keys, 3000, read_write, seed=2)
        bulk = run_trace(TreeIndexStore(n_keys, seed=seed), wl).trace
        ref_store = TreeIndexStore(n_keys, seed=seed)
        (ref_store.key, ref_store.left, ref_store.right,
         ref_store.root) = _sequential_build(n_keys, ref_store.n_sprigs, seed)
        ref = run_trace(ref_store, wl).trace
        for col in ("kinds", "durs", "bounds"):
            np.testing.assert_array_equal(getattr(bulk, col), getattr(ref, col))


class TestLSMStore:
    def test_zipf_hit_ratio(self):
        store = LSMStore(NK)
        wl = workloads.zipf(NK, NOPS, 0.99, seed=3)
        tr = run_trace(store, wl)
        assert 0.3 < tr.hit_stats["block_cache"] < 0.9
        # io per op == miss ratio (reads only)
        assert tr.io_per_op == pytest.approx(
            1 - store.hit_ratio, abs=0.1
        )

    def test_less_skew_more_io(self):
        t_hi = run_trace(LSMStore(NK), workloads.zipf(NK, NOPS, 0.99, seed=3))
        t_lo = run_trace(LSMStore(NK), workloads.zipf(NK, NOPS, 0.5, seed=3))
        assert t_lo.io_per_op > t_hi.io_per_op


class TestTwoTierCacheStore:
    def test_hit_stats(self):
        store = TwoTierCacheStore(NK, seed=4)
        wl = workloads.gaussian(NK, NOPS, 0.08, (2, 1), seed=5)
        tr = run_trace(store, wl)
        hs = tr.hit_stats
        assert 0.05 < hs["tier1"] < 0.95
        assert 0 <= hs["tier2"] <= 1
        assert tr.io_per_op > 0  # misses + eviction flushes reach the SSD

    def test_capacity_conservation(self):
        store = TwoTierCacheStore(2000, tier1_items=100, tier2_items=300, seed=0)
        wl = workloads.uniform(2000, 5000, (2, 1), seed=1)
        run_trace(store, wl, warmup_frac=0.0)
        assert len(store.t1) <= 100
        assert len(store.t2) <= 300


class TestModelAgreement:
    """O4: the Theta_prob model explains the engines' simulated throughput
    far better than masking-only, across the latency sweep."""

    @pytest.mark.parametrize("which", ["tree", "lsm", "cache"])
    def test_prob_closer_than_mask(self, which):
        if which == "tree":
            store = TreeIndexStore(NK, seed=1)
            wl = workloads.uniform(NK, NOPS, (1, 0), seed=2)
        elif which == "lsm":
            store = LSMStore(NK)
            wl = workloads.zipf(NK, NOPS, 0.99, seed=3)
        else:
            store = TwoTierCacheStore(NK, seed=4)
            wl = workloads.gaussian(NK, NOPS, 0.08, (2, 1), seed=5)
        tr = run_trace(store, wl)
        p = tr.op_params(store.times, P=12, T_sw=0.05 * US)
        src = trace_source(tr.ops)
        for l_us in (5.0, 8.0):
            best = max(
                simulate(SimConfig(L_mem=l_us * US, P=12, n_threads=n, seed=7),
                         src, 6000).throughput
                for n in (24, 40, 56)
            )
            L = np.array([l_us * US])
            prob = 1 / theta_prob_inv(L, p)[0]
            mask = 1 / theta_mask_inv(L, p)[0]
            err_prob = abs(best - prob) / prob
            err_mask = abs(best - mask) / mask
            assert err_prob < 0.25
            assert err_prob <= err_mask + 0.02


class TestRecorder:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 8), st.booleans()), min_size=1,
                    max_size=40))
    def test_counts_match_subops(self, plan):
        rec = Recorder(EngineTimes())
        for n_mem, io in plan:
            rec.mem(n_mem) if n_mem else rec.cpu(1e-7)
            if io:
                rec.io()
            rec.end_op()
        assert rec.n_ops == len(plan)
        n_mem = sum(1 for op in rec.ops for k, _ in op.subops if k == MEM)
        n_pre = sum(1 for op in rec.ops for k, _ in op.subops if k == PREIO)
        assert n_mem == rec.n_mem
        assert n_pre == rec.n_io
