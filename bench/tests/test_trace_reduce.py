"""The reduction from profiler trace to per-layer metrics, checked on a
small recorded profile (``data/cpu_spans.xplane.pb``) and on intervals
small enough to work out by hand."""
import importlib.util

import pytest

from harness import trace_reduce as tr
from harness.cells import BENCH, load_spec

DATA = BENCH / "tests" / "data" / "cpu_spans.xplane.pb"


def metric(name):
    s = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def test_union_covered_gaps():
    merged = tr.union([[5, 7], [0, 2], [1, 3], [6, 9]])
    assert merged == [[0, 3], [5, 9]]
    assert tr.covered(merged, 2, 6) == 2
    assert tr.gaps(merged, -1, 10) == [[-1, 0], [3, 5], [9, 10]]


def _toy():
    return {"device_modules": [["a", 10, 40], ["b", 30, 60], ["c", 80, 90]],
            "device_op_totals": {"%x": 2.0, "%y": 5.0},
            "device_op_events": 7,
            "spans": {"bench_sweep": [[0, 100]], "experiment": [[0, 100]],
                      "run_trace": [[0, 10]], "sweep_latency": [[10, 95]]}}


def test_metrics_on_hand_made_trace():
    t = _toy()
    ctx = {"trace": t, "traced_ops": 10, "compiles_in_window": 0}
    assert metric("device_idle_share")(ctx) == pytest.approx(40.0)
    assert metric("grid_device_ns_per_op")(ctx) == pytest.approx(6.0)
    assert metric("trace_record_ms")(ctx) == pytest.approx(10e-6)
    assert metric("compiles_in_window")(ctx) == 0
    b = tr.breakdown(t)
    assert b["device_ops"] == [["%y", 5.0], ["%x", 2.0]]
    assert b["idle_gaps"][0] == ["sweep_dispatch_and_reduction",
                                 pytest.approx(20e-9)]
    assert b["idle_gaps"][1][0] == "engine_trace"


def test_nothing_to_read_is_none():
    empty = {"device_modules": [], "device_op_totals": {},
             "device_op_events": 0, "spans": {}}
    for name in ("device_idle_share", "grid_device_ns_per_op",
                 "trace_record_ms"):
        assert metric(name)({"trace": empty, "traced_ops": 1}) is None
        assert metric(name)({"trace": None}) is None


def test_recorded_trace():
    """A profile recorded on the CPU with the benchmark's spans: two sweeps
    of nested ``bench_sweep`` > ``experiment`` > ``run_trace`` /
    ``sweep_latency``.  It has no device plane, so the device readers find
    nothing and return None; device executions placed inside the
    recorded ``sweep_latency`` spans are then reduced against them."""
    t = tr.extract(str(DATA))
    assert t["device_modules"] == [] and t["device_op_events"] == 0
    spans = t["spans"]
    assert {k: len(v) for k, v in spans.items()} == {
        "bench_sweep": 2, "experiment": 2, "run_trace": 2,
        "sweep_latency": 2}
    ctx = {"trace": t, "traced_ops": 100, "compiles_in_window": 0}
    assert metric("device_idle_share")(ctx) is None
    assert metric("grid_device_ns_per_op")(ctx) is None
    assert metric("trace_record_ms")(ctx) == pytest.approx(
        sum(e - s for s, e in spans["run_trace"]) / 2 * 1e-6)
    # one device execution filling each sweep_latency span
    t["device_modules"] = [["m", s, e] for s, e in spans["sweep_latency"]]
    lo, hi = tr.window_of(t)
    busy = sum(e - s for s, e in spans["sweep_latency"])
    assert metric("device_idle_share")(ctx) == pytest.approx(
        100.0 * (1.0 - busy / (hi - lo)))
    assert metric("grid_device_ns_per_op")(ctx) == pytest.approx(busy / 100)
    labels = {g[0] for g in tr.breakdown(t)["idle_gaps"]}
    assert "engine_trace" in labels
    assert labels <= set(tr.GAP_LABELS.values())


def test_extract_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones(8)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench_sweep"):
            with jax.profiler.TraceAnnotation("run_trace"):
                f(x).block_until_ready()
    t = tr.extract(tr.find_xplane(str(tmp_path)))
    assert len(t["spans"]["bench_sweep"]) == 1
    (s, e), = t["spans"]["run_trace"]
    assert t["spans"]["bench_sweep"][0][0] <= s <= e


def test_every_metric_has_a_reader():
    for m in load_spec()["per_layer"]:
        assert callable(metric(m["name"]))
