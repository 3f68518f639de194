"""The engine layer's readers, on hand-made spans over two sweeps: each
``experiment`` span holds a ``run_trace`` span and a ``sweep_latency``
span, and what is left of it is the engine's build and packaging."""
import pytest

from test_trace_reduce import metric


def _two_sweeps():
    # sweep 1: experiment 0-100, run_trace 30-40, sweep_latency 40-90
    # sweep 2: experiment 110-190, run_trace 120-140, sweep_latency 140-180
    return {"device_modules": [["m", 45, 85], ["m", 145, 175]],
            "device_op_totals": {}, "device_op_events": 0,
            "spans": {"bench_sweep": [[0, 105], [105, 200]],
                      "experiment": [[0, 100], [110, 190]],
                      "run_trace": [[30, 40], [120, 140]],
                      "sweep_latency": [[40, 90], [140, 180]]}}


def test_engine_build_ms():
    # self time 100 - 10 - 50 = 40 and 80 - 20 - 40 = 20 ns: mean 30 ns
    assert metric("engine_build_ms")({"trace": _two_sweeps()}) == \
        pytest.approx(30e-6)


def test_engine_host_share():
    # (100 + 80) - (50 + 40) = 90 ns of a 200-ns window
    assert metric("engine_host_share")({"trace": _two_sweeps()}) == \
        pytest.approx(45.0)


# the spans each reader needs
NEEDS = {"engine_build_ms": {"experiment", "run_trace", "sweep_latency"},
         "engine_host_share": {"experiment", "sweep_latency", "bench_sweep"}}


@pytest.mark.parametrize("missing", ["experiment", "run_trace",
                                     "sweep_latency", "bench_sweep"])
@pytest.mark.parametrize("name", sorted(NEEDS))
def test_missing_spans_read_none(name, missing):
    t = _two_sweeps()
    del t["spans"][missing]
    got = metric(name)({"trace": t})
    assert (got is None) == (missing in NEEDS[name])
    t["spans"] = {}
    assert metric(name)({"trace": t}) is None
    assert metric(name)({"trace": None}) is None
    assert metric(name)({}) is None
