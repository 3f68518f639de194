"""Engine build on the host: the mean, over the traced window's
``experiment`` spans, of each span's length less the ``run_trace`` and
``sweep_latency`` spans inside it (ms): the engine's bulk load and the
workload's key stream, plus packaging the artifact."""
from harness.trace_reduce import covered, union


def read(ctx):
    tr = ctx.get("trace")
    spans = tr["spans"] if tr else {}
    runs = spans.get("experiment", [])
    traces = spans.get("run_trace", [])
    sweeps = spans.get("sweep_latency", [])
    if not (runs and traces and sweeps):
        return None
    inner = union(traces + sweeps)
    self_ns = [(e - s) - covered(inner, s, e) for s, e in runs]
    return sum(self_ns) / len(self_ns) * 1e-6
