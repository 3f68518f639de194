"""Engine layer on the host: the share (%) of the traced window in which
an ``experiment`` span is open and no ``sweep_latency`` span is, so the
host builds the engine and records its trace and nothing is queued on the
device: 100 x (sum of ``experiment`` - sum of ``sweep_latency``) over the
window, first sweep's start to the last one's end."""
from harness.trace_reduce import window_of


def read(ctx):
    tr = ctx.get("trace")
    win = window_of(tr) if tr else None
    runs = tr["spans"].get("experiment", []) if tr else []
    sweeps = tr["spans"].get("sweep_latency", []) if tr else []
    if win is None or win[1] <= win[0] or not runs or not sweeps:
        return None
    host = sum(e - s for s, e in runs) - sum(e - s for s, e in sweeps)
    return 100.0 * host / (win[1] - win[0])
