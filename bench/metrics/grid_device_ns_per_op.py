"""Grid program on the device: the time in which a program ran on the
device inside the benchmark's ``sweep_latency`` spans, in ns per simulated
op of the traced sweeps.  The ops are counted from the scenario (grid
cells x ops per cell), not from the implementation."""
from harness.trace_reduce import busy, covered


def read(ctx):
    tr = ctx.get("trace")
    spans = tr["spans"].get("sweep_latency", []) if tr else []
    ops = ctx.get("traced_ops", 0)
    if not spans or not tr["device_modules"] or not ops:
        return None
    b = busy(tr)
    return sum(covered(b, s, e) for s, e in spans) / ops
