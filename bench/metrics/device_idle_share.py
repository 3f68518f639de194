"""The device: the share (%) of the traced window, first sweep's start to
the last one's end, in which no program ran on the device."""
from harness.trace_reduce import busy, covered, window_of


def read(ctx):
    tr = ctx.get("trace")
    win = window_of(tr) if tr else None
    if win is None or not tr["device_modules"] or win[1] <= win[0]:
        return None
    return 100.0 * (1.0 - covered(busy(tr), *win) / (win[1] - win[0]))
