"""Engine trace recording on the host: the mean length (ms) of the
benchmark's ``run_trace`` spans in the traced window, one per sweep."""


def read(ctx):
    tr = ctx.get("trace")
    spans = tr["spans"].get("run_trace", []) if tr else []
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e-6
