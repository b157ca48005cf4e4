"""Device time per serving window: the union of device operations inside
each ``gateway.window`` span (the B = 1 frame scan), averaged over
windows, in ms."""
from trace_reduce import all_ops, busy_ns


def read(summary, ctx):
    spans = summary.spans_named("gateway.window")
    ops = all_ops(summary)
    if not spans or not ops:
        return None
    return sum(busy_ns(ops, s, e) for _, s, e in spans) / len(spans) / 1e6
