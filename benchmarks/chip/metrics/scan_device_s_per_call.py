"""Device time per ``FleetRollout.run`` call: the union of device
operations inside each ``rollout.call`` span (the frame scan and its
planning ticks), averaged over calls."""
from trace_reduce import all_ops, busy_ns


def read(summary, ctx):
    spans = summary.spans_named("rollout.call")
    ops = all_ops(summary)
    if not spans or not ops:
        return None
    return sum(busy_ns(ops, s, e) for _, s, e in spans) / len(spans) / 1e9
