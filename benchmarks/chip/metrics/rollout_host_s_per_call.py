"""Host time per ``FleetRollout.run`` call: each ``rollout.call`` span's
wall time less the union of device busy time inside it (host draws,
transfers, the trace's copy back and widening), averaged over calls."""
from trace_reduce import all_ops, busy_ns


def read(summary, ctx):
    spans = summary.spans_named("rollout.call")
    if not spans:
        return None
    ops = all_ops(summary)
    host = [(e - s) - busy_ns(ops, s, e) for _, s, e in spans]
    return sum(host) / len(host) / 1e9
