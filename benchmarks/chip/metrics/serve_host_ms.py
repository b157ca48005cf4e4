"""Host time per serving window: each ``gateway.window`` span's wall time
less the union of device busy time inside it (scheduling, ingest, the
worker-thread hop, transfers and collect), averaged over windows, in ms."""
from trace_reduce import all_ops, busy_ns


def read(summary, ctx):
    spans = summary.spans_named("gateway.window")
    if not spans:
        return None
    ops = all_ops(summary)
    host = [(e - s) - busy_ns(ops, s, e) for _, s, e in spans]
    return sum(host) / len(host) / 1e6
