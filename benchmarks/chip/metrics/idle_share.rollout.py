"""Share of the traced window in which no operation ran on the device, in
%: 1 - busy / window per device, averaged over the devices the cell uses
(each device's own share is printed, and listed in the breakdown's idle
gaps).  Read only in cells whose calls open ``rollout.call`` spans."""


def read(summary, ctx):
    if not summary.spans_named("rollout.call") or not ctx["busy"]:
        return None
    from trace_reduce import window_of
    lo, hi = window_of(summary)
    busy = ctx["busy"]
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / ((hi - lo) / 1e9))
