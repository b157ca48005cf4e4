"""Launch per ``FleetRollout.run`` call: the program's ``rollout.scan``
span (the device call, to its end) inside each ``rollout.call``, less the
time any chip is busy inside it: the dispatch, and any wait for input
transfers that the device sits through.  Averaged over calls, in s."""
from spans import per_call_s


def read(summary, ctx):
    return per_call_s(summary, ("rollout.scan",), less_device=True)
