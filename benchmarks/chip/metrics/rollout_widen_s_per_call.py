"""Widening per ``FleetRollout.run`` call: the program's ``rollout.widen``
span (the [T, B] to [B, T] swap, the float64/int64 casts, the
``RolloutTrace``) inside each ``rollout.call``, averaged over calls, in
s."""
from spans import per_call_s


def read(summary, ctx):
    return per_call_s(summary, ("rollout.widen",))
