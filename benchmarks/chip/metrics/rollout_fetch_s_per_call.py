"""Device-to-host copy per ``FleetRollout.run`` call: the program's
``rollout.fetch`` span (``np.asarray`` of the twelve output stacks) inside
each ``rollout.call``, averaged over calls, in s."""
from spans import per_call_s


def read(summary, ctx):
    return per_call_s(summary, ("rollout.fetch",))
