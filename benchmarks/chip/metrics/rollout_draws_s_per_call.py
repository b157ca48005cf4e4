"""Host draws per ``FleetRollout.run`` call: the program's
``rollout.draws`` span (NumPy draws, input validation, initial state)
inside each ``rollout.call``, averaged over calls, in s."""
from spans import per_call_s


def read(summary, ctx):
    return per_call_s(summary, ("rollout.draws",))
