"""Host-to-device placement per ``FleetRollout.run`` call: the program's
``rollout.put`` span (``jnp.asarray``, or mesh padding and the sharded
``device_put``) inside each ``rollout.call``, averaged over calls, in s."""
from spans import per_call_s


def read(summary, ctx):
    return per_call_s(summary, ("rollout.put",))
