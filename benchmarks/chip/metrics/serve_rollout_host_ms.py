"""Rollout host time per serving window: the union of the B = 1 device
call's ``rollout.draws``, ``rollout.put``, ``rollout.fetch`` and
``rollout.widen`` spans (on the gateway's worker thread) inside each
``gateway.window``, averaged over windows, in ms."""
from spans import per_window_ms

STAGES = ("rollout.draws", "rollout.put", "rollout.fetch", "rollout.widen")


def read(summary, ctx):
    return per_window_ms(summary, STAGES)
