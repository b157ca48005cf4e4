"""Launch per serving window: the B = 1 device call's ``rollout.scan``
span inside each ``gateway.window``, less the time the chip is busy
inside it (the dispatch, and any wait for input transfers), averaged over
windows, in ms."""
from spans import per_window_ms


def read(summary, ctx):
    return per_window_ms(summary, ("rollout.scan",), less_device=True)
