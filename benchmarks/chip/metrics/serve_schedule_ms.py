"""Scheduling per serving window: the gateway's ``gateway.schedule`` span
(packing the queue into the window's arrival tensor) inside each
``gateway.window``, averaged over windows, in ms."""
from spans import per_window_ms


def read(summary, ctx):
    return per_window_ms(summary, ("gateway.schedule",))
