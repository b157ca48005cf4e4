"""Ingest per serving window: the gateway's ``gateway.ingest`` span
(pulling the window's arrivals through ``submit``) inside each
``gateway.window``, averaged over windows, in ms."""
from spans import per_window_ms


def read(summary, ctx):
    return per_window_ms(summary, ("gateway.ingest",))
