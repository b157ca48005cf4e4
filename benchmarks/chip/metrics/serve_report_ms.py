"""Reporting per serving window: the gateway's ``gateway.report`` span
(``report()``'s served statistics, which ``serve`` returns) inside each
``gateway.window``, averaged over windows, in ms."""
from spans import per_window_ms


def read(summary, ctx):
    return per_window_ms(summary, ("gateway.report",))
