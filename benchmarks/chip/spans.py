"""Host time of the program's own stages inside each benchmark call.

The program opens host spans inside the calls the benchmark wraps:
``FleetRollout.run`` opens ``rollout.draws``, ``rollout.put``,
``rollout.scan``, ``rollout.fetch`` and ``rollout.widen``;
``StreamingGateway.serve`` opens ``gateway.schedule``, ``gateway.ingest``
and ``gateway.report``, and its B = 1 device call opens the five
``rollout.*`` spans on the gateway's worker thread.  For each parent span
of the benchmark (``rollout.call``, ``gateway.window``) a stage is the
union of its child spans clipped to the parent, on any thread, optionally
less the time any device is busy inside that union.  A trace of a program
without these spans has no children, and every stage reads None.
"""
from __future__ import annotations

from typing import Iterable, Optional

from trace_reduce import all_ops, busy_ns, union


def stage_ns(summary, parent: str, children: Iterable[str],
             less_device: bool = False) -> Optional[float]:
    """Mean over the ``parent`` spans of the time the ``children`` spans
    cover inside each, in ns; with ``less_device``, less the union of
    device operations (of all devices) inside that cover.  None when the
    trace has no such parent or no such child."""
    names = set(children)
    parents = summary.spans_named(parent)
    kids = [s for s in summary.spans if s[0] in names]
    if not parents or not kids:
        return None
    busy = union(all_ops(summary)) if less_device else []
    total = 0
    for _, lo, hi in parents:
        for s, e in union(kids, lo, hi):
            total += e - s
            if less_device:
                total -= busy_ns([b for b in busy if b[0] < e and b[1] > s],
                                 s, e)
    return total / len(parents)


def per_call_s(summary, children, less_device: bool = False):
    """``stage_ns`` per ``rollout.call``, in seconds."""
    ns = stage_ns(summary, "rollout.call", children, less_device)
    return None if ns is None else ns / 1e9


def per_window_ms(summary, children, less_device: bool = False):
    """``stage_ns`` per ``gateway.window``, in milliseconds."""
    ns = stage_ns(summary, "gateway.window", children, less_device)
    return None if ns is None else ns / 1e6
