"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: each device's operation intervals and the benchmark's own
host spans, on one clock.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction (the text before
`` = `` in the event's name, without ``%``: ``fusion.12``, ``while.3``,
``tropical_dp_step.8``).  A ``while`` or ``call`` event spans the
operations of its body, so busy time is a union of intervals and an
operation's own time leaves out the operations nested in it.  A trace
taken on the CPU has no device plane; there the events that carry an
``hlo_op`` stat on the host's XLA threads stand in for one device,
``CPU:0``, so the reduction can be rehearsed without a chip.  Host spans are the host events whose names start with
one of ``SPAN_PREFIXES``: the ``jax.profiler.TraceAnnotation`` spans the
benchmark opens around its calls into each layer.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIXES = ("rollout.", "gateway.", "bench.")
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclass
class Summary:
    """``ops``: device -> [(op name, start_ns, end_ns)] sorted by start;
    ``spans``: [(span name, start_ns, end_ns)] sorted by start."""

    ops: Dict[str, List[Interval]] = field(default_factory=dict)
    spans: List[Interval] = field(default_factory=list)

    def spans_named(self, name: str) -> List[Interval]:
        return [s for s in self.spans if s[0] == name]


def reduce_file(path: str) -> Summary:
    """Read one ``.xplane.pb`` and reduce it."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


def reduce_profile(pd) -> Summary:
    out = Summary()
    cpu_ops: List[Interval] = []
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append((name, s, s + int(ev.duration_ns)))
            out.ops[f"TPU:{m.group(1)}"] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                xla_thread = line.name.startswith("tf_XLA")
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name.startswith(SPAN_PREFIXES):
                        out.spans.append((ev.name, s,
                                          s + int(ev.duration_ns)))
                    elif xla_thread and ev.duration_ns > 0 and \
                            any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append((ev.name, s, s + int(ev.duration_ns)))
    if not out.ops and cpu_ops:
        out.ops["CPU:0"] = sorted(cpu_ops, key=lambda o: o[1])
    out.spans.sort(key=lambda s: s[1])
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, lo: int = None, hi: int = None) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals, clipped to [lo, hi] when given."""
    merged: List[List[int]] = []
    for iv in sorted(intervals, key=lambda iv: iv[-2]):
        s, e = iv[-2], iv[-1]
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals, lo: int = None, hi: int = None) -> int:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def all_ops(summary: Summary) -> List[Interval]:
    """Every device's operations together."""
    return [op for ops in summary.ops.values() for op in ops]


def gaps(busy: List[Tuple[int, int]], lo: int, hi: int):
    """The idle stretches [start, end) of [lo, hi] between busy
    intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(summary: Summary, t: int, exclude=("bench.window",)) -> str:
    """Name of the innermost benchmark span open at time ``t`` (the one
    that started last), or ``outside spans``."""
    best = None
    for name, s, e in summary.spans:
        if name in exclude or not s <= t < e:
            continue
        if best is None or s >= best[1]:
            best = (name, s)
    return best[0] if best else "outside spans"


def window_of(summary: Summary) -> Tuple[int, int]:
    """The traced window: the ``bench.window`` span."""
    w = summary.spans_named("bench.window")
    if not w:
        raise ValueError("trace has no bench.window span")
    return w[0][1], w[0][2]


def self_times(ops: List[Interval], lo: int, hi: int) -> Dict[str, int]:
    """Each operation's own time inside [lo, hi], summed by name: its
    duration less that of the operations nested directly in it."""
    tot: Dict[str, int] = {}
    stack: List[list] = []                  # [name, end, own]

    def close(entry):
        tot[entry[0]] = tot.get(entry[0], 0) + entry[2]

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        d = max(0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][2] -= d
        stack.append([name, e, d])
    while stack:
        close(stack.pop())
    return tot


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The device operations that took most time (own time, summed over
    devices) and the longest idle gaps, each named by the device and the
    benchmark span open when it began."""
    lo, hi = window_of(summary)
    tot: Dict[str, int] = {}
    for ops in summary.ops.values():
        for name, d in self_times(ops, lo, hi).items():
            tot[name] = tot.get(name, 0) + d
    dev_ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    for dev, ops in summary.ops.items():
        for s, e in gaps(union(ops, lo, hi), lo, hi):
            idle.append((f"{dev} in {span_at(summary, s)}", e - s))
    idle = sorted(idle, key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, d / 1e9] for n, d in dev_ops],
            "idle_gaps": [[n, d / 1e9] for n, d in idle]}


def device_busy(summary: Summary) -> Dict[str, float]:
    """Seconds each device was busy inside the traced window."""
    lo, hi = window_of(summary)
    return {dev: busy_ns(ops, lo, hi) / 1e9
            for dev, ops in summary.ops.items()}
