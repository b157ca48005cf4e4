"""The stage arithmetic of ``spans.py``, on spans and device operations
laid out by hand (times in ns)."""
import pytest

import spans
from trace_reduce import Summary


def _summary(child_spans, ops=()):
    # two parent calls of 100 ns each; the children are given per case
    return Summary(ops={"TPU:0": sorted(ops, key=lambda o: o[1])},
                   spans=sorted([("rollout.call", 0, 100),
                                 ("rollout.call", 200, 300)]
                                + list(child_spans), key=lambda s: s[1]))


def test_child_is_clipped_to_its_parent():
    # 20 ns of the first child and 30 ns of the second lie outside
    s = _summary([("rollout.draws", -20, 40), ("rollout.draws", 270, 330)])
    assert spans.stage_ns(s, "rollout.call", ["rollout.draws"]) == \
        (40 + 30) / 2


def test_overlapping_children_count_once():
    # draws 10-50 and widen 30-70 overlap by 20 ns: the union is 60 ns
    s = _summary([("rollout.draws", 10, 50), ("rollout.widen", 30, 70)])
    assert spans.stage_ns(s, "rollout.call",
                          ["rollout.draws", "rollout.widen"]) == 60 / 2
    assert spans.stage_ns(s, "rollout.call", ["rollout.draws"]) == 40 / 2


def test_child_on_another_thread_inside_the_parent():
    # a worker thread's span opens after the parent and closes before it;
    # the trace reduction keeps no thread, so it counts like any other
    s = Summary(spans=[("gateway.window", 0, 1000),
                       ("gateway.schedule", 5, 15),
                       ("rollout.draws", 300, 360),
                       ("gateway.report", 900, 950)])
    assert spans.stage_ns(s, "gateway.window", ["rollout.draws"]) == 60
    assert spans.per_window_ms(s, ["gateway.schedule",
                                   "gateway.report"]) == 60 / 1e6


def test_device_busy_time_is_subtracted():
    # scan 10-90 in the first call, busy 20-40 and 30-60 (union 40 ns) on
    # one chip and 50-70 on another: 50 ns busy, 30 ns of launch.  The
    # second call's scan 210-260 holds no operation: all 50 ns launch.
    s = _summary([("rollout.scan", 10, 90), ("rollout.scan", 210, 260)],
                 ops=[("fusion.1", 20, 40), ("fusion.2", 30, 60),
                      ("while.1", 150, 205)])
    s.ops["TPU:1"] = [("fusion.1", 50, 70)]
    got = spans.stage_ns(s, "rollout.call", ["rollout.scan"],
                         less_device=True)
    assert got == (30 + 50) / 2
    assert spans.per_call_s(s, ["rollout.scan"]) == \
        pytest.approx((80 + 50) / 2 / 1e9)


def test_a_trace_without_the_spans_reads_none():
    s = _summary([])
    assert spans.stage_ns(s, "rollout.call", ["rollout.draws"]) is None
    assert spans.per_window_ms(s, ["gateway.ingest"]) is None
