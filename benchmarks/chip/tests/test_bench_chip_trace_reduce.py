"""The trace reduction, on intervals counted by hand and on a trace that
one traced run of ``serve-lenet-split`` recorded on a TPU v5e (one
serving window, ``data/serve_window.xplane.pb``)."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "serve_window.xplane.pb"


def test_union_gaps_and_self_times_by_hand():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 20, 40),
           ("copy.1", 60, 70), ("fusion.3", 150, 160)]
    assert tr.union(ops) == [(0, 100), (150, 160)]
    assert tr.busy_ns(ops) == 110
    assert tr.busy_ns(ops, 50, 155) == 55
    assert tr.gaps(tr.union(ops, 0, 200), 0, 200) == [(100, 150), (160, 200)]
    own = tr.self_times(ops, 0, 200)
    # while.1 holds fusion.1 (20), fusion.2 (20, overlapping fusion.1 by
    # 10, nested under it as the trace nests them) and copy.1 (10)
    assert own["fusion.3"] == 10 and own["copy.1"] == 10
    assert own["fusion.1"] + own["while.1"] + own["fusion.2"] == 100 - 10


def test_span_at_takes_the_innermost_open_span():
    s = tr.Summary(spans=[("bench.window", 0, 100), ("gateway.window", 10, 50),
                          ("bench.check", 60, 80)])
    assert tr.span_at(s, 20) == "gateway.window"
    assert tr.span_at(s, 55) == "outside spans"
    assert tr.span_at(s, 70) == "bench.check"


def test_serve_window_by_hand():
    """One serving window of ``serve-lenet-split`` traced on a TPU v5e
    (``run_cell.run(..., trace_out=..., traffic_overrides={"trace_seconds":
    0.001})``, trimmed by ``trim_trace.py``).  Hand-read from the raw
    trace: ``bench.window`` from 47,103,788 ns for 17,117,239 ns;
    ``gateway.window`` from 47,117,578 ns for 16,685,359 ns; one
    ``jit_rollout`` module from 51,315,140 ns for 3,376,854 ns holding
    9825 ``XLA Ops`` events, the first from 51,315,424 ns, the latest
    ending at 54,691,994 ns; 8 ``while.175`` events (P2's descent, one a
    frame) summing 1,862,603 ns."""
    s = tr.reduce_file(DATA)
    assert s.spans == [("bench.window", 47103788, 47103788 + 17117239),
                       ("gateway.window", 47117578, 47117578 + 16685359)]
    assert tr.window_of(s) == (47103788, 47103788 + 17117239)
    ops = s.ops["TPU:0"]
    assert len(ops) == 9825 and ops[0][1] == 51315424
    assert max(e for _, _, e in ops) == 54691994
    busy = tr.device_busy(s)["TPU:0"]
    # the ops fill their one module but for the gaps between them
    assert 0.99 * 3376854e-9 <= busy <= (54691994 - 51315424) * 1e-9
    idle = 1.0 - busy / (17117239 * 1e-9)
    assert 0.79 < idle < 0.81
    lo, hi = tr.window_of(s)
    own = tr.self_times(ops, lo, hi)
    assert sum(own.values()) == round(busy * 1e9)
    w175 = [o for o in ops if o[0] == "while.175"]
    assert len(w175) == 8 and sum(e - b for _, b, e in w175) == 1862603
    bd = tr.breakdown(s)
    # idle before the device call and after it, inside the window's span
    assert bd["idle_gaps"][0][0] in ("TPU:0 in gateway.window",
                                     "TPU:0 in outside spans")
    assert sum(d for _, d in bd["device_ops"]) <= busy + 1e-12


KERNEL = Path(__file__).resolve().parent / "data" / "kernel_rollout.xplane.pb"


@pytest.fixture(scope="module")
def kernel_trace():
    return tr.reduce_file(KERNEL)


def test_kernel_trace_by_hand(kernel_trace):
    """A B = 1024, T = 4 ``FleetRollout.run`` call of the AlexNet
    deployment through the Pallas kernels, traced on a TPU v5e and
    trimmed by ``trim_trace.py``.  Hand-read from the raw trace: 7643
    ``XLA Ops`` events, the first starting at 57,759,456 ns and the latest
    ending at 269,802,952 ns (a ``while``), inside one ``jit_rollout`` module of
    212,044,410 ns from 57,759,174 ns; the ``rollout.call`` span from
    51,874,708 ns to 279,494,961 ns; 44 ``tropical_dp_step`` launches
    summing 175,575,757 ns; 4 ``link_geometry`` launches summing
    127,970 ns."""
    s = kernel_trace
    assert s.spans == [("rollout.call", 51874708, 279494961)]
    ops = s.ops["TPU:0"]
    assert len(ops) == 7643
    assert ops[0][1] == 57759456 and max(e for _, _, e in ops) == 269802952
    busy = tr.busy_ns(ops, 51874708, 279494961)
    # the ops fill their module but for the short gaps between them
    assert 0.999 * 212044410 <= busy <= 269802952 - 57759456
    own = tr.self_times(ops, 51874708, 279494961)
    assert sum(own.values()) == busy
    dp = [o for o in ops if o[0].startswith("tropical_dp_step.")]
    assert len(dp) == 44 and sum(e - b for _, b, e in dp) == 175575757
    geo = [o for o in ops if o[0].startswith("link_geometry.")]
    assert len(geo) == 4 and sum(e - b for _, b, e in geo) == 127970


def test_kernel_rooflines_from_the_trace(kernel_trace):
    import work_counts
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    got = work_counts.roofline(kernel_trace, {"peak": peak},
                               ("tropical_dp_step",),
                               work_counts.tropical_dp(1024, 8, 11, 8))
    least = work_counts.tropical_dp(1024, 8, 11, 8)["bytes"] / 819e9
    assert got["bound"] == "bytes" and got["launches"] == 44
    assert got["value"] == pytest.approx(100 * 44 * least / 0.175575757)
    assert got["value"] < 1.0
