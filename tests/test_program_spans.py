"""The program's host spans, read back from a CPU profile by the
benchmark's trace reduction: ``FleetRollout.run`` opens ``rollout.draws``,
``rollout.put``, ``rollout.scan``, ``rollout.fetch`` and ``rollout.widen``
one after another inside the call; ``StreamingGateway.serve`` opens
``gateway.schedule`` and ``gateway.ingest``, then its device call opens
the five ``rollout.*`` spans on the worker thread, then
``gateway.report``."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
# the benchmark's trace reduction, behind the program's own modules
sys.path.append(str(ROOT / "benchmarks" / "chip"))

import trace_reduce                                             # noqa: E402

ROLLOUT = ["rollout.draws", "rollout.put", "rollout.scan", "rollout.fetch",
           "rollout.widen"]
T, U = 2, 4


@pytest.fixture(scope="module")
def rollout():
    from repro.configs.lenet import LENET
    from repro.core import (RadioChannel, RadioParams, RolloutSpec,
                            cnn_cost, make_devices)
    from repro.core.positions import hex_init
    from repro.runtime.fleet_rollout import FleetRollout

    ro = FleetRollout(RadioChannel(RadioParams()),
                      make_devices(U, mem_frac=2e-4), cnn_cost(LENET),
                      RolloutSpec(frames=T, requests_per_frame=2), seed=0)
    base = hex_init(U, 40.0, jitter=0.5, seed=1)
    ro.run(base, n_trajectories=2)              # compile outside the trace
    return ro, base


def _profile(tmp_path, parent, fn):
    """``fn()`` inside a ``parent`` span under the profiler; the spans of
    the reduced trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(parent):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    return trace_reduce.reduce_file(path).spans


def _assert_nested_in_order(spans, parent, names):
    (outer,) = [s for s in spans if s[0] == parent]
    inner = [s for s in spans if s[0] != parent]
    assert [s[0] for s in inner] == names
    for _, s, e in inner:
        assert outer[1] <= s <= e <= outer[2]
    for (_, _, e), (_, s, _) in zip(inner, inner[1:]):
        assert e <= s


def test_rollout_run_opens_five_spans_in_order(tmp_path, rollout):
    ro, base = rollout
    spans = _profile(tmp_path, "bench.call",
                     lambda: ro.run(base, n_trajectories=2))
    _assert_nested_in_order(spans, "bench.call", ROLLOUT)


def test_gateway_serve_opens_its_spans_around_the_rollouts(tmp_path,
                                                            rollout):
    from repro.runtime.gateway import (GatewayConfig, LoadGenerator,
                                       StreamingGateway)
    ro, base = rollout
    gw = StreamingGateway(ro, base, GatewayConfig(window_frames=T),
                          seed=3)
    gen = LoadGenerator(U, kind="poisson", rate=1.0, deadline_s=8.0, seed=5)
    try:
        gw.serve(gen, n_windows=1, drain=False)     # warm the B = 1 shape
        spans = _profile(tmp_path, "bench.window",
                         lambda: gw.serve(gen, n_windows=1, drain=False))
    finally:
        gw.close()
    _assert_nested_in_order(
        spans, "bench.window",
        ["gateway.schedule", "gateway.ingest"] + ROLLOUT
        + ["gateway.report"])
