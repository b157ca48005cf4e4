"""The per-stage metrics that read the program's own host spans, from one
small traced CPU run of ``rollout-lenet-split`` and one of
``serve-lenet-split`` (the check test's shrunk traffic): each is present
and non-negative, and a call's stages fit inside the call."""
import pytest

import trace_reduce
from test_bench_chip_check import SEED, SMALL_ROLLOUT, SMALL_SERVE

ROLLOUT_STAGES = ("rollout_draws_s_per_call", "rollout_put_s_per_call",
                  "rollout_launch_s_per_call", "rollout_fetch_s_per_call",
                  "rollout_widen_s_per_call")
SERVE_STAGES = ("serve_schedule_ms", "serve_ingest_ms", "serve_report_ms",
                "serve_rollout_host_ms", "serve_launch_ms")


def _traced(workload, overrides, path):
    import run_cell
    result, _ = run_cell.run(
        ["--workload", workload, "--seed", str(SEED), "--seconds", "0.3",
         "--trace", "1"],
        require_tpu=False, traffic_overrides=overrides,
        compile_cache=False, trace_out=str(path))
    return result, trace_reduce.reduce_file(path)


def _mean_wall_ns(summary, name):
    spans = summary.spans_named(name)
    return sum(e - s for _, s, e in spans) / len(spans)


@pytest.mark.parametrize("workload,overrides,stages,parent,scale", [
    ("rollout-lenet-split", SMALL_ROLLOUT, ROLLOUT_STAGES, "rollout.call",
     1e9),
    ("serve-lenet-split", SMALL_SERVE, SERVE_STAGES, "gateway.window", 1e6),
], ids=["rollout", "serve"])
def test_stage_metrics_read_and_fit_in_the_call(tmp_path, workload,
                                                overrides, stages, parent,
                                                scale):
    res, summary = _traced(workload, overrides, tmp_path / "t.xplane.pb")
    assert res["correct"] is True
    m = res["metrics"]
    for name in stages:
        assert name in m and m[name]["value"] >= 0, name
    other = SERVE_STAGES if stages is ROLLOUT_STAGES else ROLLOUT_STAGES
    assert not set(other) & set(m)
    total = sum(m[name]["value"] for name in stages) * scale
    assert 0 < total <= _mean_wall_ns(summary, parent)
    # the idle gaps are named by the program's spans, not only the call's
    assert any(" in rollout." in gap or " in gateway.schedule" in gap
               for gap, _ in res["breakdown"]["idle_gaps"])
