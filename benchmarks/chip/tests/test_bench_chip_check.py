"""The comparison that decides ``correct``: it passes the program, fails
the control (the reference in bfloat16 in the program's place), and fails
a run whose timed path is broken underneath.

Everything runs on the CPU at a size a test run holds: the harness's look
for a chip is skipped, the traffic is shrunk, the window is short.
"""
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
SEED = 2 ** 31 + 977


def _cfg(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def _limits(check):
    return json.loads((CHIP / "checks" / f"{check}.json").read_text())[
        "limits"]


@pytest.mark.parametrize("cfgname", ["paper-u8-lenet-split",
                                     "paper-u8-alexnet-split"])
def test_control_fails_and_reference_in_float32_passes(cfgname):
    from compare import compare, judge
    from inputs import base_positions, call_rng, rollout_draws
    from reference import Deployment, simulate
    cfg, dep = _cfg(cfgname), None
    dep = Deployment.from_config(cfg)
    base = base_positions(cfg, SEED)
    inputs = rollout_draws(cfg, base, 8, 8, call_rng(SEED, 0))
    limits = _limits("rollout")
    ctl = simulate(dep, inputs, ml_dtypes.bfloat16)
    got = compare(ctl, simulate(dep, inputs, np.float64, forced=ctl),
                  dep.battery_j)
    assert not judge(got, limits), got
    own = simulate(dep, inputs, np.float32)
    got = compare(own, simulate(dep, inputs, np.float64, forced=own),
                  dep.battery_j)
    assert judge(got, limits), got


def _run(workload, overrides, seed=SEED, trace=0):
    import run_cell
    result, lines = run_cell.run(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0.3",
         "--trace", str(trace)],
        require_tpu=False, traffic_overrides=overrides,
        compile_cache=False)
    assert len(lines) == len(result["check"])
    return result


SMALL_ROLLOUT = {"trajectories": 16, "frames": 6, "sample_rows": 8}
SMALL_SERVE = {"sample_windows": 6, "warm_windows": 1}


def test_rollout_run_is_correct_on_cpu():
    res = _run("rollout-lenet-split", SMALL_ROLLOUT)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"rollout_frames_per_s", "setup_s"}
    assert list(res)[-1] == "check"


def test_serve_run_is_correct_on_cpu():
    # every request of the window reaches an answer: served, or shed from
    # a frame solved infeasible, which the check holds to the reference
    res = _run("serve-lenet-split", SMALL_SERVE)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_window_p50_ms",
                                   "serve_window_p95_ms", "setup_s"}
    assert list(res)[-1] == "check"


def _broken(monkeypatch, alter):
    from repro.runtime.fleet_rollout import FleetRollout
    real = FleetRollout.run

    def run(self, *a, **kw):
        tr = real(self, *a, **kw)
        alter(tr)
        return tr

    monkeypatch.setattr(FleetRollout, "run", run)


def _answer_altered(tr):
    # one frame's latencies altered where they are produced
    tr.source_latency[:, 1] *= 1.001
    tr.latency[:, 1] *= 1.001


def _half_batch_left_out(tr):
    # the second half of the trajectories repeats the first
    h = tr.latency.shape[0] // 2
    for f in ("positions", "latency", "source_latency", "total_power",
              "energy_tx", "energy_cmp", "charge", "assign", "feasible"):
        arr = getattr(tr, f)
        arr[h:2 * h] = arr[:h]


def _state_unchanged(tr):
    # P2 leaves the swarm where the frame found it: no position moves on
    tr.positions[:] = tr.positions[:, :1]


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out,
                                   _state_unchanged],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_rollout_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = _run("rollout-lenet-split", SMALL_ROLLOUT)
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault", [_answer_altered, _state_unchanged],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_serve_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = _run("serve-lenet-split", SMALL_SERVE)
    assert res["correct"] is False, res["check"]


def test_serve_run_is_correct_on_cpu():
    res = _run("serve-lenet-split", SMALL_SERVE)
    assert res["correct"] is True
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_window_p50_ms",
                                   "serve_window_p95_ms", "setup_s"}


def test_traced_run_reports_per_layer_metrics():
    res = _run("rollout-lenet-split", SMALL_ROLLOUT, trace=1)
    assert res["correct"] is True
    assert {"rollout_host_s_per_call", "scan_device_s_per_call",
            "window_compiles.rollout", "idle_share.rollout"} \
        <= set(res["metrics"])
    assert res["metrics"]["window_compiles.rollout"]["value"] == 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"]


def test_no_chip_means_no_result():
    import run_cell
    with pytest.raises(run_cell.NoChip):
        run_cell.run(["--workload", "rollout-lenet-split", "--seed", "1",
                      "--seconds", "1"], compile_cache=False)
