"""Benchmark: the streaming arrival gateway under offered-load sweeps
and composed faults — throughput, shedding, deadline hit rate, replay.

Two sections price what ``runtime.gateway.StreamingGateway`` buys:

* ``load_sweep`` — an open-loop flood source offers ``x`` times the
  device capacity (``requests_per_frame`` per frame); for each multiple
  the gateway serves a fixed horizon against the REAL fused rollout
  (split-forced LeNet fleet) and reports goodput, shed rate by reason,
  deadline hit rate and admission-to-result latency percentiles.  The
  curve must saturate: goodput caps at device capacity while everything
  beyond it is shed deterministically (never queued unboundedly) and
  every request that IS served meets its deadline.
* ``chaos`` — one seeded ``FaultSchedule`` composes an arrival flood, a
  device stall (absorbed by bounded retry + backoff), a clock skew and a
  correlated burst + crash on the fleet itself; the run must shed with
  recorded reasons, keep the served deadline-hit-rate at 100%, and —
  rebuilt from the same seeds — replay its arrival tensors and served
  statistics bitwise.

Every gateway in the process shares ONE ``PlanFnCache``: after the first
window compiles, the entire sweep (and the replay) must pay ZERO further
retraces — the serving edge never perturbs the compiled plan.

Usage:
    PYTHONPATH=src python benchmarks/bench_gateway.py
        [--uavs 5] [--window 8] [--windows 6] [--smoke]
        [--json BENCH_gateway.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

import jax

# allow `python benchmarks/bench_gateway.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.configs.lenet import LENET
from repro.core import (RadioChannel, RadioParams, RolloutSpec, cnn_cost,
                        make_devices)
from repro.core.positions import hex_init
from repro.runtime.chaos import FaultSchedule
from repro.runtime.fleet_rollout import FleetRollout
from repro.runtime.gateway import (GatewayConfig, LoadGenerator,
                                   StreamingGateway)
from repro.runtime.scenario_engine import PlanFnCache

PARAMS = RadioParams()
CH = RadioChannel(PARAMS)
MC = cnn_cost(LENET)
SPLIT_MEM_FRAC = 2e-4      # LeNet overflows one UAV -> forced chain split


def make_rollout(uavs: int, window: int, per_frame: int,
                 cache: PlanFnCache,
                 use_kernels: bool = False) -> FleetRollout:
    devs = make_devices(uavs, mem_frac=SPLIT_MEM_FRAC)
    spec = RolloutSpec(frames=window, requests_per_frame=per_frame,
                       recovery_prob=0.5)
    return FleetRollout(CH, devs, MC, spec, plan_cache=cache, seed=0,
                        use_kernels=use_kernels)


def make_gateway(rollout: FleetRollout, base: np.ndarray, window: int,
                 schedule: FaultSchedule = None,
                 queue_capacity: int = 64) -> StreamingGateway:
    return StreamingGateway(
        rollout, base,
        GatewayConfig(window_frames=window, frame_s=1.0,
                      queue_capacity=queue_capacity,
                      retry_base_backoff_s=0.001, max_attempts=3),
        schedule=schedule, seed=0)


def bench_load_sweep(uavs: int, window: int, windows: int, per_frame: int,
                     load_multiples: List[float],
                     cache: PlanFnCache) -> Dict:
    """Goodput / shed-rate / deadline-hit curves vs offered load."""
    base = hex_init(uavs, 40.0, jitter=0.5, seed=1)
    rollout = make_rollout(uavs, window, per_frame, cache)
    capacity_rps = float(per_frame)        # per frame_s=1.0 second
    points = []
    for x in load_multiples:
        gen = LoadGenerator(uavs, kind="flood", rate=x * per_frame,
                            deadline_s=2.0 * window, seed=3)
        gw = make_gateway(rollout, base, window)
        t0 = time.perf_counter()
        rep = gw.serve(gen, n_windows=windows)
        wall = time.perf_counter() - t0
        gw.close()
        points.append({
            "load_multiple": x,
            "offered_rps": rep["offered_rps"],
            "throughput_rps": rep["throughput_rps"],
            "goodput_fraction": rep["throughput_rps"] / capacity_rps,
            "shed_rate": rep["shed_total"] / max(rep["submitted"], 1),
            "shed": rep["shed"],
            "deadline_hit_rate": rep["deadline_hit_rate"],
            "latency_p50_s": rep["latency_p50_s"],
            "latency_p99_s": rep["latency_p99_s"],
            "wall_s": wall,
            "windows_per_s": windows / wall,
        })
        print(f"load_sweep  : x={x:.2f} offered={rep['offered_rps']:.2f}"
              f"rps served={rep['throughput_rps']:.2f}rps "
              f"shed={points[-1]['shed_rate']:.2f} "
              f"hit={rep['deadline_hit_rate']:.3f} "
              f"p99={rep['latency_p99_s']:.2f}s wall={wall:.2f}s")
    return {"capacity_rps": capacity_rps, "points": points}


def chaos_schedule(uavs: int, frames: int) -> FaultSchedule:
    t = frames // 4
    return (FaultSchedule(uavs, frames, seed=5)
            .burst(frame=max(1, t), size=2, persistence=0.7)
            .crash(frame=2 * t, uav=0, frames=t)
            .arrival_flood(2 * t, 3.0, frames=t)
            .device_stall(t, attempts=1)
            .clock_skew(3 * t, -1.0, frames=t))


def bench_chaos(uavs: int, window: int, windows: int, per_frame: int,
                cache: PlanFnCache) -> Dict:
    """Composed faults through the serving edge + the fleet, twice: the
    second build must replay the first bitwise."""
    base = hex_init(uavs, 40.0, jitter=0.5, seed=1)
    frames = window * windows

    def run():
        rollout = make_rollout(uavs, window, per_frame, cache)
        gw = make_gateway(rollout, base, window,
                          schedule=chaos_schedule(uavs, frames),
                          queue_capacity=4 * per_frame * window)
        gen = LoadGenerator(uavs, kind="burst", rate=0.5 * per_frame,
                            deadline_s=1.5 * window, seed=7,
                            priorities=(0, 1),
                            priority_weights=(0.2, 0.8))
        rep = gw.serve(gen, n_windows=windows)
        tensors = [a.copy() for a in gw.arrival_tensors]
        gw.close()
        return rep, tensors

    rep, tensors = run()
    rep2, tensors2 = run()
    replay_ok = rep == rep2 and all(
        np.array_equal(a, b) for a, b in zip(tensors, tensors2))
    print(f"chaos       : served={rep['served']} shed={rep['shed']} "
          f"retries={rep['retries']} hit={rep['deadline_hit_rate']:.3f}")
    print(f"chaos       : replay bitwise identical: {replay_ok}")
    return {"report": rep, "replay_bitwise_identical": replay_ok}


def run(uavs: int = 5, window: int = 8, windows: int = 6,
        per_frame: int = 3, smoke: bool = False) -> Dict:
    cache = PlanFnCache()
    result: Dict = {
        "benchmark": "gateway",
        "backend": jax.default_backend(),
        "config": {"uavs": uavs, "window_frames": window,
                   "windows": windows, "requests_per_frame": per_frame,
                   "smoke": smoke},
    }
    multiples = [0.5, 2.0, 4.0] if smoke else [0.25, 0.5, 1.0, 2.0, 4.0]

    sweep = bench_load_sweep(uavs, window, windows, per_frame, multiples,
                             cache)
    # everything after the first point rides the one compiled window
    traces_after_sweep = sum(cache.traces.values())
    result["load_sweep"] = sweep
    chaos = bench_chaos(uavs, window, windows, per_frame, cache)
    result["chaos"] = chaos
    retraces = sum(cache.traces.values()) - traces_after_sweep
    result["retraces"] = {"cache_keys": len(cache.traces),
                          "sweep_traces": traces_after_sweep,
                          "after_sweep_new_traces": retraces}
    print(f"retraces    : {traces_after_sweep} traces for the sweep, "
          f"{retraces} after it (chaos + replay)")

    pts = sweep["points"]
    assert retraces == 0, "gateway runs retraced the compiled window"
    assert chaos["replay_bitwise_identical"], "chaos replay diverged"
    for p in pts:
        assert p["deadline_hit_rate"] == 1.0, \
            f"x={p['load_multiple']}: a served request missed its deadline"
        # goodput can never exceed what the device solves per second
        assert p["throughput_rps"] <= sweep["capacity_rps"] + 1e-9
    assert chaos["report"]["deadline_hit_rate"] == 1.0
    assert chaos["report"]["retries"] >= 1, "device stall never exercised"
    over = [p for p in pts if p["load_multiple"] > 1.0]
    assert all(p["shed_rate"] > 0.0 for p in over), \
        "overload must shed, not queue unboundedly"
    # shedding is monotone in offered load across the sweep
    rates = [p["shed_rate"] for p in pts]
    assert all(a <= b + 1e-9 for a, b in zip(rates, rates[1:])), \
        f"shed rate not monotone in offered load: {rates}"
    print("PASS: saturating goodput, deterministic overload shedding, "
          "100% deadline hits on served work, bitwise replay, 0 retraces")
    return result


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--uavs", type=int, default=5)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--per-frame", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run")
    ap.add_argument("--json", type=str, default=None,
                    help="write the result dict to this path")
    args = ap.parse_args(argv)
    if args.smoke:
        cfg = dict(uavs=4, window=4, windows=3, per_frame=2, smoke=True)
    else:
        cfg = dict(uavs=args.uavs, window=args.window,
                   windows=args.windows, per_frame=args.per_frame)
    result = run(**cfg)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return result


if __name__ == "__main__":
    main()
