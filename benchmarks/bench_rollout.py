"""Benchmark: the device-side fleet rollout vs the legacy per-frame
``SwarmSim`` host loop, plus the mesh-sharded trajectory axis.

Four sections, one JSON (``BENCH_rollout.json``):

* ``rollout`` — a (B, T, U) fleet rollout (mobility jitter + fused
  P2 -> P1 -> P3 per frame, battery accounting on) in ONE jit call, against
  the legacy host loop: a ``SwarmSim.run_legacy`` scalar ``LLHRPlanner``
  call per frame per trajectory (timed on a sample and extrapolated).  Two
  baselines are reported: the semantics-matched chain-DP planner loop (the
  SAME computation the rollout runs, host-looped — also the parity oracle;
  the headline >= 50x target at B = 256, T = 32, U = 8 is against it) and
  the seed default (branch-and-bound placement).  The rollout's P2 runs
  few steps per frame because the scan carry WARM-STARTS it — each frame
  refines the previous frame's adopted optimum instead of re-solving from
  scratch; separation quality is asserted below.
* ``kernel_path`` — the same rollout compiled through the Pallas planner
  kernels (``use_kernels=True``, ISSUE 9): asserted bitwise-identical to
  the jnp-path trace, with the steady-state ratio recorded.
* ``parity`` — B = 1, frozen dynamics: every frame of the rollout must
  match the legacy oracle's latency/power/feasibility (also asserted by
  ``tests/test_rollout.py``); the JSON records the max relative error.
* ``devices_sweep`` — the SAME rollout with the trajectory axis sharded
  over a 1-D mesh (``FleetRollout.run(devices=n)``) at each requested
  device count: throughput, retraces-after-first (must stay 0 per mesh),
  and the max deviation of every ``RolloutTrace`` aggregate statistic
  from the single-device reference (asserted <= 1e-6 — the shard-
  invariance contract).  On CPU, counts > 1 need
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; unavailable
  counts are recorded as skipped, never silently dropped.

All timed regions end with ``jax.block_until_ready`` (async dispatch must
not stop the clock early).  Zero retraces across repeated rollouts is
asserted in both modes.

Usage:
    [XLA_FLAGS=--xla_force_host_platform_device_count=8]
    PYTHONPATH=src python benchmarks/bench_rollout.py
        [--batch 256] [--frames 32] [--uavs 8] [--devices 1,2,8]
        [--smoke] [--json BENCH_rollout.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict

import numpy as np

import jax

from repro.configs.lenet import LENET
from repro.core import (LLHRPlanner, PositionSpec, RadioChannel, RadioParams,
                        RolloutSpec, SwarmSim, cnn_cost, make_devices,
                        solve_chain_dp)
from repro.core.positions import hex_init
from repro.runtime.fleet_rollout import FleetRollout

PARAMS = RadioParams()
CH = RadioChannel(PARAMS)


def bench_rollout(batch: int, frames: int, uavs: int, steps: int,
                  repeats: int, sample_frames: int) -> Dict:
    """(B, T) rollout in one call vs the legacy loop, extrapolated."""
    mc = cnn_cost(LENET)
    devs = make_devices(uavs)
    spec = RolloutSpec(frames=frames, requests_per_frame=2,
                       jitter_sigma_m=2.0, battery_j=5e3)
    ro = FleetRollout(CH, devs, mc, spec,
                      position_spec=PositionSpec(steps=steps,
                                                 repair_iters=25), seed=0)
    base = hex_init(uavs, 40.0, jitter=0.5, seed=0)

    def run_blocking():
        trace = ro.run(base, n_trajectories=batch)
        jax.block_until_ready((trace.latency, trace.charge))
        return trace

    t0 = time.perf_counter()
    trace = run_blocking()
    first = time.perf_counter() - t0
    traces_after_first = ro.trace_count
    steady = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        trace = run_blocking()
        steady.append(time.perf_counter() - t0)
    # best-of-N on BOTH sides: the steady-state cost of the compiled
    # program, with scheduler noise filtered out the same way for the
    # rollout and the host loop
    steady_s = float(np.min(steady))
    retraces = ro.trace_count - traces_after_first

    # legacy baselines: the host loop pays one scalar LLHRPlanner call per
    # frame per trajectory; time a short run and extrapolate to B * T.
    # chain_dp = the SAME computation host-looped (the parity oracle);
    # bnb = the seed SwarmSim's default placement solver.
    def legacy_per_frame(solver) -> float:
        planner = LLHRPlanner(CH, position_steps=steps, **(
            {"placement_solver": solver} if solver else {}))
        sim = SwarmSim(mc, devs, planner, requests_per_frame=2, seed=0,
                       backend="legacy")
        sim.run_legacy(frames=1)               # warm the jitted P2 scan
        best = float("inf")
        for _ in range(3):                     # best-of-3, like the rollout
            t0 = time.perf_counter()
            sim.run_legacy(frames=sample_frames)
            best = min(best,
                       (time.perf_counter() - t0) / sample_frames)
        return best

    per_frame_s = legacy_per_frame(solve_chain_dp)
    per_frame_bnb_s = legacy_per_frame(None)

    # warm-started P2 must not degrade the swarm geometry: every frame of
    # every trajectory keeps the eq. (8d) 2R separation
    pos = trace.positions                           # [B, T, U, 2]
    d = np.sqrt(((pos[:, :, :, None] - pos[:, :, None, :]) ** 2).sum(-1))
    d[:, :, np.eye(uavs, dtype=bool)] = np.inf
    min_sep = float(d.min())

    frames_total = batch * frames
    return {
        "batch": batch, "frames": frames, "uavs": uavs, "p2_steps": steps,
        "first_call_s": first, "steady_s": steady_s,
        "frames_per_s": frames_total / steady_s,
        "retraces_after_first": retraces,
        "legacy_per_frame_s": per_frame_s,
        "legacy_frames_per_s": 1.0 / per_frame_s,
        "legacy_bnb_per_frame_s": per_frame_bnb_s,
        "legacy_sampled_frames": sample_frames,
        "speedup_vs_legacy_loop": per_frame_s * frames_total / steady_s,
        "speedup_vs_legacy_bnb_loop":
            per_frame_bnb_s * frames_total / steady_s,
        "feasibility_rate": trace.feasibility_rate,
        "mean_latency_s": trace.mean_latency,
        "p95_latency_s": trace.latency_percentile(95.0),
        "battery_min_j": float(trace.charge[:, -1].min()),
        "min_separation_m": min_sep,
        "required_separation_m": 40.0,
    }


def bench_devices(batch: int, frames: int, uavs: int, steps: int,
                  repeats: int, counts) -> Dict:
    """Shard the trajectory axis over n devices; assert stats invariance.

    Every count runs the SAME host-drawn streams (fresh ``FleetRollout``
    per count, same seed), so any statistic deviation from the n = 1
    reference is the sharding's fault, not the RNG's.  A ragged run
    (B not divisible by the largest count) exercises the padding mask.
    """
    mc = cnn_cost(LENET)
    devs = make_devices(uavs)
    spec = RolloutSpec(frames=frames, requests_per_frame=2,
                       jitter_sigma_m=2.0, battery_j=5e3)
    pspec = PositionSpec(steps=steps, repair_iters=25)
    base = hex_init(uavs, 40.0, jitter=0.5, seed=0)
    avail = jax.local_device_count()

    def stats(trace) -> Dict:
        return {"feasibility_rate": trace.feasibility_rate,
                "mean_latency_s": trace.mean_latency,
                "mean_power_w": trace.mean_power,
                "p50_latency_s": trace.latency_percentile(50.0),
                "p95_latency_s": trace.latency_percentile(95.0)}

    def run_count(n: int, b: int):
        ro = FleetRollout(CH, devs, mc, spec, position_spec=pspec, seed=0)
        trace = ro.run(base, n_trajectories=b, devices=n)
        jax.block_until_ready((trace.latency,))
        traces_first = ro.trace_count
        best = float("inf")
        for _ in range(repeats):
            ro2 = FleetRollout(CH, devs, mc, spec, position_spec=pspec,
                               seed=0)
            t0 = time.perf_counter()
            t = ro2.run(base, n_trajectories=b, devices=n)
            jax.block_until_ready((t.latency,))
            best = min(best, time.perf_counter() - t0)
        return trace, traces_first, ro.trace_count - traces_first, best

    out: Dict = {"available_devices": avail, "batch": batch,
                 "frames": frames, "uavs": uavs, "counts": {}}
    ref, _, _, _ = run_count(1, batch)
    ref_stats = stats(ref)
    ragged_b = batch - 1 if batch > 1 else batch   # forces the pad mask
    for n in counts:
        key = str(n)
        if n > avail:
            out["counts"][key] = {
                "skipped": f"needs {n} devices, {avail} available (set "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           f"count={n})"}
            continue
        trace, _, retraces, steady = run_count(n, batch)
        dev = {k: abs(v - ref_stats[k]) for k, v in stats(trace).items()
               if np.isfinite(ref_stats[k])}
        entry = {"steady_s": steady,
                 "frames_per_s": batch * frames / steady,
                 "retraces_after_first": retraces,
                 "max_stat_abs_dev_vs_1dev": max(dev.values()),
                 **stats(trace)}
        if n > 1:
            rt, _, _, _ = run_count(n, ragged_b)
            entry["ragged"] = {
                "batch": ragged_b, "padded_to": rt.latency.shape[0],
                "n_trajectories": rt.n_trajectories,
                "feasibility_rate": rt.feasibility_rate}
            assert rt.n_trajectories == ragged_b, "padding mask leaked"
        assert retraces == 0, f"{n}-device rollout retraced"
        assert entry["max_stat_abs_dev_vs_1dev"] <= 1e-6, \
            f"{n}-device stats diverged from the single-device reference"
        out["counts"][key] = entry
    return out


def bench_kernel_path(batch: int, frames: int, uavs: int, steps: int,
                      repeats: int) -> Dict:
    """``use_kernels`` on/off: the SAME rollout through the Pallas planner
    kernels (ISSUE 9 tropical-DP wavefront + fused link geometry) vs the
    jnp hot loops.  Every trace field must be bitwise identical — the
    kernels are a program swap, not an approximation — and the steady
    ratio is recorded (the two compiled programs are distinct PlanFnCache
    entries, so neither run retraces the other)."""
    mc = cnn_cost(LENET)
    devs = make_devices(uavs)
    spec = RolloutSpec(frames=frames, requests_per_frame=2,
                       jitter_sigma_m=2.0, battery_j=5e3)
    base = hex_init(uavs, 40.0, jitter=0.5, seed=0)

    def run_one(use_kernels: bool):
        ro = FleetRollout(CH, devs, mc, spec,
                          position_spec=PositionSpec(steps=steps,
                                                     repair_iters=25),
                          seed=0, use_kernels=use_kernels)
        trace = ro.run(base, n_trajectories=batch)
        jax.block_until_ready((trace.latency,))
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            t = ro.run(base, n_trajectories=batch)
            jax.block_until_ready((t.latency,))
            best = min(best, time.perf_counter() - t0)
        return trace, best

    jnp_trace, jnp_s = run_one(False)
    ker_trace, ker_s = run_one(True)
    fields = ("latency", "total_power", "feasible", "cap_feasible",
              "source_latency", "assign", "positions", "active", "charge",
              "n_requests", "energy_tx", "energy_cmp")
    bitwise = all(np.array_equal(getattr(jnp_trace, f),
                                 getattr(ker_trace, f)) for f in fields)
    return {"batch": batch, "frames": frames, "uavs": uavs,
            "jnp_steady_s": jnp_s, "kernel_steady_s": ker_s,
            "steady_ratio_vs_jnp": ker_s / jnp_s,
            "bitwise_equal_fields": len(fields) if bitwise else -1,
            "bitwise_equal": bitwise}


def bench_parity(frames: int, uavs: int, use_kernels: bool = False) -> Dict:
    """B = 1, frozen dynamics: per-frame parity vs the legacy oracle."""
    mc = cnn_cost(LENET)
    devs = make_devices(uavs)
    pos = hex_init(uavs, 40.0, jitter=0.5, seed=1)
    rng = np.random.default_rng(7)
    sources = rng.integers(0, uavs, size=(frames, 1))
    ro = FleetRollout(CH, devs, mc, RolloutSpec(frames=frames), seed=0,
                      use_kernels=use_kernels)
    trace = ro.run(pos, n_trajectories=1, sources=sources)
    oracle = LLHRPlanner(CH, placement_solver=solve_chain_dp,
                         optimize_positions=False)
    lat_err = pw_err = 0.0
    agree = True
    for t in range(frames):
        plan, _ = oracle.plan(mc, devs, [int(sources[t, 0])],
                              positions=pos, t=t)
        agree &= bool(trace.feasible[0, t]) == plan.feasible
        if plan.feasible:
            lat_err = max(lat_err, abs(trace.latency[0, t] -
                                       plan.total_latency) /
                          plan.total_latency)
            pw_err = max(pw_err, abs(trace.total_power[0, t] -
                                     plan.total_power) /
                         max(plan.total_power, 1e-12))
    return {"frames": frames, "uavs": uavs, "feasibility_agrees": agree,
            "max_latency_rel_err": lat_err, "max_power_rel_err": pw_err}


def run(batch: int = 256, frames: int = 32, uavs: int = 8, steps: int = 30,
        repeats: int = 5, sample_frames: int = 4,
        smoke: bool = False, device_counts=None) -> Dict:
    if device_counts is None:
        device_counts = [n for n in (1, 2, 4, 8)
                         if n <= jax.local_device_count()]
    result: Dict = {
        "benchmark": "fleet_rollout",
        "backend": jax.default_backend(),
        "config": {"batch": batch, "frames": frames, "uavs": uavs,
                   "p2_steps": steps, "repeats": repeats,
                   "sample_frames": sample_frames, "smoke": smoke,
                   "device_counts": list(device_counts)},
    }

    ro = bench_rollout(batch, frames, uavs, steps, repeats, sample_frames)
    result["rollout"] = ro
    print(f"rollout : B={batch} T={frames} U={uavs}: first "
          f"{ro['first_call_s']:.2f}s, steady {ro['steady_s'] * 1e3:.1f} ms "
          f"({ro['frames_per_s']:.0f} frames/s), "
          f"{ro['retraces_after_first']} retraces")
    print(f"legacy  : {ro['legacy_frames_per_s']:.1f} frames/s "
          f"(SwarmSim chain-DP host loop, sampled "
          f"{ro['legacy_sampled_frames']}; bnb default "
          f"{1.0 / ro['legacy_bnb_per_frame_s']:.1f} frames/s)")
    print(f"speedup : {ro['speedup_vs_legacy_loop']:.1f}x vs the matched "
          f"chain-DP loop ({ro['speedup_vs_legacy_bnb_loop']:.1f}x vs bnb "
          f"default); feasibility {100 * ro['feasibility_rate']:.0f}%, "
          f"min sep {ro['min_separation_m']:.1f} m, p95 latency "
          f"{ro['p95_latency_s']:.4f}s")

    ker = bench_kernel_path(batch, frames, uavs, steps,
                            max(2, repeats // 2))
    result["kernel_path"] = ker
    print(f"kernels : use_kernels=True "
          f"{ker['kernel_steady_s'] * 1e3:.1f} ms vs jnp "
          f"{ker['jnp_steady_s'] * 1e3:.1f} ms "
          f"({ker['steady_ratio_vs_jnp']:.2f}x), bitwise "
          f"{ker['bitwise_equal']}")

    par = bench_parity(min(frames, 8), uavs)
    result["parity"] = par
    print(f"parity  : feasibility agrees={par['feasibility_agrees']}, "
          f"max rel err latency {par['max_latency_rel_err']:.2e} / power "
          f"{par['max_power_rel_err']:.2e}")

    sweep = bench_devices(batch, frames, uavs, steps,
                          max(2, repeats // 2), device_counts)
    result["devices_sweep"] = sweep
    for n, entry in sweep["counts"].items():
        if "skipped" in entry:
            print(f"sharded : {n} devices skipped ({entry['skipped']})")
        else:
            ragged = entry.get("ragged")
            print(f"sharded : {n} devices: "
                  f"{entry['frames_per_s']:.0f} frames/s, max stat dev "
                  f"{entry['max_stat_abs_dev_vs_1dev']:.1e}, "
                  f"{entry['retraces_after_first']} retraces"
                  + (f", ragged B={ragged['batch']} padded to "
                     f"{ragged['padded_to']}" if ragged else ""))

    assert ro["retraces_after_first"] == 0, \
        "rollout retraced across repeated runs"
    assert ker["bitwise_equal"], \
        "use_kernels rollout diverged from the jnp-path rollout"
    assert par["feasibility_agrees"], "per-frame feasibility diverged"
    assert par["max_latency_rel_err"] < 1e-3 and \
        par["max_power_rel_err"] < 1e-3, "per-frame parity drifted"
    assert ro["min_separation_m"] >= ro["required_separation_m"] - 0.5, \
        "warm-started P2 violated the 2R separation constraint"
    if not smoke:
        assert ro["speedup_vs_legacy_loop"] >= 50.0, \
            "speedup target (50x rollout vs legacy SwarmSim loop) missed"
        print("PASS: >=50x vs legacy loop, 0 retraces, B=1 parity held")
    return result


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--uavs", type=int, default=8)
    ap.add_argument("--steps", type=int, default=30,
                    help="fused P2 iterations per frame (the scan carry "
                         "warm-starts P2, so fewer steps than a cold solve)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--sample-frames", type=int, default=4,
                    help="legacy frames timed (extrapolated to B*T)")
    ap.add_argument("--devices", type=str, default=None,
                    help="comma-separated device counts for the sharded "
                         "sweep, e.g. 1,2,8 (default: {1,2,4,8} capped to "
                         "what is available; on CPU force more via "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized run; no speedup asserts")
    ap.add_argument("--json", type=str, default=None,
                    help="write the result dict to this path")
    args = ap.parse_args(argv)
    counts = None if args.devices is None else \
        sorted({int(x) for x in args.devices.split(",") if x.strip()})
    if args.smoke:
        cfg = dict(batch=8, frames=4, uavs=4, steps=30, repeats=2,
                   sample_frames=2, smoke=True, device_counts=counts)
    else:
        cfg = dict(batch=args.batch, frames=args.frames, uavs=args.uavs,
                   steps=args.steps, repeats=args.repeats,
                   sample_frames=args.sample_frames, device_counts=counts)
    result = run(**cfg)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return result


if __name__ == "__main__":
    main()
