"""Smoke test of the LLHR planner on a TPU, through its normal entry points.

Default (one chip), in one process:

* batch path — ``FleetRollout.run`` at the rollout-paper shape (U = 8,
  LeNet, RQ = 2, T = 32, B = 65536; warm-started P2, mobility jitter and
  batteries on, as in ``benchmarks/bench_rollout.py``), once through the
  jnp hot loops and once through the Pallas kernels (``use_kernels``).
  Per-trajectory assignments must agree, feasible frames must have finite
  latencies, the kernel program must contain ``tpu_custom_call``, and a
  B = 1 rollout must match the scalar legacy planner frame by frame;
* wide DP — the U = L = 32 chain of ``bench_placement.py``'s big case
  through the single- and multi-source batched chain DP, both paths,
  checked against the scalar ``placement.solve_chain_dp`` on a sample;
* served path — the ``bench_gateway.py`` deployment (U = 5, split-forced
  LeNet, 8-frame windows) serving Poisson traffic through
  ``StreamingGateway``, both paths.

``--chips 4`` runs only the trajectory-sharded rollout,
``FleetRollout.run(devices=4)``, and the same-seed one-chip run it is
compared with.

Times printed here are set-up and smoke timings (compilation included),
not benchmark numbers.  Without a TPU the script exits non-zero before
any phase.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.

Usage: python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

BATCH = 65536          # trajectories of the rollout-paper shape
FRAMES = 32
UAVS = 8
WIDE = dict(batch=64, uavs=32, layers=32, seed=1, spread=250.0)
PARITY_FRAMES = 8
PARITY_TOL = 1e-3      # per-frame relative error vs the float64 legacy loop
ORACLE_TOL = 1e-5      # chain-DP latency vs the NumPy solver (relative)
ORACLE_SAMPLE = 16
SHARD_TOL = 1e-6       # rollout statistics, 4 chips vs 1 (relative)
TRACE_FIELDS = ("latency", "total_power", "feasible", "cap_feasible",
                "source_latency", "assign", "positions", "active", "charge",
                "n_requests", "energy_tx", "energy_cmp")


class SmokeFailure(Exception):
    """A result that the smoke test checks came out wrong."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def timed(label: str, fn):
    """Run ``fn`` (which returns host data, so the device has finished)
    and print its wall time as a set-up timing."""
    t0 = time.perf_counter()
    out = fn()
    print(f"  setup-timing {label}: {time.perf_counter() - t0:.3f} s",
          flush=True)
    return out


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{info['platform']}")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices; JAX found {info['count']}")
    return info


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def max_rel_diff(a, b) -> float:
    """Largest relative difference of two arrays (over the larger
    magnitude); inf where only one side is finite."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    diff = a != b
    if not diff.any():
        return 0.0
    a, b = a[diff], b[diff]
    if not (np.isfinite(a) & np.isfinite(b)).all():
        return float("inf")
    return float((np.abs(a - b) / np.maximum(np.abs(a), np.abs(b))).max())


def describe_diff(a, b) -> str:
    n = int(np.count_nonzero(np.asarray(a) != np.asarray(b)))
    if n == 0:
        return "bitwise equal"
    return (f"{n} of {np.asarray(a).size} entries differ, max relative "
            f"difference {max_rel_diff(a, b)!r}")


# ---------------------------------------------------------------------------
# batch path: the rollout-paper fleet rollout
# ---------------------------------------------------------------------------


def make_fleet(use_kernels: bool):
    from repro.configs.lenet import LENET
    from repro.core import (PositionSpec, RadioChannel, RadioParams,
                            RolloutSpec, cnn_cost, make_devices)
    from repro.runtime.fleet_rollout import FleetRollout
    spec = RolloutSpec(frames=FRAMES, requests_per_frame=2,
                       jitter_sigma_m=2.0, battery_j=5e3)
    return FleetRollout(RadioChannel(RadioParams()), make_devices(UAVS),
                        cnn_cost(LENET), spec,
                        position_spec=PositionSpec(steps=30,
                                                   repair_iters=25),
                        seed=0, use_kernels=use_kernels)


def base_positions():
    from repro.core.positions import hex_init
    return hex_init(UAVS, 40.0, jitter=0.5, seed=0)


def lowered_rollout_text(ro, batch: int) -> str:
    """StableHLO of the single-device rollout program ``ro`` runs."""
    import jax
    B, T, U = batch, FRAMES, UAVS
    f32, b8 = np.float32, np.bool_
    shapes = [((B, U, 2), f32), ((B, U), f32), ((B, U), b8),
              ((B, U, 2), f32), ((T, B, U, 2), f32), ((T, B, U), f32),
              ((T, B, U), f32), ((T, B, U), b8), ((T, B, U), f32)]
    return ro._rollout.lower(
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).as_text()


def check_trace(name: str, trace) -> None:
    feas = trace.feasible
    print(f"  {name}: feasibility {trace.feasibility_rate!r}, mean latency "
          f"{trace.mean_latency!r} s, p95 {trace.latency_percentile(95)!r}"
          f" s, mean power {trace.mean_power!r} W", flush=True)
    check(trace.feasibility_rate > 0, f"{name}: no feasible frame")
    check(bool(np.isfinite(trace.latency[feas]).all()),
          f"{name}: non-finite latency on a feasible frame")
    check(bool(np.isfinite(trace.positions).all()),
          f"{name}: non-finite positions")


def phase_rollout(batch: int = BATCH) -> None:
    base = base_positions()
    traces = {}
    for use_kernels in (False, True):
        ro = make_fleet(use_kernels)
        text = lowered_rollout_text(ro, batch)
        has_kernel = "tpu_custom_call" in text
        print(f"  use_kernels={use_kernels}: program has tpu_custom_call: "
              f"{has_kernel}", flush=True)
        check(has_kernel == use_kernels,
              f"use_kernels={use_kernels}: tpu_custom_call present = "
              f"{has_kernel}")
        traces[use_kernels] = timed(
            f"rollout B={batch} T={FRAMES} U={UAVS} use_kernels="
            f"{use_kernels} first call (compile + run)",
            lambda: ro.run(base, n_trajectories=batch))
        timed(f"rollout use_kernels={use_kernels} second call (run)",
              lambda: ro.run(base, n_trajectories=batch))
        check_trace(f"use_kernels={use_kernels}", traces[use_kernels])
    jnp_t, ker_t = traces[False], traces[True]
    for f in TRACE_FIELDS:
        print(f"  kernel vs jnp {f}: "
              f"{describe_diff(getattr(jnp_t, f), getattr(ker_t, f))}",
              flush=True)
    differ = (jnp_t.assign != ker_t.assign).any(axis=(2, 3))      # [B, T]
    print(f"  kernel vs jnp: {int(differ.sum())} of {differ.size} "
          f"(trajectory, frame) assignments differ", flush=True)
    check(not differ.any(), "kernel and jnp assignments differ")


def phase_parity() -> None:
    from benchmarks.bench_rollout import bench_parity
    for use_kernels in (False, True):
        par = timed(f"B=1 parity use_kernels={use_kernels}",
                    lambda: bench_parity(PARITY_FRAMES, UAVS,
                                         use_kernels=use_kernels))
        print(f"  B=1 vs legacy loop, use_kernels={use_kernels}: "
              f"feasibility agrees {par['feasibility_agrees']}, worst "
              f"relative error latency {float(par['max_latency_rel_err'])!r}, "
              f"power {float(par['max_power_rel_err'])!r} (tolerance "
              f"{PARITY_TOL})", flush=True)
        check(par["feasibility_agrees"], "per-frame feasibility diverged")
        check(par["max_latency_rel_err"] < PARITY_TOL
              and par["max_power_rel_err"] < PARITY_TOL,
              "per-frame parity outside tolerance")


# ---------------------------------------------------------------------------
# wide DP: U = L = 32
# ---------------------------------------------------------------------------


def phase_wide_dp(batch: int = WIDE["batch"], uavs: int = WIDE["uavs"],
                  layers: int = WIDE["layers"]) -> None:
    from benchmarks.bench_placement import build_case
    from repro.core import PlacementProblem, solve_chain_dp
    from repro.core.batch import (solve_chain_dp_batched,
                                  solve_chain_dp_multisource)
    args, devs, _ = build_case(batch, uavs, layers, seed=WIDE["seed"],
                               spread=WIDE["spread"])
    sources = np.broadcast_to(np.arange(uavs), (batch, uavs))
    single, multi = {}, {}
    for k in (False, True):
        single[k] = timed(
            f"single-source DP U=L={uavs} B={batch} use_kernel={k} "
            "first call", lambda: solve_chain_dp_batched(*args,
                                                         use_kernel=k))
        multi[k] = timed(
            f"multi-source DP U=L={uavs} B={batch} M={uavs} use_kernel={k}"
            " first call", lambda: solve_chain_dp_multisource(
                *args[:8], sources, use_kernel=k))
    for name, res in (("single-source", single), ("multi-source", multi)):
        (a0, l0), (a1, l1) = res[False], res[True]
        print(f"  {name} kernel vs jnp: assignments equal "
              f"{bool(np.array_equal(a0, a1))}, latency max relative "
              f"difference {max_rel_diff(l0, l1)!r}, feasible "
              f"{int(np.isfinite(l0).sum())} of {l0.size}", flush=True)
        check(np.array_equal(a0, a1), f"{name}: kernel and jnp differ")
    a_s, l_s = single[False]
    a_m, l_m = multi[False]
    rows = np.arange(batch)
    check(np.array_equal(a_m[rows, args[8]], a_s)
          and np.array_equal(l_m[rows, args[8]], l_s),
          "multi-source slot of the scenario's source != single-source")
    compute, memory, act_bits, input_bits = args[:4]
    rate, source = args[7], args[8]
    worst = 0.0
    sample = min(ORACLE_SAMPLE, batch)
    for i in range(sample):
        sol = solve_chain_dp(PlacementProblem(
            compute, memory, act_bits, list(devs), rate[i],
            source=int(source[i]), input_bits=input_bits))
        check(np.isfinite(sol.latency) == np.isfinite(l_s[i]),
              f"scenario {i}: feasibility differs from the NumPy solver")
        if np.isfinite(sol.latency):
            check(tuple(a_s[i]) == tuple(sol.assign),
                  f"scenario {i}: assignment differs from the NumPy solver")
            worst = max(worst, float(abs(l_s[i] - sol.latency)
                                     / sol.latency))
    print(f"  vs placement.solve_chain_dp on {sample} scenarios: "
          f"worst relative latency error {worst!r} (tolerance "
          f"{ORACLE_TOL})", flush=True)
    check(worst < ORACLE_TOL, "chain DP outside the oracle tolerance")


# ---------------------------------------------------------------------------
# served path: the gateway deployment
# ---------------------------------------------------------------------------


def phase_served(windows: int = 6) -> None:
    from benchmarks.bench_gateway import make_gateway, make_rollout
    from repro.core.positions import hex_init
    from repro.runtime.gateway import LoadGenerator
    from repro.runtime.scenario_engine import PlanFnCache
    uavs, window, per_frame = 5, 8, 3
    base = hex_init(uavs, 40.0, jitter=0.5, seed=1)
    for use_kernels in (False, True):
        ro = make_rollout(uavs, window, per_frame, PlanFnCache(),
                          use_kernels=use_kernels)
        # compile outside the gateway so no solve attempt waits on it
        timed(f"gateway window program use_kernels={use_kernels} "
              "(compile + run)",
              lambda: ro.run(base, n_trajectories=1, frames=window,
                             arrivals=np.zeros((window, 1, uavs),
                                               np.float32)))
        gw = make_gateway(ro, base, window)
        gen = LoadGenerator(uavs, kind="poisson", rate=0.8 * per_frame,
                            deadline_s=2.0 * window, seed=3)
        try:
            rep = timed(f"serve {windows} windows use_kernels="
                        f"{use_kernels}",
                        lambda: gw.serve(gen, n_windows=windows))
        finally:
            gw.close()
        print(f"  served path use_kernels={use_kernels}: submitted "
              f"{rep['submitted']}, served {rep['served']}, shed "
              f"{rep['shed']}, windows {rep['windows']}, windows_failed "
              f"{rep['windows_failed']}, retries {rep['retries']}, "
              f"deadline hit rate {rep['deadline_hit_rate']!r}",
              flush=True)
        check(rep["served"] > 0, "gateway served nothing")
        check(rep["windows_failed"] == 0 and rep["retries"] == 0,
              "gateway needed retries or lost a window")
        check(rep["served"] + rep["shed_total"] == rep["submitted"]
              and rep["queued"] == 0,
              "served + shed != submitted")
        check(rep["deadline_hit_rate"] == 1.0,
              "a served request missed its deadline")


# ---------------------------------------------------------------------------
# --chips 4: the trajectory-sharded rollout
# ---------------------------------------------------------------------------


def rollout_stats(trace) -> dict:
    return {"feasibility_rate": trace.feasibility_rate,
            "mean_latency_s": trace.mean_latency,
            "mean_power_w": trace.mean_power,
            "p50_latency_s": trace.latency_percentile(50.0),
            "p95_latency_s": trace.latency_percentile(95.0)}


def phase_sharded(chips: int, batch: int = BATCH) -> None:
    base = base_positions()
    traces = {}
    for n in (1, chips):
        ro = make_fleet(False)
        traces[n] = timed(
            f"rollout B={batch} devices={n} first call (compile + run)",
            lambda: ro.run(base, n_trajectories=batch, devices=n))
        timed(f"rollout devices={n} second call (run)",
              lambda: ro.run(base, n_trajectories=batch, devices=n))
        check_trace(f"devices={n}", traces[n])
    ref, got = rollout_stats(traces[1]), rollout_stats(traces[chips])
    worst = 0.0
    for k, v in ref.items():
        dev = 0.0 if got[k] == v else abs(got[k] - v) / max(abs(v), 1e-30)
        worst = max(worst, dev)
        print(f"  {k}: devices=1 {v!r}, devices={chips} {got[k]!r}, "
              f"relative deviation {dev!r}", flush=True)
    differ = (traces[1].assign
              != traces[chips].assign[:batch]).any(axis=(2, 3))
    print(f"  devices={chips} vs 1: {int(differ.sum())} of {differ.size} "
          f"(trajectory, frame) assignments differ; worst statistic "
          f"deviation {worst!r} (tolerance {SHARD_TOL})", flush=True)
    check(worst <= SHARD_TOL, "sharded statistics diverged")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded rollout and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    info = device_info(args.chips)

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 1:
        phases = [("batch rollout", phase_rollout),
                  ("B=1 parity", phase_parity),
                  ("wide DP", phase_wide_dp),
                  ("served path", phase_served)]
    else:
        phases = [("sharded rollout",
                   lambda: phase_sharded(args.chips))]
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        print(f"phase: {name}", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:            # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAILED", flush=True)
            continue
        print(f"phase {name}: passed ({time.perf_counter() - t0:.1f} s "
              f"set-up and smoke time)", flush=True)
    print(f"peak_bytes_in_use: {peak_bytes()}", flush=True)
    print(f"total smoke time: {time.perf_counter() - t_all:.1f} s",
          flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
