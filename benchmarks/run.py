"""Benchmark harness — paper figures (CSV) + perf-trajectory JSON.

Modes:

* (default)        — one module per paper figure + kernel microbench,
                     printing ``name,us_per_call,derived[,feasibility]``
                     CSV.  The LLHR figure points ride the fleet rollout
                     (one device call per point).
* ``--bench``      — the perf pipeline: runs ``bench_placement``,
                     ``bench_kernels``, ``bench_scenario_engine``,
                     ``bench_positions``, ``bench_rollout``,
                     ``bench_multisource``,
                     ``bench_chaos`` and ``bench_gateway`` at full
                     size and writes the corresponding ``BENCH_*.json``
                     files (wall-clock, compile time, speedups vs the
                     NumPy oracle, the PR 1 tracer, the scalar P2 loop,
                     the legacy per-frame SwarmSim loop, and the
                     per-source solve loop) into ``--out``.
* ``--smoke``      — same pipeline at tiny B/U/L (CI-sized, CPU-friendly)
                     PLUS the rebased fig2-5 scripts in --smoke mode, so
                     the paper-figure path is exercised in CI; agreement,
                     feasibility, parity and zero-retrace asserts stay on,
                     speedup asserts are skipped.

Compiled programs persist across runs in JAX's compilation cache
(``repro.runtime.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` or the
checkout's ``.jax_cache/``).
"""
from __future__ import annotations

import argparse
import os
import sys

# allow `python benchmarks/run.py` from the repo root (sys.path[0] is then
# benchmarks/, not the root that holds the package)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_figures(smoke: bool = False) -> None:
    from benchmarks import (bench_kernels, fig2_latency_power,
                            fig3_latency_memory, fig4_min_power,
                            fig5_request_scaling)
    print("name,us_per_call,derived,feasibility")
    flags = ["--smoke"] if smoke else []
    for mod in (fig2_latency_power, fig3_latency_memory, fig4_min_power,
                fig5_request_scaling):
        mod.main(flags)
    if not smoke:
        bench_kernels.main([])


def run_bench(out_dir: str, smoke: bool) -> None:
    from benchmarks import (bench_chaos, bench_gateway, bench_kernels,
                            bench_multisource, bench_placement,
                            bench_positions, bench_rollout,
                            bench_scenario_engine)
    os.makedirs(out_dir, exist_ok=True)
    flags = ["--smoke"] if smoke else []
    bench_placement.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_placement.json")])
    bench_kernels.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_kernels.json")])
    bench_scenario_engine.main(
        flags + ["--json",
                 os.path.join(out_dir, "BENCH_scenario_engine.json")])
    bench_positions.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_positions.json")])
    bench_rollout.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_rollout.json")])
    bench_multisource.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_multisource.json")])
    bench_chaos.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_chaos.json")])
    bench_gateway.main(
        flags + ["--json", os.path.join(out_dir, "BENCH_gateway.json")])
    if smoke:
        # the paper-figure path rides the rollout now — exercise it in CI
        run_figures(smoke=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", action="store_true",
                    help="run the perf pipeline, write BENCH_*.json")
    ap.add_argument("--smoke", action="store_true",
                    help="perf pipeline at tiny CI sizes (implies --bench)")
    ap.add_argument("--out", type=str, default="benchmarks",
                    help="directory for BENCH_*.json artifacts")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        # CI runs under the trace-discipline sanitizer: NaN debugging on,
        # and any plan-cache retrace inside the pipeline fails the run
        # (first compiles of fresh signatures are allowed).  Full-size
        # --bench runs skip it: jax_debug_nans disables async dispatch
        # and would distort the published wall-clock numbers.
        from repro.debug import sanitized

        with sanitized():
            run_bench(args.out, smoke=True)
    elif args.bench:
        run_bench(args.out, smoke=False)
    else:
        run_figures()


if __name__ == "__main__":
    main()
