"""Kernel microbenchmarks — model-layer CSV figures + planner-kernel JSON.

Two modes share this module:

* ``main([])`` (no ``--json``) — the historical CSV microbench of the
  MODEL kernels (attention, rGLRU, MoE, conv, mLSTM).  On this CPU
  container the Pallas kernels run in interpret mode (Python) so their
  wall time is meaningless; what we benchmark is (a) the pure-jnp
  reference path wall time (the compute the kernels replace), and (b) the
  analytic FLOPs each call covers (derived column = GFLOP/call) so
  per-chip TPU time = derived / 197e12 at peak.
* ``main(["--json", path])`` — the PLANNER kernels (ISSUE 9): the
  tropical-DP wavefront step and the fused link-geometry kernel, timed
  against the jnp oracles they replace and bitwise-checked against them.
  Registered in ``run.py --bench/--smoke`` -> ``BENCH_kernels.json``.

``BENCH_kernels.json`` schema (all timings seconds, best-of-N):

* ``backend``/``config``            — jax backend + run sizes.
* ``<kernel>.config``               — operand shapes + the autotuned
                                      block table row the launch used.
* ``<kernel>.jnp``                  — the jitted jnp oracle:
                                      ``first_call_s`` (trace + compile
                                      + solve) and ``steady_s``.
* ``<kernel>.kernel``               — the Pallas path, same fields, plus
                                      ``mode``: "interpret" on CPU/GPU
                                      (the kernel body is traced into the
                                      jitted program — compiled XLA, not
                                      a Python-loop interpreter at
                                      steady state) or "compiled" when
                                      the backend lowers Pallas natively
                                      (TPU).  Compiled-TPU/GPU timings
                                      are NOT reachable from this CPU
                                      container; rerun there to fill
                                      them.
* ``<kernel>.steady_ratio_vs_jnp``  — kernel steady / jnp steady
                                      (<= 1 means the kernel path is
                                      no slower).
* ``<kernel>.bitwise_agree``        — all outputs bit-identical to the
                                      jitted oracle (asserted).
* ``<kernel>.arithmetic_intensity_flop_per_byte`` — analytic AI at the
                                      benchmarked shape.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

try:
    from benchmarks.common import emit
except ModuleNotFoundError:  # invoked as `python benchmarks/bench_kernels.py`
    from common import emit

KEY = jax.random.PRNGKey(0)


def timeit(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


# ---------------------------------------------------------------------------
# model-kernel CSV figures (unchanged contract: run.py figure mode)
# ---------------------------------------------------------------------------


def bench_flash() -> None:
    from repro.kernels.flash_attention.ref import attention_ref
    b, h, kv, s, d = 1, 8, 8, 1024, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kv, s, d), jnp.float32)
    fn = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us = timeit(fn, q, k, v)
    gflop = 2 * 2 * b * h * s * s / 2 * d / 1e9
    emit(f"kernel/flash_attention/b{b}h{h}s{s}d{d}", us, f"{gflop:.2f}")


def bench_decode() -> None:
    from repro.kernels.decode_attention.ref import decode_ref
    b, kv, g, s, d = 8, 8, 4, 8192, 64
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (b, kv, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kv, s, d), jnp.float32)
    pos = jnp.full((b,), s - 1)
    fn = jax.jit(lambda q, k, v, p: decode_ref(q, k, v, p))
    us = timeit(fn, q, k, v, pos)
    gflop = 2 * 2 * b * kv * g * s * d / 1e9
    emit(f"kernel/decode_attention/b{b}kv{kv}s{s}", us, f"{gflop:.2f}")


def bench_rglru() -> None:
    from repro.kernels.rglru_scan.ref import rglru_ref
    b, t, w = 4, 2048, 1024
    ks = jax.random.split(KEY, 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, w)))
    bb = jax.random.normal(ks[1], (b, t, w)) * 0.1
    h0 = jax.random.normal(ks[2], (b, w))
    fn = jax.jit(lambda a, b_, h: rglru_ref(a, b_, h)[0])
    us = timeit(fn, a, bb, h0)
    gb = 3 * b * t * w * 4 / 1e9
    emit(f"kernel/rglru_scan/b{b}t{t}w{w}", us, f"{gb:.3f}GB")


def bench_moe() -> None:
    from repro.kernels.moe_matmul.ref import moe_matmul_ref
    e, c, d, f = 16, 256, 512, 1024
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32)
    w = jax.random.normal(ks[1], (e, d, f), jnp.float32)
    fn = jax.jit(moe_matmul_ref)
    us = timeit(fn, x, w)
    gflop = 2 * e * c * d * f / 1e9
    emit(f"kernel/moe_matmul/e{e}c{c}d{d}f{f}", us, f"{gflop:.2f}")


def bench_conv() -> None:
    from repro.kernels.conv2d.ref import conv2d_ref
    n, hw, cin, cout, k = 8, 27, 96, 256, 5
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (n, hw, hw, cin))
    w = jax.random.normal(ks[1], (k, k, cin, cout)) * 0.1
    b = jnp.zeros((cout,))
    fn = jax.jit(lambda x, w, b: conv2d_ref(x, w, b, padding=2))
    us = timeit(fn, x, w, b)
    gflop = 2 * n * hw * hw * k * k * cin * cout / 1e9
    emit(f"kernel/conv2d/alexnet-conv2", us, f"{gflop:.2f}")


def bench_mlstm() -> None:
    from repro.models.recurrent import (mlstm_init, mlstm_seq,
                                        mlstm_seq_ref, mlstm_state)
    p = mlstm_init(KEY, 256, 4, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1024, 256))
    st = mlstm_state(2, 4, 64)
    fn_c = jax.jit(lambda p, x, s: mlstm_seq(p, x, s, chunk=128)[0])
    fn_r = jax.jit(lambda p, x, s: mlstm_seq_ref(p, x, s)[0])
    us_c = timeit(fn_c, p, x, st, iters=3)
    us_r = timeit(fn_r, p, x, st, iters=3)
    emit("kernel/mlstm_chunkwise/b2s1024d256", us_c,
         f"seq_ref={us_r:.0f}us speedup={us_r / us_c:.1f}x")


def run_figures() -> None:
    bench_flash()
    bench_decode()
    bench_rglru()
    bench_moe()
    bench_conv()
    bench_mlstm()


# ---------------------------------------------------------------------------
# planner kernels (ISSUE 9): tropical DP + fused link geometry -> JSON
# ---------------------------------------------------------------------------


def _time_paths(ref_fn, kernel_fn, args, repeats: int):
    """Time the jnp oracle and the kernel path on the SAME operands and
    assert every output bit-identical.  BOTH sides are wrapped in one
    ``jax.jit`` by the callers — the planner only ever invokes either
    inside its compiled plan program, so the contract under test is the
    traced-program cost, not Python-entry dispatch overhead (and
    jit-vs-eager differs in the last ulp anyway: XLA fuses with FMA)."""

    def once(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t0
        steady = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            steady.append(time.perf_counter() - t0)
        return {"first_call_s": first,
                "steady_s": float(np.min(steady))}, out

    ref_t, ref_out = once(ref_fn)
    ker_t, ker_out = once(kernel_fn)
    agree = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(ker_out, ref_out))
    assert agree, "kernel diverged bitwise from its jnp oracle"
    return ref_t, ker_t, agree


def _kernel_mode() -> str:
    from repro.kernels import resolve_interpret
    return "interpret" if resolve_interpret(None) else "compiled"


def bench_tropical_dp(B: int, M: int, L: int, S: int,
                      repeats: int) -> Dict:
    from repro.kernels import autotune
    from repro.kernels.tropical_dp.ops import dp_wavefront_step
    from repro.kernels.tropical_dp.ref import dp_step_ref
    from repro.kernels.tropical_dp.tropical_dp import to_lanes
    rng = np.random.default_rng(0)
    dp = rng.uniform(0, 10, (B, M, L, S + 1)).astype(np.float32)
    dp[:, :, 0, :] = np.inf
    dp[:, :, 0, 0] = 0.0
    tr = rng.uniform(0, 5, (B, L, S, S + 1)).astype(np.float32)
    tr[:, 0] = np.inf
    tr0 = rng.uniform(0, 5, (B, M, S)).astype(np.float32)
    ct = rng.uniform(0, 2, (L, S)).astype(np.float32)
    ok = (rng.random((L, S)) > 0.1).astype(np.float32)
    # the kernel's layout: scenarios last, split into 128-lane rows
    last = (lambda x: to_lanes(jnp.moveaxis(jnp.asarray(x), 0, -1)))
    args = (last(dp), last(tr), last(tr0), jnp.asarray(ct), jnp.asarray(ok))
    ref_t, ker_t, agree = _time_paths(
        jax.jit(dp_step_ref),
        jax.jit(functools.partial(dp_wavefront_step, use_kernel=True)),
        args, repeats)
    # one wavefront step: [B,M,L,S] x S+1 min-plus contraction + two
    # argmin reductions ~ 3 flop-equivalents per contraction element
    flop = 3.0 * B * M * L * S * (S + 1)
    bytes_ = 4.0 * (dp.size + tr.size + tr0.size + ct.size + ok.size
                    + 3 * B * M * S)
    return {
        "config": {"B": B, "M": M, "L": L, "S": S,
                   "blocks": autotune.lookup("tropical_dp", U=S, L=L, S=S,
                                             dtype="float32")},
        "jnp": ref_t,
        "kernel": {**ker_t, "mode": _kernel_mode()},
        "steady_ratio_vs_jnp": ker_t["steady_s"] / ref_t["steady_s"],
        "bitwise_agree": agree,
        "gflop_per_call": flop / 1e9,
        "arithmetic_intensity_flop_per_byte": flop / bytes_,
    }


def bench_link_geometry(B: int, U: int, repeats: int) -> Dict:
    from repro.core.channel import RadioParams
    from repro.kernels import autotune
    from repro.kernels.link_geometry.ops import fused_link_geometry
    from repro.kernels.link_geometry.ref import link_geometry_ref
    params = RadioParams()
    rng = np.random.default_rng(1)
    pos = jnp.asarray(rng.uniform(0, 400, (B, U, 2)), jnp.float32)
    active = jnp.asarray(rng.random((B, U)) > 0.1)
    g = rng.uniform(0.5, 1.5, (B, U, U))
    gain = jnp.asarray((g + g.transpose(0, 2, 1)) / 2, jnp.float32)
    args = (pos, active, gain)
    ref_t, ker_t, agree = _time_paths(
        jax.jit(functools.partial(link_geometry_ref, params=params)),
        jax.jit(lambda p, a, gs: fused_link_geometry(
            p, params, active=a, gain_scale=gs, use_kernel=True)),
        args, repeats)
    # dist (5/pair incl. sqrt) + gain/threshold (4) + row-max power (2) +
    # rate log2 chain (6) per [B,U,U] entry
    flop = 17.0 * B * U * U
    bytes_ = 4.0 * (pos.size + active.size + gain.size + 3 * B * U * U)
    return {
        "config": {"B": B, "U": U,
                   "blocks": autotune.lookup("link_geometry", U=U,
                                             dtype="float32")},
        "jnp": ref_t,
        "kernel": {**ker_t, "mode": _kernel_mode()},
        "steady_ratio_vs_jnp": ker_t["steady_s"] / ref_t["steady_s"],
        "bitwise_agree": agree,
        "gflop_per_call": flop / 1e9,
        "arithmetic_intensity_flop_per_byte": flop / bytes_,
    }


def run(smoke: bool = False, repeats: int = 10) -> Dict:
    if smoke:
        dp_cfg = dict(B=4, M=2, L=4, S=4)
        geo_cfg = dict(B=4, U=4)
        repeats = min(repeats, 3)
    else:
        dp_cfg = dict(B=64, M=8, L=12, S=8)
        geo_cfg = dict(B=256, U=16)
    result: Dict = {
        "benchmark": "planner_kernels",
        "backend": jax.default_backend(),
        "config": {"smoke": smoke, "repeats": repeats,
                   "tropical_dp": dp_cfg, "link_geometry": geo_cfg},
    }
    td = bench_tropical_dp(repeats=repeats, **dp_cfg)
    result["tropical_dp"] = td
    print(f"tropical_dp  : jnp {td['jnp']['steady_s'] * 1e3:7.2f} ms, "
          f"kernel({td['kernel']['mode']}) "
          f"{td['kernel']['steady_s'] * 1e3:7.2f} ms, ratio "
          f"{td['steady_ratio_vs_jnp']:.2f}, bitwise={td['bitwise_agree']}")
    lg = bench_link_geometry(repeats=repeats, **geo_cfg)
    result["link_geometry"] = lg
    print(f"link_geometry: jnp {lg['jnp']['steady_s'] * 1e3:7.2f} ms, "
          f"kernel({lg['kernel']['mode']}) "
          f"{lg['kernel']['steady_s'] * 1e3:7.2f} ms, ratio "
          f"{lg['steady_ratio_vs_jnp']:.2f}, bitwise={lg['bitwise_agree']}")
    assert td["bitwise_agree"] and lg["bitwise_agree"]
    if not smoke:
        # CPU acceptance: the whole-axis-block kernel body is the same
        # vectorized program XLA compiles for the jnp path, so the kernel
        # must not regress it (ratio <= 1 + noise)
        for name, sec in (("tropical_dp", td), ("link_geometry", lg)):
            assert sec["steady_ratio_vs_jnp"] <= 1.10, \
                f"{name} kernel path slower than the jnp oracle"
        print("PASS: both planner kernels bitwise-exact and no slower "
              "than jnp")
    return result


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-sized planner-kernel run")
    ap.add_argument("--json", type=str, default=None,
                    help="write the planner-kernel result dict to this "
                         "path (selects the JSON mode; without it the "
                         "model-kernel CSV figures run)")
    ap.add_argument("--repeats", type=int, default=10)
    args = ap.parse_args(argv)
    if args.json is None and not args.smoke:
        run_figures()
        return {}
    result = run(smoke=args.smoke, repeats=args.repeats)
    if args.json:
        out_dir = os.path.dirname(args.json)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return result


if __name__ == "__main__":
    main()
