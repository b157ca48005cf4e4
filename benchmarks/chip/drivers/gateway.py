"""Driver ``gateway``: back-to-back serving windows of ``StreamingGateway``.

An open-loop ``LoadGenerator`` offers requests on the gateway's virtual
frame clock; each ``serve(gen, n_windows=1, drain=False)`` call schedules
the queue into one window's ``[T, 1, U]`` arrival tensor, ingests the
window's arrivals, makes the one B = 1 ``FleetRollout.run`` device call
and collects it.  The window metrics are the wall time of each such call.
After the window the queue is flushed with arrival-free windows (untimed)
so that every request submitted in the window reaches an outcome.

Traffic keys: ``window_frames``, ``frame_s``, ``queue_capacity``,
``arrival`` (``kind``, ``rate_per_frame``, ``deadline_s``),
``use_kernels``, ``warm_windows``, ``sample_windows`` (windows the check
compares).
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from inputs import base_positions, concat_inputs, rollout_draws
from program import make_rollout, trace_rows
from reference import Deployment, simulate
from compare import compare

SPAN = "gateway.window"


class _Recording:
    """The gateway's rollout, with every window's trace kept for the
    check (the gateway itself keeps only feasibility and latency).  The
    window being solved is the one scheduled last: ``serve`` with
    ``n_windows=1`` schedules, ingests, dispatches and collects one
    window, and a retried solve overwrites its own entry."""

    def __init__(self, ro):
        self._ro = ro
        self.devices, self.spec = ro.devices, ro.spec
        self.traces = {}
        self.gateway = None

    def run(self, *args, **kw):
        tr = self._ro.run(*args, **kw)
        w = len(self.gateway.arrival_tensors) - 1
        self.traces[w] = trace_rows(tr, [0])
        return tr


class Driver:
    span = SPAN

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self._span = span
        self.T = int(traffic["window_frames"])

    def setup(self) -> None:
        from repro.runtime.gateway import (GatewayConfig, LoadGenerator,
                                           StreamingGateway)
        tr = self.traffic
        self.base = base_positions(self.cfg, self.seed)
        self.ro = make_rollout(self.cfg, bool(tr["use_kernels"]))
        self.rec = _Recording(self.ro)
        gw_seed, gen_seed = np.random.SeedSequence(
            [self.seed, 11]).generate_state(2)
        self.gw_seed = int(gw_seed)
        self.gw = StreamingGateway(
            self.rec, self.base,
            GatewayConfig(window_frames=self.T, frame_s=tr["frame_s"],
                          queue_capacity=tr["queue_capacity"]),
            seed=self.gw_seed)
        self.rec.gateway = self.gw
        arr = tr["arrival"]
        self.gen = LoadGenerator(
            len(self.ro.devices), kind=arr["kind"],
            rate=arr["rate_per_frame"], deadline_s=arr["deadline_s"],
            seed=int(gen_seed))
        # the first windows compile (or load) the one B = 1 program
        for _ in range(int(tr["warm_windows"])):
            self.gw.serve(self.gen, n_windows=1, drain=False)

    def trace_count(self) -> int:
        return self.ro.trace_count

    def window(self, seconds: float) -> dict:
        gw = self.gw
        self.rid0, self.w0 = gw._next_rid, gw._window
        times = []
        t0 = time.perf_counter()
        while True:
            s = time.perf_counter()
            with self._span(SPAN):
                gw.serve(self.gen, n_windows=1, drain=False)
            # serve() returns once the window's results are host arrays;
            # blocking on them marks that point for the timing
            jax.block_until_ready(self.rec.traces[gw._window - 1])
            e = time.perf_counter()
            times.append(e - s)
            if e - t0 >= seconds:
                break
        self.rid1, self.w1 = gw._next_rid, gw._window
        ms = np.asarray(times) * 1e3
        return {"metrics": {"serve_window_p50_ms": float(np.percentile(ms, 50)),
                            "serve_window_p95_ms": float(np.percentile(ms, 95))},
                "calls": len(times)}

    def finish(self) -> tuple:
        """Flush, after the window and untimed: arrival-free windows until
        every deadline has passed, so that each request submitted in the
        window has its outcome.  Returns (attempted, failed).

        A request shed because its frame was solved infeasible (for
        instance with its source UAV down) got the planner's answer, as an
        infeasible frame does in the rollout cells, and ``check`` holds
        that answer against the reference; every other outcome (shed for
        want of capacity, a deadline, a lost window, or never decided) is
        a failure."""
        gw = self.gw
        fs, T = self.traffic["frame_s"], self.T
        flush = int(math.ceil(self.traffic["arrival"]["deadline_s"]
                              / (fs * T))) + 1
        report = gw.serve(None, n_windows=flush, drain=True)
        gw.close()
        print(f"gateway report (virtual clock): {report}", flush=True)
        reqs = gw.requests[self.rid0:self.rid1]
        outcomes = {}
        for r in reqs:
            outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
        print(f"window's requests by outcome: {outcomes}", flush=True)
        answered = outcomes.get("served", 0) + \
            outcomes.get("shed_infeasible_frame", 0)
        return len(reqs), len(reqs) - answered

    def release(self) -> None:
        self.ro = None
        self.rec._ro = None

    def check(self) -> dict:
        """Sampled windows (and the busiest) against the reference, and
        every request of those windows against the outcome the reference
        gives it: a request served on a frame the reference cannot serve,
        or shed from one it can, makes ``lat_rel`` infinite."""
        gw, T = self.gw, self.T
        ws = np.arange(self.w0, self.w1)
        n = min(int(self.traffic["sample_windows"]), len(ws))
        pick = set(np.random.default_rng(np.random.SeedSequence(
            [self.seed, 3])).choice(ws, n, replace=False).tolist())
        load = [gw.arrival_tensors[w].sum() for w in ws]
        pick.add(int(ws[int(np.argmax(load))]))
        pick = sorted(pick)
        inputs, cands = [], []
        for w in pick:
            rng = np.random.default_rng(np.random.SeedSequence(
                [self.gw_seed, w]))
            inputs.append(rollout_draws(self.cfg, self.base, 1, T, rng,
                                        arrivals=gw.arrival_tensors[w]))
            cands.append(self.rec.traces[w])
        cand = {f: np.concatenate([c[f] for c in cands]) for f in cands[0]}
        dep = Deployment.from_config(self.cfg)
        ref = simulate(dep, concat_inputs(inputs), np.float64, forced=cand)
        numbers = compare(cand, ref, dep.battery_j)
        # each request's outcome, and the arrival tensors it was packed in
        row = {w: i for i, w in enumerate(pick)}
        bad = seen = 0
        counts = {w: np.zeros((T, len(self.base))) for w in pick}
        for r in gw.requests:
            if r.outcome == "queued":
                bad += 1
            if r.window not in row or r.outcome.startswith("shed_") and \
                    r.outcome != "shed_infeasible_frame":
                continue
            i, t = row[r.window], r.frame - r.window * T
            counts[r.window][t, r.uav] += 1
            seen += 1
            ok = bool(ref["feasible"][i, t]) and \
                bool(np.isfinite(ref["source_latency"][i, t, r.uav]))
            want = "served" if ok else "shed_infeasible_frame"
            bad += int(r.outcome != want)
        for w in pick:
            bad += int((counts[w] != gw.arrival_tensors[w][:, 0]).sum())
        if bad:
            # a request whose outcome the reference does not give it, or
            # one the arrival tensors do not account for, is wrong
            numbers["lat_rel"] = float("inf")
        return numbers, (f"{len(pick)} windows and their {seen} requests, "
                         f"{bad} outcomes the reference does not give")
