"""Driver ``rollout``: back-to-back ``FleetRollout.run`` calls.

Each call plans B trajectories over T frames with the host draws
``FleetRollout.run`` makes from a generator seeded by (seed, call), and
ends in host arrays, as ``run`` returns them.  The rate is every
trajectory-frame of every call completed in the window over the wall time
from the window's start to the end of the last call.

Traffic keys: ``trajectories`` (B), ``frames`` (T), ``use_kernels``,
``devices`` (chips the trajectory axis is sharded over), ``sample_rows``
(trajectories the check compares).
"""
from __future__ import annotations

import time

import numpy as np

from inputs import base_positions, call_rng, concat_inputs, rollout_draws
from program import make_rollout, trace_rows
from reference import Deployment, simulate
from compare import compare

SPAN = "rollout.call"


class Driver:
    span = SPAN

    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.B = int(traffic["trajectories"])
        self.T = int(traffic["frames"])
        self.n_dev = int(traffic["devices"])
        self._span = span
        self.samples = []          # (call, rows, candidate fields)
        self.calls = 0

    def setup(self) -> None:
        self.base = base_positions(self.cfg, self.seed)
        self.ro = make_rollout(self.cfg, bool(self.traffic["use_kernels"]))
        # one call at the window's own shape compiles (or loads) the one
        # program the window runs
        warm = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 2 ** 32]))
        self._run(warm)

    def _run(self, rng):
        return self.ro.run(self.base, n_trajectories=self.B, frames=self.T,
                           rng=rng, devices=self.n_dev)

    def trace_count(self) -> int:
        return self.ro.trace_count

    def window(self, seconds: float) -> dict:
        frames = failed = 0
        pool = int(self.traffic["sample_rows"])
        t0 = time.perf_counter()
        t_end = t0
        while True:
            k = self.calls
            rng = call_rng(self.seed, k)
            try:
                with self._span(SPAN):
                    tr = self._run(rng)
            except Exception as e:           # a failed call fails its frames
                print(f"rollout call {k} raised: {e!r}", flush=True)
                failed += self.B * self.T
                frames += self.B * self.T
                self.calls += 1
                break
            t_end = time.perf_counter()
            self.calls += 1
            frames += self.B * self.T
            fin = np.isfinite(tr.latency) & np.isfinite(tr.total_power) \
                & np.isfinite(tr.positions).all((2, 3)) \
                & np.isfinite(tr.charge).all(2) \
                & np.isfinite(tr.energy_tx).all(2) \
                & np.isfinite(tr.energy_cmp).all(2)
            failed += int((tr.feasible & ~fin).sum())
            rows = np.sort(np.random.default_rng(np.random.SeedSequence(
                [self.seed, k, 1])).choice(self.B, min(pool, self.B),
                                           replace=False))
            self.samples.append((k, rows, trace_rows(tr, rows)))
            del tr
            if t_end - t0 >= seconds:
                break
        self.counts = (frames, failed)
        return {"metrics": {"rollout_frames_per_s":
                            frames / max(t_end - t0, 1e-9)},
                "calls": self.calls}

    def finish(self):
        """Every call of the window ended in host arrays, so nothing is
        pending.  Returns (attempted, failed) trajectory-frames."""
        return self.counts

    def release(self) -> None:
        """Drop the program's compiled state before the reference runs."""
        self.ro = None

    def check(self) -> dict:
        """The sampled trajectories against the reference."""
        pool = int(self.traffic["sample_rows"])
        picks = np.random.default_rng(np.random.SeedSequence(
            [self.seed, 3])).permutation(
                sum(len(r) for _, r, _ in self.samples))[:pool]
        flat = [(i, j) for i, (_, rows, _) in enumerate(self.samples)
                for j in range(len(rows))]
        chosen = {}
        for p in sorted(picks):
            i, j = flat[p]
            chosen.setdefault(i, []).append(j)
        cands, inputs = [], []
        for i, js in sorted(chosen.items()):
            k, rows, fields = self.samples[i]
            inputs.append(rollout_draws(self.cfg, self.base, self.B, self.T,
                                        call_rng(self.seed, k),
                                        rows=rows[js]))
            cands.append({f: v[js] for f, v in fields.items()})
        cand = {f: np.concatenate([c[f] for c in cands]) for f in cands[0]}
        inp = concat_inputs(inputs)
        dep = Deployment.from_config(self.cfg)
        ref = simulate(dep, inp, np.float64, forced=cand)
        return compare(cand, ref, dep.battery_j), \
            f"{len(picks)} trajectories of {len(chosen)} calls"
