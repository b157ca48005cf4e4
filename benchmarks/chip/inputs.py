"""Inputs made from the seed: the swarm's base positions and the host
draws a rollout call consumes.

``FleetRollout.run(..., rng=g)`` draws, from the generator it is handed
and in this order: waypoints (only when ``waypoint_range_m`` > 0), the
mobility jitter (only when ``jitter_sigma_m`` > 0), the failure and
recovery uniforms, and, when no arrivals are passed, the per-frame
multinomial arrivals over a uniform swarm.  ``rollout_draws`` makes the
same draws from a generator seeded alike, so the reference sees exactly
the inputs the program saw without reading anything the program made.
"""
from __future__ import annotations

import math

import numpy as np


def base_positions(cfg: dict, seed: int) -> np.ndarray:
    """[U, 2] hexagonal packing at ``spacing_m``, centred, nearest points
    first, with Gaussian jitter drawn from ``seed``."""
    bp = cfg["base_positions"]
    if bp["layout"] != "hex":
        raise ValueError(f"unknown base layout {bp['layout']!r}")
    U, sp = int(cfg["uavs"]), float(bp["spacing_m"])
    side = int(math.ceil(math.sqrt(U))) + 2
    pts = np.array([(c * sp + (sp / 2.0 if r % 2 else 0.0),
                     r * sp * math.sqrt(3.0) / 2.0)
                    for r in range(side) for c in range(side)])
    pts -= pts.mean(axis=0)
    pts = pts[np.argsort((pts ** 2).sum(1), kind="stable")[:U]]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    return pts + rng.normal(scale=bp["jitter_m"], size=pts.shape)


def call_rng(seed: int, call: int) -> np.random.Generator:
    """The generator handed to rollout call ``call`` of a run."""
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(call)]))


def rollout_draws(cfg: dict, base: np.ndarray, B: int, T: int,
                  rng: np.random.Generator, arrivals=None,
                  rows=None) -> dict:
    """The inputs of one rollout call of B trajectories and T frames,
    restricted to trajectory ``rows`` (all by default).  ``arrivals``
    [T, B, U] stands for the call's own arrival tensor when it passed
    one."""
    rs = cfg["rollout_spec"]
    U = int(cfg["uavs"])
    pos0 = np.broadcast_to(np.asarray(base, np.float64),
                           (B, U, 2)).astype(np.float32)
    waypoint = pos0.copy()
    if rs["waypoint_range_m"] > 0:
        waypoint = waypoint + rng.uniform(
            -rs["waypoint_range_m"], rs["waypoint_range_m"],
            size=(B, U, 2)).astype(np.float32)
    jitter = np.zeros((T, B, U, 2), np.float32)
    if rs["jitter_sigma_m"] > 0:
        jitter = rng.normal(scale=rs["jitter_sigma_m"],
                            size=(T, B, U, 2)).astype(np.float32)
    fail_u = rng.random((T, B, U)).astype(np.float32)
    recov_u = rng.random((T, B, U)).astype(np.float32)
    if arrivals is None:
        arrivals = rng.multinomial(rs["requests_per_frame"],
                                   np.full(U, 1.0 / U), size=(T, B))
    arrivals = np.asarray(arrivals, np.float32)
    rows = np.arange(B) if rows is None else np.asarray(rows)
    return {"pos0": pos0[rows], "waypoint": waypoint[rows],
            "charge0": np.full((len(rows), U), rs["battery_j"], np.float32),
            "alive0": np.ones((len(rows), U), bool),
            "jitter": jitter[:, rows], "fail_u": fail_u[:, rows],
            "recov_u": recov_u[:, rows], "arrivals": arrivals[:, rows]}


#: inputs whose leading axis is the frame, not the trajectory
TIME_MAJOR = ("jitter", "fail_u", "recov_u", "arrivals")


def concat_inputs(parts) -> dict:
    """Stack the inputs of several calls along the trajectory axis."""
    return {k: np.concatenate([p[k] for p in parts],
                              axis=1 if k in TIME_MAJOR else 0)
            for k in parts[0]}
