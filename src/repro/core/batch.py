"""Batched LLHR planning primitives — the NumPy oracles lifted to a leading
scenario axis in pure ``jnp``.

Everything here mirrors an existing scalar implementation elementwise:

* ``power_threshold_batched`` / ``solve_power_batched``   <-> ``power.solve_power``
  (closed-form P1, eq. 6-7)
* ``rate_matrix_batched``                                 <-> ``PowerSolution.rate_matrix``
  (eq. 5 at the solved powers, zeroed on infeasible links)
* ``solve_chain_dp_batched``                              <-> ``placement.solve_chain_dp``
  (contiguous-block chain DP, P3 fast path)
* ``solve_chain_dp_multisource``                          <-> ``placement.place_requests``
  (the DP vmapped over the frame's source axis; the stream's aggregate
  per-UAV load is priced exactly by ``placement_compute_load`` +
  ``shared_cap_feasible`` — eq. 11b over the whole request stream)
* ``solve_positions_batched``                             <-> ``positions.solve_positions_legacy``
  (P2 projected-gradient descent on eq. 9, separation repair on device)

The scalar NumPy versions stay the reference oracles; the batched paths are
tested elementwise against them (``tests/test_batch_engine.py``) and power the
fleet-scale scenario engine in ``repro.runtime.scenario_engine``.  All
functions are pure, ``vmap``/``jit``-compatible, and take an optional

* ``active``      [B,U]   bool — False marks a failed UAV: zero power, no
                          links, and the chain DP refuses to host layers on it
                          (the paper's delegation semantics, batched);
* ``gain_scale``  [B,U,U] multiplicative channel-gain factor (log-normal
                          shadowing draws from the scenario generator).

Shapes use B = scenarios, U = UAVs, L = layers.  Computation runs in JAX's
default float32; the oracle tests compare at 1e-5 relative tolerance.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import RadioParams


# ---------------------------------------------------------------------------
# Geometry + channel (eq. 4, 5, 7), batched
# ---------------------------------------------------------------------------


def pairwise_dist_batched(positions: jnp.ndarray) -> jnp.ndarray:
    """[..., U, 2] positions -> [..., U, U] Euclidean distances."""
    x, y = positions[..., 0], positions[..., 1]
    dx = x[..., :, None] - x[..., None, :]
    dy = y[..., :, None] - y[..., None, :]
    return jnp.sqrt(dx ** 2 + dy ** 2)


# The channel formulas below fold every constant into ONE Python float per
# expression and divide only by arrays: XLA on TPU rewrites a divide by a
# constant into a multiply by its reciprocal (and A / (B / C) into
# A * C / B) while Mosaic divides, so any other spelling rounds differently
# in the jnp planner and in the link-geometry kernel, which computes the
# same expressions (``link_constants``).


def link_constants(params: RadioParams, bits: Optional[float] = None
                   ) -> Tuple[float, float, float]:
    """(threshold, snr, rate) coefficients of eq. (7) and eq. (5) with the
    eq. (4) gain h0 / d^2 folded in:

        threshold = d^2 / gain_scale * noise (2^(bits / (B tau)) - 1) / h0
        rate      = ln(1 + p gain_scale h0 / (noise d^2)) * B / ln 2
    """
    bits = params.packet_bits if bits is None else bits
    spectral = bits * math.log(2.0) / (params.bandwidth_hz * params.tau)
    return (params.noise_watts * (math.exp(spectral) - 1.0) / params.h0,
            params.h0 / params.noise_watts,
            params.bandwidth_hz / math.log(2.0))


def power_threshold_batched(dist: jnp.ndarray, params: RadioParams,
                            bits: Optional[float] = None,
                            gain_scale: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """eq. (7): minimum power delivering ``bits`` within tau, per link, at
    the eq. (4) gain with the same d0 = 1 m clamp as ``RadioChannel.gain``."""
    d2 = jnp.maximum(dist, 1.0) ** 2
    if gain_scale is not None:
        d2 = d2 / gain_scale
    return d2 * link_constants(params, bits)[0]


@dataclass(frozen=True)
class BatchPowerSolution:
    """Batched twin of ``power.PowerSolution`` (arrays gain a leading B)."""

    power: jnp.ndarray          # [B, U]
    threshold: jnp.ndarray      # [B, U]
    feasible: jnp.ndarray       # [B, U] bool
    link_feasible: jnp.ndarray  # [B, U, U] bool
    total_power: jnp.ndarray    # [B]


def solve_power_batched(dist: jnp.ndarray, params: RadioParams,
                        links: Optional[jnp.ndarray] = None,
                        active: Optional[jnp.ndarray] = None,
                        gain_scale: Optional[jnp.ndarray] = None,
                        threshold_matrix: Optional[jnp.ndarray] = None
                        ) -> BatchPowerSolution:
    """Closed-form P1 (eq. 6-7) over a scenario batch; mirrors
    ``power.solve_power`` elementwise on each scenario's (sub)swarm.

    A failed UAV (``active`` False) binds no link and transmits at zero power,
    exactly as if it were deleted from the scalar problem.  Pass
    ``threshold_matrix`` (a prior ``power_threshold_batched`` result for the
    same dist/gain_scale) to skip recomputing eq. (7).
    """
    U = dist.shape[-1]
    p_max = params.p_max_watts
    eye = jnp.eye(U, dtype=bool)
    if threshold_matrix is None:
        threshold_matrix = power_threshold_batched(dist, params,
                                                   gain_scale=gain_scale)
    th = jnp.where(eye, 0.0, threshold_matrix)
    link_feasible = th <= p_max                      # diag: th=0 -> True
    if active is not None:
        pair = active[..., :, None] & active[..., None, :]
        link_feasible = link_feasible & (pair | eye)
    use = link_feasible if links is None else (links & link_feasible)
    threshold = jnp.where(use & ~eye, th, 0.0).max(-1)
    power = jnp.minimum(threshold, p_max)
    feasible = threshold <= p_max
    if active is not None:
        power = jnp.where(active, power, 0.0)
        threshold = jnp.where(active, threshold, 0.0)
    return BatchPowerSolution(power=power, threshold=threshold,
                              feasible=feasible, link_feasible=link_feasible,
                              total_power=power.sum(-1))


def rate_matrix_batched(dist: jnp.ndarray, power: jnp.ndarray,
                        params: RadioParams, link_feasible: jnp.ndarray,
                        gain_scale: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """eq. (5) at the solved powers: rho_{i,k} [B,U,U]; 0 on infeasible
    links, inf on the diagonal (self-transfer is free)."""
    U = dist.shape[-1]
    _, snr_c, rate_c = link_constants(params)
    snr = power[..., :, None] * snr_c
    if gain_scale is not None:
        snr = snr * gain_scale
    snr = snr / jnp.maximum(dist, 1.0) ** 2
    rate = jnp.log(1.0 + snr) * rate_c
    rate = jnp.where(link_feasible, rate, 0.0)
    return jnp.where(jnp.eye(U, dtype=bool), jnp.inf, rate)


# ---------------------------------------------------------------------------
# Batched P2 — UAV positions (eq. 8-9), repair on device
# ---------------------------------------------------------------------------


def position_coeff(params: RadioParams) -> float:
    """The eq. (9) per-link power weight: sigma^2/h0 * (2^(K/(B tau)) - 1).
    Minimizing sum of coeff * d^2 over links is the paper's P2 objective."""
    return (params.noise_watts / params.h0) * \
        (math.exp(params.packet_bits * math.log(2.0) /
                  (params.bandwidth_hz * params.tau)) - 1.0)


def coverage_radius(n_uavs: int, radius: float) -> float:
    """Coverage-circle radius (eq. 8c) big enough to hold a 2R-separated
    packing of ``n_uavs`` — the same bound the legacy scalar solver uses."""
    return max(radius, 2.0 * radius * (math.sqrt(float(n_uavs)) + 1.0))


def chain_links(n_uavs: int,
                order: Optional[Sequence[int]] = None) -> np.ndarray:
    """[U, U] bool chain-links mask i -> i+1 (walked in ``order`` if given) —
    the placement pipeline's shape, and P2's default topology."""
    links = np.zeros((n_uavs, n_uavs), dtype=bool)
    idx = list(order) if order is not None else list(range(n_uavs))
    for a, b in zip(idx[:-1], idx[1:]):
        links[a, b] = True
    return links


@partial(jax.jit, static_argnames=("steps", "repair_iters"))
def _positions_pgd(pos0: jnp.ndarray, links: jnp.ndarray, coeff: jnp.ndarray,
                   lr: jnp.ndarray, two_r: jnp.ndarray, cover_r: jnp.ndarray,
                   center: jnp.ndarray, steps: int, repair_iters: int):
    """Projected-gradient P2 over a scenario batch, fully on device.

    Forward pass: ``steps`` iterations of normalized gradient descent on the
    eq. (9) objective plus the smooth separation hinge (eq. 8d), each step
    projected onto the coverage circle (eq. 8c).  The scan carries the
    best-so-far iterate per scenario, so the emitted objective trace is
    monotonically non-increasing BY CONSTRUCTION and the returned solution is
    the trajectory argmin (an anytime solver), not just the last iterate.

    Repair pass: the legacy host-side NumPy argmin loop
    (``positions.solve_positions_legacy``) becomes a second fixed-length
    ``lax.scan``: each iteration finds the worst-separated pair PER SCENARIO
    and pushes it symmetrically to 2R + 2e-3 about its midpoint, guarded to a
    no-op once the minimum pairwise distance clears 2R.  No host round-trip.

    Args: pos0 [B, U, 2] initialization; links [B, U, U] bool (symmetrized
    here); coeff/lr/two_r/cover_r scalars; center [B, 2] coverage-circle
    centers.  Returns (positions [B, U, 2], link objective [B], residual
    separation violation [B], objective trace [B, steps]).
    """
    U = pos0.shape[-2]
    B = pos0.shape[0]
    eye = jnp.eye(U, dtype=bool)
    links = links | jnp.swapaxes(links, -1, -2)

    def objective(pos):                                             # [B]
        d2 = ((pos[..., :, None, :] - pos[..., None, :, :]) ** 2).sum(-1)
        obj = jnp.where(links, coeff * d2, 0.0).sum((-2, -1)) / 2.0
        viol = jnp.maximum(two_r ** 2 - d2, 0.0)
        pen = jnp.where(eye, 0.0, viol ** 2).sum((-2, -1))
        return obj + 10.0 * coeff * pen

    def project(pos):
        rel = pos - center[:, None, :]
        r = jnp.linalg.norm(rel, axis=-1, keepdims=True)
        return center[:, None, :] + \
            rel * jnp.minimum(1.0, cover_r / jnp.maximum(r, 1e-9))

    def gd(carry, _):
        pos, best_pos, best_obj = carry
        g = jax.grad(lambda p: objective(p).sum())(pos)
        gn = jnp.sqrt((g ** 2).sum((-2, -1), keepdims=True))
        pos = project(pos - lr * g / (gn + 1e-12))
        obj = objective(pos)
        better = obj < best_obj
        best_pos = jnp.where(better[:, None, None], pos, best_pos)
        best_obj = jnp.minimum(obj, best_obj)
        return (pos, best_pos, best_obj), best_obj

    pos0 = project(pos0)
    (_, pos, _), trace = jax.lax.scan(gd, (pos0, pos0, objective(pos0)),
                                      None, length=steps)

    rows = jnp.arange(B)

    def repair(pos, _):
        diff = pos[:, :, None, :] - pos[:, None, :, :]
        d = jnp.sqrt((diff ** 2).sum(-1))
        d = jnp.where(eye, jnp.inf, d)
        flat = d.reshape(B, -1)
        arg = jnp.argmin(flat, -1)
        i, k = arg // U, arg % U
        pi, pk = pos[rows, i], pos[rows, k]
        mid = (pi + pk) / 2.0
        dir_ = pi - pk
        nrm = jnp.linalg.norm(dir_, axis=-1, keepdims=True)
        # coincident pair: push along a fixed axis instead of collapsing
        dir_ = jnp.where(nrm < 1e-6, jnp.array([1.0, 0.0]), dir_ / (nrm + 1e-9))
        push = dir_ * (two_r / 2.0 + 1e-3)
        need = (flat.min(-1) < two_r - 1e-6)[:, None]
        pos = pos.at[rows, i].set(jnp.where(need, mid + push, pi))
        pos = pos.at[rows, k].set(jnp.where(need, mid - push, pk))
        return pos, None

    pos, _ = jax.lax.scan(repair, pos, None, length=repair_iters)
    d2 = ((pos[:, :, None, :] - pos[:, None, :, :]) ** 2).sum(-1)
    d = jnp.sqrt(jnp.where(eye, jnp.inf, d2))
    viol = jnp.maximum(0.0, two_r - d.min((-2, -1)))
    link_obj = jnp.where(links, coeff * d2, 0.0).sum((-2, -1)) / 2.0
    return pos, link_obj, viol, trace.T


@dataclass(frozen=True)
class BatchPositionSolution:
    """Batched twin of ``positions.PositionSolution``.

    ``objective`` is the raw eq. (9) link objective after repair;
    ``objective_trace`` is the penalized objective of the best-so-far iterate
    per GD step — monotonically non-increasing (property-tested)."""

    positions: np.ndarray        # [B, U, 2]
    objective: np.ndarray        # [B]
    max_violation: np.ndarray    # [B] residual separation violation (m)
    objective_trace: np.ndarray  # [B, steps]
    iterations: int


def solve_positions_batched(init_positions: np.ndarray,
                            params: RadioParams,
                            radius: float = 20.0,
                            links: Optional[np.ndarray] = None,
                            steps: int = 800,
                            lr: float = 0.5,
                            repair_iters: int = 50,
                            center: Optional[Tuple[float, float]] = None
                            ) -> BatchPositionSolution:
    """Batched P2 (eq. 8-9): projected gradient descent over a [B, U, 2]
    batch of initializations with the separation repair on device.

    ``links``: [U, U] or [B, U, U] bool transfer topology (default: the
    chain i -> i+1, e.g. from ``chain_links`` or a placement via
    ``links_from_assignment_batched``).  ``center``: coverage-circle center
    shared by the batch; default is each scenario's initialization centroid.
    ``positions.solve_positions`` is exactly the B = 1 slice of this path.
    """
    if hasattr(params, "params"):            # accept a RadioChannel too
        params = params.params
    pos0 = jnp.asarray(init_positions, jnp.float32)
    B, U = pos0.shape[0], pos0.shape[1]
    if links is None:
        links = chain_links(U)
    links = np.asarray(links, dtype=bool)
    if links.ndim == 2:
        links = np.broadcast_to(links, (B, U, U))
    if center is None:
        center_j = pos0.mean(axis=1)
    else:
        center_j = jnp.broadcast_to(jnp.asarray(center, jnp.float32), (B, 2))
    pos, obj, viol, trace = _positions_pgd(
        pos0, jnp.asarray(links), jnp.float32(position_coeff(params)),
        jnp.float32(lr), jnp.float32(2.0 * radius),
        jnp.float32(coverage_radius(U, radius)), center_j,
        steps, repair_iters)
    return BatchPositionSolution(
        positions=np.asarray(pos, np.float64),
        objective=np.asarray(obj, np.float64),
        max_violation=np.asarray(viol, np.float64),
        objective_trace=np.asarray(trace, np.float64),
        iterations=steps)


def links_from_assignment_batched(assign: jnp.ndarray, source: jnp.ndarray,
                                  n_uavs: int) -> jnp.ndarray:
    """[B, L] chain-DP assignment (+ [B] source) -> [B, U, U] bool mask of
    the inter-UAV transfers each placement performs: source -> first layer's
    device, then every device change along the chain.  Infeasible scenarios
    (assign -1) use no links.  Pure ``jnp`` — traceable inside the fused
    plan, and the P2 topology for re-optimizing positions to a placement."""
    B, L = assign.shape
    prev = jnp.concatenate([source[:, None], assign[:, :-1]], axis=1)  # [B,L]
    valid = (prev >= 0) & (assign >= 0) & (prev != assign)
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, L))
    a = jnp.clip(prev, 0, n_uavs - 1)
    b = jnp.clip(assign, 0, n_uavs - 1)
    hits = jnp.zeros((B, n_uavs, n_uavs), jnp.int32)
    return hits.at[rows, a, b].add(valid.astype(jnp.int32)) > 0


# ---------------------------------------------------------------------------
# Batched contiguous-block chain DP (P3 fast path)
# ---------------------------------------------------------------------------
#
# Two implementations share the same recurrence (``placement.solve_chain_dp``
# batched):
#
# * ``_chain_dp_solve``           — lax.scan wavefront over layers with dense
#                                   [L, B, S+1] parent pointers and a reverse
#                                   lax.scan backtrack, all in ONE jit call.
#                                   O(1) traced ops per layer, so U, L >= 32
#                                   compiles in seconds.  This is the default.
# * ``_chain_dp_tables_unrolled`` — the PR 1 Python-unrolled tracer (O(L*S)
#                                   stacked ops + a host-side backtrack loop).
#                                   Kept verbatim as the benchmark baseline
#                                   (``benchmarks/bench_placement.py``) and as
#                                   a second parity oracle in the tests.


@partial(jax.jit, static_argnames=("order",))
def _chain_dp_solve_kernelized(compute: jnp.ndarray, memory: jnp.ndarray,
                               act_bits: jnp.ndarray, input_bits: jnp.ndarray,
                               mem_cap: jnp.ndarray, compute_cap: jnp.ndarray,
                               throughput: jnp.ndarray, rate: jnp.ndarray,
                               sources: jnp.ndarray, active: jnp.ndarray,
                               order: Tuple[int, ...]):
    """Kernel-path chain DP: the Pallas tropical wavefront step with a
    native source-slot axis.

    Same recurrence, values and tie-breaks as ``_chain_dp_solve`` — the
    masks and the backward scan are that function's code; only the
    forward-step relaxation is swapped for
    ``kernels.tropical_dp.dp_wavefront_step``.  ``sources`` carries a slot
    axis [B, M] so the multi-source planner shares ONE kernel launch per
    step across every (scenario, slot) pair: the transfer tensor ``tr`` is
    source-independent (its a = 0 row is dead — the kernel folds the
    per-slot source row ``tr0`` in-register instead of the oracle's
    ``tr_src`` overwrite).

    Layout: the kernel's, scenarios last and split into lane rows
    ``(Bt, C)`` (``tropical_dp.lane_split``, padded with inf scenarios
    that are sliced off).  ``tr`` [L, S, S+1, Bt, C] and ``tr0``
    [M, S, Bt, C] are built once per solve; the scan carries the table
    ``dp`` [M, L+1, S+1, Bt, C], hands it to the kernel whole (the kernel
    reads rows 0..L-1) and writes each new row in place, so nothing is
    copied or transposed per step.  The parent pointers are transposed
    once, after the scan, for the backtrack.  Returns
    ``(assign [B, M, L], latency [B, M])``, bitwise-identical to vmapping
    ``_chain_dp_solve`` over the slot axis.
    """
    from repro.kernels.tropical_dp.ops import dp_wavefront_step
    from repro.kernels.tropical_dp.tropical_dp import from_lanes, to_lanes
    L = compute.shape[0]
    S = len(order)
    B, M = sources.shape
    INF = jnp.inf
    order_arr = jnp.asarray(order, jnp.int32)                       # [S]
    pre_c = jnp.concatenate([jnp.zeros(1), jnp.cumsum(compute)])    # [L+1]
    pre_m = jnp.concatenate([jnp.zeros(1), jnp.cumsum(memory)])
    a_ix = jnp.arange(L)
    bits_in = jnp.where(a_ix == 0, input_bits,
                        act_bits[jnp.maximum(a_ix - 1, 0)])         # [L]

    mem_cap_o = mem_cap[order_arr]                                  # [S]
    cmp_cap_o = compute_cap[order_arr]
    thr_o = throughput[order_arr]
    active_t = active[:, order_arr].T                               # [S, B]

    # Slot-invariant transfer tensor: _chain_dp_solve's values, scenarios
    # last, except that the a = 0 row keeps its (dead) placeholder — the
    # kernel overrides that row with tr0, so one tr serves every slot.
    prev_dev = jnp.concatenate([jnp.zeros(1, jnp.int32), order_arr])
    rate_t = jnp.moveaxis(rate, 0, -1)                              # [U,U,B]
    r_prev = rate_t[prev_dev[None, :], order_arr[:, None]]          # [S,S+1,B]
    tr = jnp.where(r_prev[None] > 0,
                   bits_in[:, None, None, None] / r_prev[None],
                   INF)                                             # [L,S,S+1,B]
    s0_lt_s = (jnp.arange(S + 1)[None, :]
               < jnp.arange(1, S + 1)[:, None])                     # [S, S+1]
    tr = jnp.where(s0_lt_s[None, :, :, None] & active_t[None, :, None, :],
                   tr, INF)
    tr = to_lanes(tr)                                               # [..,Bt,C]
    # per-slot source row, masked exactly like the oracle's tr_src at s0 = 0
    r_src = rate_t[sources.T[:, None, :], order_arr[None, :, None],
                   jnp.arange(B)]                                   # [M, S, B]
    tr_src = jnp.where(r_src > 0, input_bits / r_src, INF)
    tr0 = to_lanes(jnp.where(active_t[None], tr_src, INF))          # [..,Bt,C]

    dp0 = jnp.full((M, L + 1, S + 1) + tr0.shape[-2:], INF)
    dp0 = dp0.at[:, 0, 0].set(0.0)

    def forward(dp, b):
        blk_c = pre_c[b] - pre_c[:L]                                # [L] (a)
        blk_m = pre_m[b] - pre_m[:L]
        ok = ((blk_m[:, None] <= mem_cap_o[None, :] + 1e-9) &
              (blk_c[:, None] <= cmp_cap_o[None, :] + 1e-9) &
              (a_ix < b)[:, None])                                  # [L, S]
        ct = blk_c[:, None] / thr_o[None, :]                        # [L, S]
        row, pa, ps = dp_wavefront_step(
            dp, tr, tr0, ct.astype(jnp.float32),
            ok.astype(jnp.float32))                                 # [M,S,Bt,C]
        dp = jax.lax.dynamic_update_slice(dp, row[:, None],
                                          (0, b, 1, 0, 0))
        return dp, (pa, ps)

    dp, (pa, ps) = jax.lax.scan(forward, dp0, jnp.arange(1, L + 1))
    # backtrack on R = B * M flattened (scenario, slot) rows —
    # _chain_dp_solve's reverse scan verbatim
    R = B * M
    final = jnp.moveaxis(from_lanes(dp[:, L], B), -1, 0)            # [B,M,S+1]
    final = final.reshape(R, S + 1)
    s_best = jnp.argmin(final, 1).astype(jnp.int32)
    latency = final.min(1)

    def parent_rows(p):              # [L, M, S, Bt, C] -> [L, R, S+1]
        p = jnp.moveaxis(from_lanes(p, B), -1, 1).reshape(L, R, S)
        return jnp.concatenate([jnp.zeros((L, R, 1), jnp.int32), p], -1)

    pa, ps = parent_rows(pa), parent_rows(ps)
    rows = jnp.arange(R)

    def backward(carry, j):
        b, s = carry
        dev = order_arr[jnp.maximum(s - 1, 0)]                      # [R]
        bi = jnp.clip(b - 1, 0, L - 1)
        a = pa[bi, rows, s]
        s0 = ps[bi, rows, s]
        at_start = j == a
        nb = jnp.where(at_start, a, b)
        ns = jnp.where(at_start, s0, s)
        return (nb, ns), dev

    init = (jnp.full((R,), L, jnp.int32), s_best)
    with jax.named_scope("backtrack"):
        _, devs = jax.lax.scan(backward, init, jnp.arange(L - 1, -1, -1))
    assign = devs[::-1].T.astype(jnp.int32)                         # [R, L]
    assign = jnp.where(jnp.isfinite(latency)[:, None], assign, -1)
    return assign.reshape(B, M, L), latency.reshape(B, M)


@partial(jax.jit, static_argnames=("order", "use_kernel"))
def _chain_dp_solve(compute: jnp.ndarray, memory: jnp.ndarray,
                    act_bits: jnp.ndarray, input_bits: jnp.ndarray,
                    mem_cap: jnp.ndarray, compute_cap: jnp.ndarray,
                    throughput: jnp.ndarray, rate: jnp.ndarray,
                    source: jnp.ndarray, active: jnp.ndarray,
                    order: Tuple[int, ...], use_kernel: bool = False):
    """Scan-based chain DP: solve + backtrack fully on device.

    Forward pass: one ``lax.scan`` step per layer count b carries the dense
    dp table [B, L+1, S+1] (dp[b][s] = best cost of placing layers [0..b)
    with layer b-1 on device order[s-1]) and relaxes ALL (block-start a,
    predecessor-state s0, device-state s) candidates as a single masked
    min-reduction over a [B, L, S+1, S] tensor.  Tie-breaking matches the
    scalar solver's loop order (a outer, s0 inner, strict improvement) via
    first-argmin over the flattened (a, s0) axis.

    Backward pass: a reverse ``lax.scan`` over layers walks the parent
    pointers (pa = block start, ps = predecessor state, gathered per batch
    element) and emits the full [B, L] device-id assignment — no host loop.

    ``use_kernel=True`` routes the forward relaxation through the Pallas
    tropical-DP kernel (``_chain_dp_solve_kernelized`` with a single source
    slot) — bitwise-identical output, tie-breaks included.
    """
    if use_kernel:
        assign, latency = _chain_dp_solve_kernelized(
            compute, memory, act_bits, input_bits, mem_cap, compute_cap,
            throughput, rate, source[:, None], active, order)
        return assign[:, 0], latency[:, 0]
    L = compute.shape[0]
    S = len(order)
    B = rate.shape[0]
    INF = jnp.inf
    order_arr = jnp.asarray(order, jnp.int32)                       # [S]
    pre_c = jnp.concatenate([jnp.zeros(1), jnp.cumsum(compute)])    # [L+1]
    pre_m = jnp.concatenate([jnp.zeros(1), jnp.cumsum(memory)])
    a_ix = jnp.arange(L)
    # bits entering a block that starts at layer a (eq. 12 / eq. 14)
    bits_in = jnp.where(a_ix == 0, input_bits,
                        act_bits[jnp.maximum(a_ix - 1, 0)])         # [L]

    mem_cap_o = mem_cap[order_arr]                                  # [S]
    cmp_cap_o = compute_cap[order_arr]
    thr_o = throughput[order_arr]
    active_o = active[:, order_arr]                                 # [B, S]

    # Transfer into a block on device order[s-1] from predecessor state s0:
    # s0 >= 1 reads rate[order[s0-1], order[s-1]] (inf diagonal -> same-device
    # transfer is 0); the s0 = 0 row is a placeholder — dp[a>0][0] is inf and
    # the a = 0 row is overridden with the source rate below, exactly the
    # scalar solver's `if a == 0` branch.
    prev_dev = jnp.concatenate([jnp.zeros(1, jnp.int32), order_arr])
    r_prev = rate[:, prev_dev[:, None], order_arr[None, :]]         # [B,S+1,S]
    tr = jnp.where(r_prev[:, None, :, :] > 0,
                   bits_in[None, :, None, None] / r_prev[:, None, :, :],
                   INF)                                             # [B,L,S+1,S]
    r_src = rate[jnp.arange(B), source][:, order_arr]               # [B, S]
    tr_src = jnp.where(r_src > 0, input_bits / r_src, INF)
    tr = tr.at[:, 0, :, :].set(tr_src[:, None, :])
    # Bake the step-invariant masks into tr once: the predecessor state must
    # precede the block's device state (s0 < s) and the device must be alive.
    s0_lt_s = (jnp.arange(S + 1)[:, None]
               < jnp.arange(1, S + 1)[None, :])                     # [S+1, S]
    tr = jnp.where(s0_lt_s[None, None] & active_o[:, None, None, :],
                   tr, INF)
    # s0 minor-most: the inner reduction of each step runs over it
    tr = tr.swapaxes(2, 3)                                          # [B,L,S,S+1]

    dp0 = jnp.full((B, L + 1, S + 1), INF).at[:, 0, 0].set(0.0)

    def forward(dp, b):
        blk_c = pre_c[b] - pre_c[:L]                                # [L] (a)
        blk_m = pre_m[b] - pre_m[:L]
        ok = ((blk_m[:, None] <= mem_cap_o[None, :] + 1e-9) &
              (blk_c[:, None] <= cmp_cap_o[None, :] + 1e-9) &
              (a_ix < b)[:, None])                                  # [L, S]
        ct = blk_c[:, None] / thr_o[None, :]                        # [L, S]
        # Two-stage min keeps the bulk pass lean: reduce s0 on the full
        # tensor first, then fold the step-dependent ct/ok terms (which are
        # s0-independent) on the small [B, L, S] remainder.  First-argmin
        # over s0 then over a == first-argmin over lexicographic (a, s0),
        # the scalar solver's tie-break.
        m1 = dp[:, :L, None, :] + tr                                # [B,L,S,S+1]
        s0_best = jnp.argmin(m1, 3).astype(jnp.int32)               # [B, L, S]
        cand = m1.min(3) + ct[None]
        cand = jnp.where(ok[None], cand, INF)                       # [B, L, S]
        a_best = jnp.argmin(cand, 1).astype(jnp.int32)              # [B, S]
        row = jnp.concatenate([jnp.full((B, 1), INF), cand.min(1)], 1)
        dp = dp.at[:, b, :].set(row)
        pad = jnp.zeros((B, 1), jnp.int32)
        pa = jnp.concatenate([pad, a_best], 1)                      # [B, S+1]
        ps = jnp.concatenate(
            [pad, jnp.take_along_axis(s0_best, a_best[:, None, :], 1)[:, 0]],
            1)
        return dp, (pa, ps)

    dp, (pa, ps) = jax.lax.scan(forward, dp0, jnp.arange(1, L + 1))
    final = dp[:, L, :]                                             # [B, S+1]
    s_best = jnp.argmin(final, 1).astype(jnp.int32)
    latency = final.min(1)

    # Reverse scan j = L-1 .. 0; carry (b, s) = the DP state whose block
    # [a, b) contains layer j.  pa/ps are stacked per forward step, so the
    # parents of table row b live at pa[b-1].
    rows = jnp.arange(B)

    def backward(carry, j):
        b, s = carry
        dev = order_arr[jnp.maximum(s - 1, 0)]                      # [B]
        bi = jnp.clip(b - 1, 0, L - 1)
        a = pa[bi, rows, s]
        s0 = ps[bi, rows, s]
        at_start = j == a                  # layer j opens the block: hop to
        nb = jnp.where(at_start, a, b)     # the parent state for layer j-1
        ns = jnp.where(at_start, s0, s)
        return (nb, ns), dev

    init = (jnp.full((B,), L, jnp.int32), s_best)
    # the scope holds the reverse scan alone: opened above ``init`` it
    # renumbers instructions of the compiled rollout
    with jax.named_scope("backtrack"):
        _, devs = jax.lax.scan(backward, init, jnp.arange(L - 1, -1, -1))
    assign = devs[::-1].T.astype(jnp.int32)                         # [B, L]
    assign = jnp.where(jnp.isfinite(latency)[:, None], assign, -1)
    return assign, latency


def _chain_dp_solve_multi(compute: jnp.ndarray, memory: jnp.ndarray,
                          act_bits: jnp.ndarray, input_bits: jnp.ndarray,
                          mem_cap: jnp.ndarray, compute_cap: jnp.ndarray,
                          throughput: jnp.ndarray, rate: jnp.ndarray,
                          sources: jnp.ndarray, active: jnp.ndarray,
                          order: Tuple[int, ...], use_kernel: bool = False):
    """``_chain_dp_solve`` vmapped over a source axis.

    The chain DP depends on the capturing UAV only through the first-block
    transfer row (``tr_src``), so solving a frame's WHOLE request stream —
    one placement per capturing UAV — is a ``vmap`` of the scan DP over
    ``sources`` [B, S] with every other operand broadcast.  Returns
    ``(assign [B, S, L], latency [B, S])``; the per-request caps inside each
    DP stay per-placement — pricing the frame's aggregate load against the
    period budget is ``placement_compute_load`` + the caller's cap check.

    ``use_kernel=True`` skips the vmap entirely: the Pallas kernel carries
    the source-slot axis in its grid, so the whole stream shares ONE kernel
    launch per wavefront step (``_chain_dp_solve_kernelized``) — bitwise-
    identical output.
    """
    if use_kernel:
        return _chain_dp_solve_kernelized(compute, memory, act_bits,
                                          input_bits, mem_cap, compute_cap,
                                          throughput, rate, sources, active,
                                          order)

    def one(src):
        return _chain_dp_solve(compute, memory, act_bits, input_bits,
                               mem_cap, compute_cap, throughput, rate, src,
                               active, order)

    return jax.vmap(one, in_axes=1, out_axes=1)(sources)


def placement_compute_load(assign: jnp.ndarray, weights: jnp.ndarray,
                           compute: jnp.ndarray, n_uavs: int) -> jnp.ndarray:
    """Aggregate per-UAV MACs of a multi-source assignment batch.

    ``assign`` [B, S, L] (device ids, -1 = infeasible), ``weights`` [B, S]
    arrival counts per source, ``compute`` [L] MACs per layer.  Returns
    [B, n_uavs]: the eq. (11b) left-hand side summed over the frame's whole
    request stream — every request of every source charges the MACs of the
    layers its placement hosts.  Infeasible placements contribute nothing
    (they are already priced as inf latency by the DP).
    """
    onehot = assign[..., None] == jnp.arange(n_uavs)        # [B, S, L, U]
    macs_s = (compute[None, None, :, None] * onehot).sum(2)  # [B, S, U]
    return (macs_s * weights[..., None]).sum(1)              # [B, U]


def shared_cap_feasible(load: jnp.ndarray, cap: jnp.ndarray) -> jnp.ndarray:
    """eq. (11b) over the whole request stream: True where no UAV's
    aggregate load exceeds its period budget.  ``load`` [B, U], ``cap`` [U].
    The tolerance matches the scalar solvers' absolute 1e-9 slack plus a
    float32-scale relative term (the aggregate is a float32 sum of
    MAC-scale numbers; an exact-boundary frame must not flap on rounding).
    """
    return (load <= cap[None, :] * (1.0 + 1e-6) + 1e-9).all(-1)


def solve_chain_dp_multisource(compute: np.ndarray, memory: np.ndarray,
                               act_bits: np.ndarray, input_bits: float,
                               mem_cap: np.ndarray, compute_cap: np.ndarray,
                               throughput: np.ndarray, rate: np.ndarray,
                               sources: np.ndarray,
                               active: Optional[np.ndarray] = None,
                               device_order: Optional[Sequence[int]] = None,
                               use_kernel: bool = False
                               ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-facing multi-source mirror of ``solve_chain_dp_batched``.

    ``sources``: [B, S] capturing-UAV index per request slot.  Returns
    ``(assign [B, S, L], latency [B, S])`` — one chain-DP placement per
    (scenario, source), solved in ONE device call via the vmapped scan DP.
    Shared-cap pricing of the aggregate stream is separate
    (``placement_compute_load`` / ``shared_cap_feasible``) so callers can
    weight each source by its arrival count.
    """
    sources = np.asarray(sources, np.int32)
    B, S = sources.shape
    args, order = _as_dp_args(compute, memory, act_bits, input_bits, mem_cap,
                              compute_cap, throughput, rate,
                              sources[:, 0], active, device_order)
    args = args[:-2] + (jnp.asarray(sources, jnp.int32),) + args[-1:]
    assign, latency = _chain_dp_solve_multi(*args, order,
                                            use_kernel=use_kernel)
    return (np.asarray(assign, dtype=np.int64),
            np.asarray(latency, dtype=np.float64))


@partial(jax.jit, static_argnames=("order",))
def _chain_dp_tables_unrolled(compute: jnp.ndarray, memory: jnp.ndarray,
                              act_bits: jnp.ndarray, input_bits: jnp.ndarray,
                              mem_cap: jnp.ndarray, compute_cap: jnp.ndarray,
                              throughput: jnp.ndarray, rate: jnp.ndarray,
                              source: jnp.ndarray, active: jnp.ndarray,
                              order: Tuple[int, ...]):
    """DP tables for ``solve_chain_dp`` over a batch (PR 1 baseline).

    dp[b][s] = best cost of placing layers [0..b) with layer b-1 on device
    order[s-1]; candidates scan block starts a and predecessor states s0
    vectorized over the batch.  Tie-breaking matches the scalar solver's
    loop order (a outer, s0 inner, strict improvement) via first-argmin.
    """
    L = compute.shape[0]
    S = len(order)
    B = rate.shape[0]
    pre_c = jnp.concatenate([jnp.zeros(1), jnp.cumsum(compute)])
    pre_m = jnp.concatenate([jnp.zeros(1), jnp.cumsum(memory)])
    batch_ix = jnp.arange(B)

    dp = [[jnp.full((B,), jnp.inf) for _ in range(S + 1)]
          for _ in range(L + 1)]
    dp[0][0] = jnp.zeros((B,))
    zero_par = jnp.zeros((B,), dtype=jnp.int32)
    par_a = [[zero_par for _ in range(S + 1)] for _ in range(L + 1)]
    par_s0 = [[zero_par for _ in range(S + 1)] for _ in range(L + 1)]

    for b in range(1, L + 1):
        a_ix = jnp.arange(b)
        # bits entering a block that starts at layer a (eq. 12 / eq. 14)
        bits_in = jnp.where(a_ix == 0, input_bits,
                            act_bits[jnp.maximum(a_ix - 1, 0)])      # [b]
        for s in range(1, S + 1):
            dev = order[s - 1]
            blk_m = pre_m[b] - pre_m[:b]                             # [b]
            blk_c = pre_c[b] - pre_c[:b]
            ok = ((blk_m <= mem_cap[dev] + 1e-9) &
                  (blk_c <= compute_cap[dev] + 1e-9))
            ct = blk_c / throughput[dev]
            # transfer into the block from state (a, s0): source when a == 0
            # (dp[0][s0>0] is inf, so only s0 = 0 survives), else from
            # order[s0-1].  rate diag is inf -> same-device transfer is 0.
            prev = jnp.array([order[s0 - 1] if s0 >= 1 else 0
                              for s0 in range(s)], dtype=jnp.int32)  # [s]
            r_prev = rate[:, prev, dev]                              # [B, s]
            tr = jnp.where(r_prev[:, None, :] > 0,
                           bits_in[None, :, None] / r_prev[:, None, :],
                           jnp.inf)                                  # [B, b, s]
            r_src = rate[batch_ix, source, dev]                      # [B]
            tr_src = jnp.where(r_src > 0, input_bits / r_src, jnp.inf)
            tr = tr.at[:, 0, :].set(tr_src[:, None])
            dp_prev = jnp.stack(
                [jnp.stack([dp[a][s0] for s0 in range(s)], -1)
                 for a in range(b)], 1)                              # [B, b, s]
            cand = dp_prev + tr + ct[None, :, None]
            cand = jnp.where(ok[None, :, None], cand, jnp.inf)
            cand = jnp.where(active[:, dev, None, None], cand, jnp.inf)
            flat = cand.reshape(B, -1)                  # index = a * s + s0
            arg = jnp.argmin(flat, -1).astype(jnp.int32)
            dp[b][s] = flat.min(-1)
            par_a[b][s] = arg // s
            par_s0[b][s] = arg % s
    dp_final = jnp.stack([dp[L][s] for s in range(S + 1)], -1)       # [B, S+1]
    s_best = jnp.argmin(dp_final, -1).astype(jnp.int32)
    latency = dp_final.min(-1)
    pa = jnp.stack([jnp.stack(row, -1) for row in par_a], -1)  # [B, S+1, L+1]
    ps = jnp.stack([jnp.stack(row, -1) for row in par_s0], -1)
    return latency, s_best, pa, ps


def _as_dp_args(compute, memory, act_bits, input_bits, mem_cap, compute_cap,
                throughput, rate, source, active, device_order):
    B, U = rate.shape[0], rate.shape[-1]
    order = tuple(device_order) if device_order is not None else \
        tuple(range(U))
    if active is None:
        active = jnp.ones((B, U), dtype=bool)
    return (jnp.asarray(compute, jnp.float32),
            jnp.asarray(memory, jnp.float32),
            jnp.asarray(act_bits, jnp.float32), jnp.float32(input_bits),
            jnp.asarray(mem_cap, jnp.float32),
            jnp.asarray(compute_cap, jnp.float32),
            jnp.asarray(throughput, jnp.float32),
            jnp.asarray(rate, jnp.float32),
            jnp.asarray(source, jnp.int32), jnp.asarray(active)), order


def solve_chain_dp_batched(compute: np.ndarray, memory: np.ndarray,
                           act_bits: np.ndarray, input_bits: float,
                           mem_cap: np.ndarray, compute_cap: np.ndarray,
                           throughput: np.ndarray, rate: np.ndarray,
                           source: np.ndarray,
                           active: Optional[np.ndarray] = None,
                           device_order: Optional[Sequence[int]] = None,
                           use_kernel: bool = False
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched mirror of ``placement.solve_chain_dp`` (scan fast path).

    Args: per-layer ``compute``/``memory``/``act_bits`` [L] shared across the
    batch; device caps/throughput [U]; ``rate`` [B,U,U] (inf diagonal, 0 =
    infeasible link); ``source`` [B] capturing-UAV index; ``active`` [B,U].

    Returns ``(assign, latency)``: assign [B, L] device ids (-1 everywhere on
    infeasible scenarios), latency [B] (inf when infeasible).  Solve AND
    backtrack run in one jit call (``_chain_dp_solve``); compile cost is
    O(1) in L and S, so U = L = 32 instances trace in seconds.
    """
    args, order = _as_dp_args(compute, memory, act_bits, input_bits, mem_cap,
                              compute_cap, throughput, rate, source, active,
                              device_order)
    assign, latency = _chain_dp_solve(*args, order, use_kernel=use_kernel)
    return (np.asarray(assign, dtype=np.int64),
            np.asarray(latency, dtype=np.float64))


def solve_chain_dp_batched_unrolled(compute: np.ndarray, memory: np.ndarray,
                                    act_bits: np.ndarray, input_bits: float,
                                    mem_cap: np.ndarray,
                                    compute_cap: np.ndarray,
                                    throughput: np.ndarray, rate: np.ndarray,
                                    source: np.ndarray,
                                    active: Optional[np.ndarray] = None,
                                    device_order: Optional[Sequence[int]]
                                    = None
                                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The PR 1 implementation: Python-unrolled DP trace + host backtrack.

    Same contract as ``solve_chain_dp_batched``.  Retained as the benchmark
    baseline and parity oracle; its compile time grows O(L*S) with stacked
    ops, so keep it to small instances.
    """
    args, order = _as_dp_args(compute, memory, act_bits, input_bits, mem_cap,
                              compute_cap, throughput, rate, source, active,
                              device_order)
    latency, s_best, pa, ps = _chain_dp_tables_unrolled(*args, order)
    return (_reconstruct_assignments(np.asarray(latency), np.asarray(s_best),
                                     np.asarray(pa), np.asarray(ps),
                                     order, len(compute)),
            np.asarray(latency, dtype=np.float64))


def _reconstruct_assignments(latency: np.ndarray, s_best: np.ndarray,
                             pa: np.ndarray, ps: np.ndarray,
                             order: Tuple[int, ...], L: int) -> np.ndarray:
    """Walk the parent pointers back to per-layer device ids (host side)."""
    B = latency.shape[0]
    assign = np.full((B, L), -1, dtype=np.int64)
    for n in range(B):
        if not np.isfinite(latency[n]):
            continue
        b, s = L, int(s_best[n])
        while b > 0 and s > 0:
            a, s0 = int(pa[n, s, b]), int(ps[n, s, b])
            assign[n, a:b] = order[s - 1]
            b, s = a, s0
    return assign


__all__ = [
    "BatchPowerSolution", "BatchPositionSolution", "pairwise_dist_batched",
    "link_constants", "power_threshold_batched", "solve_power_batched",
    "rate_matrix_batched", "solve_chain_dp_batched",
    "solve_chain_dp_batched_unrolled", "solve_chain_dp_multisource",
    "solve_positions_batched", "links_from_assignment_batched",
    "placement_compute_load", "shared_cap_feasible", "chain_links",
    "position_coeff", "coverage_radius",
]
