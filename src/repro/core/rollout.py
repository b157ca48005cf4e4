"""Device-side fleet rollout: T-frame swarm simulation as ONE ``lax.scan``.

The host-loop ``SwarmSim`` calls the planner once per frame — exactly the
per-request re-solve the paper says a dynamic swarm cannot afford.  This
module turns the whole frame loop into a device program: a ``lax.scan`` over
T frames, each frame applying

  1. **mobility**   — waypoint drift (bounded step toward a per-UAV
                      waypoint) plus Gaussian jitter;
  2. **failures**   — Bernoulli failure and recovery draws, plus externally
                      forced failures (the simulator's injection hook);
  3. **battery**    — a UAV whose charge hit zero is excluded from planning
                      exactly like a failed UAV (the contingency semantics
                      the chain DP already implements via ``active``);
  4. **requests**   — per-UAV arrival counts (Section II-A: EVERY UAV
                      generates RQ_i requests, sum = RQ); arrivals drawn on
                      a dead UAV are captured by the first survivor;
  5. **planning**   — the fused P2 -> P1 -> eq. (5) -> chain-DP -> tightened
                      powers solve, IN-TRACE, with one chain-DP placement
                      PER CAPTURING UAV (the DP vmapped over the source
                      axis) and the frame's aggregate per-UAV MACs priced
                      exactly against the eq. (11b) period budget
                      (``make_plan_fn(multi_source=True)`` below —
                      ``ScenarioEngine`` jits the same pure functions);
  6. **accounting** — arrival-weighted frame latency, transmit energy
                      (power x airtime summed over the source axis),
                      compute energy (J/MAC x the aggregate MAC load), and
                      the battery state carried into the next frame.

Everything is batched over B independent fleet trajectories, so a whole
(B, T) rollout is one jit call with zero host crossings between frames.
Random draws (jitter, failure/recovery uniforms, arrival counts) are made on
the host ONCE per rollout and shipped as scan inputs — which is what makes
the legacy host loop replayable as a per-frame parity oracle
(``tests/test_rollout.py``).

Shapes: B = trajectories, T = frames, U = UAVs (also S, the source axis:
every UAV is a potential capturing source), L = layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import (_chain_dp_solve, _chain_dp_solve_multi,
                              _positions_pgd, chain_links, coverage_radius,
                              links_from_assignment_batched,
                              pairwise_dist_batched, placement_compute_load,
                              position_coeff, power_threshold_batched,
                              rate_matrix_batched, shared_cap_feasible,
                              solve_power_batched)
from repro.core.channel import RadioParams


@dataclass(frozen=True)
class PositionSpec:
    """Static P2 hyperparameters for the fused planner.

    Part of the compiled-plan cache key: engines sharing (problem signature,
    spec) share ONE compiled plan; changing any field compiles a new one.
    """

    steps: int = 300           # projected-gradient iterations
    lr: float = 0.5            # normalized-gradient step size (m)
    radius: float = 20.0       # UAV coverage radius R (eq. 8c/8d)
    repair_iters: int = 50     # device-side push-apart iterations

    def key(self) -> tuple:
        return ("p2", self.steps, self.lr, self.radius, self.repair_iters)


@dataclass(frozen=True)
class RolloutSpec:
    """Static dynamics constants of a fleet rollout.

    Every field is baked into the traced scan body, so the whole spec is
    part of the compiled-rollout cache key (``key()``).  ``frames`` is only
    the default horizon — the scan length comes from the input arrays, so a
    different T re-uses the same compiled callable (one retrace per new T).

    * Mobility: each UAV drifts up to ``drift_m_per_frame`` toward its
      waypoint, plus N(0, jitter_sigma_m) per-axis jitter.
    * Requests: ``requests_per_frame`` is the frame's TOTAL arrival count RQ
      (Section II-A: sum over UAVs of RQ_i); which UAV captures each request
      is drawn per frame — uniform over the swarm, or biased by
      ``arrival_weights`` (one relative capture propensity per UAV, e.g. a
      camera-carrying scout generating most of the traffic).
    * Failures: i.i.d. Bernoulli per frame — alive UAVs fail with
      ``failure_prob``, failed ones rejoin with ``recovery_prob``.
    * Battery: every UAV starts with ``battery_j`` joules; serving drains
      ``compute_j_per_mac`` per multiply plus transmit power x airtime, and
      hovering costs ``hover_watts`` over the ``frame_s`` frame.  A drained
      UAV is excluded from planning from the NEXT frame on (detection at
      the frame boundary, like a lapsed heartbeat) and never recovers.
    """

    frames: int = 32
    frame_s: float = 60.0              # optimization period (Section IV)
    requests_per_frame: int = 1        # RQ: total arrivals per frame
    arrival_weights: Optional[Tuple[float, ...]] = None  # per-UAV RQ_i bias
    drift_m_per_frame: float = 0.0     # waypoint pull per frame (m)
    jitter_sigma_m: float = 0.0        # mobility jitter std-dev (m)
    waypoint_range_m: float = 0.0      # waypoints drawn in +-range around base
    failure_prob: float = 0.0
    recovery_prob: float = 0.0
    battery_j: float = math.inf        # initial charge (J); inf = no battery
    hover_watts: float = 0.0
    compute_j_per_mac: float = 1e-9    # ~1 nJ/MAC, Raspberry-Pi class

    def __post_init__(self):
        if self.arrival_weights is not None:
            object.__setattr__(self, "arrival_weights",
                               tuple(float(w) for w in self.arrival_weights))

    def key(self) -> tuple:
        # arrival_weights is deliberately NOT part of the key: the weights
        # only bias the HOST-side multinomial draws (FleetRollout.run), so
        # two specs differing only there produce bit-identical traced
        # programs and must share one compiled rollout
        return ("rollout-spec", self.frame_s, self.requests_per_frame,
                self.drift_m_per_frame, self.jitter_sigma_m,
                self.waypoint_range_m, self.failure_prob, self.recovery_prob,
                self.battery_j, self.hover_watts, self.compute_j_per_mac)


# ---------------------------------------------------------------------------
# The fused planning tick as a reusable pure function
# ---------------------------------------------------------------------------


def make_plan_fn(*, params: RadioParams, compute, memory, act_bits,
                 input_bits, mem_cap, compute_cap, throughput,
                 order: Tuple[int, ...],
                 p2: Optional[PositionSpec] = None,
                 multi_source: bool = False,
                 max_sources: Optional[int] = None,
                 use_kernels: bool = False):
    """The WHOLE planning tick as one pure, trace-safe function:

        (P2 positions from the input initializations, when ``p2`` is set)
        -> pairwise distances -> P1 powers -> eq. (5) rates
        -> chain-DP placement (solve + device-side backtrack)
        -> used-links mask from the assignment -> tightened P1 powers.

    Nothing crosses the host boundary between stages: the used-links
    tightening (the scalar planner's ``min_power_for_placement``) consumes
    the assignment straight from the DP backtrack via
    ``links_from_assignment_batched``, and reuses the eq. (7) thresholds
    computed for the first P1 pass.

    With ``multi_source=False`` the returned function is

        solve(positions, source [B], active, gain_scale, p2_links)
        -> (positions, power, rate, assign [B, L], latency [B])

    — one capturing UAV per scenario.  With ``multi_source=True`` it serves
    a frame's WHOLE request stream (Section II-A: every UAV generates RQ_i
    requests):

        solve(positions, n_req [B, U], active, gain_scale, p2_links)
        -> (positions, power, rate, assign [B, U, L], lat_src [B, U],
            latency [B], load [B, U], cap_ok [B])

    The chain DP is vmapped over the source axis (it differs only in the
    first-block transfer row), each source weighted by its arrival count:
    frame ``latency`` is the arrival-weighted per-request mix, the powers
    are tightened to the UNION of every served source's links, and ``load``
    is the frame's aggregate per-UAV MACs — priced EXACTLY against the
    eq. (11b) period budget (``cap_ok``; an over-budget frame reports inf
    latency).  This replaces the 1/RQ fair-share cap split the benchmarks
    used to approximate the legacy planner's shared residual caps with.

    Relation to the legacy residual-cap loop (``place_requests``): the
    stream is priced at each source's LATENCY-OPTIMAL placement.  That
    agrees with the legacy loop wherever caps do not bind (identical
    placements, identical latencies) and wherever the stream is jointly
    unroutable (both infeasible); in between — a contended stream the
    legacy loop rescues by re-routing LATER requests onto worse devices
    as capacity fills — this pass is deliberately CONSERVATIVE: it flags
    the frame infeasible rather than serve a degraded placement the DP
    never solved.  The parity tests pin both agreeing regimes.

    ``max_sources`` bounds the vmapped source axis: with S = max_sources
    < U the tick gathers the S LARGEST arrival counts in-trace (a frame
    with RQ total arrivals has at most RQ distinct sources, so the
    rollout compiles S = min(U, RQ) DP slots instead of U) and scatters
    the results back onto the U axis — unrequested sources then report
    assign -1 / latency inf.  With the default S = U every source is
    solved whether or not it drew arrivals (the engine's ``plan_batch_
    multi`` contract: per-source fields cover the whole swarm).

    ``ScenarioEngine`` jits the returned functions directly (one call per
    ``plan_batch`` / ``plan_batch_multi``); ``make_rollout_fn`` embeds the
    SAME multi-source function inside the frame scan, so a rollout frame
    and a batched plan are bit-identical.

    ``use_kernels=True`` swaps the tick's two hot loops for the Pallas
    kernels — ``kernels.link_geometry`` fuses the four [B, U, U] geometry
    passes into one, and ``kernels.tropical_dp`` runs the chain-DP
    wavefront (all source slots in one launch per step).  The emitted
    plans are bitwise-identical to the jnp path; the flag only selects
    the program, so it must be part of any compiled-plan cache key.
    """
    compute = jnp.asarray(compute, jnp.float32)
    memory = jnp.asarray(memory, jnp.float32)
    act_bits = jnp.asarray(act_bits, jnp.float32)
    input_bits = jnp.float32(input_bits)
    mem_cap = jnp.asarray(mem_cap, jnp.float32)
    compute_cap = jnp.asarray(compute_cap, jnp.float32)
    throughput = jnp.asarray(throughput, jnp.float32)
    U = int(mem_cap.shape[0])

    def geometry(positions, active, gain_scale, p2_links):
        if p2 is not None:
            positions, _, _, _ = _positions_pgd(
                positions, p2_links,
                jnp.float32(position_coeff(params)), jnp.float32(p2.lr),
                jnp.float32(2.0 * p2.radius),
                jnp.float32(coverage_radius(U, p2.radius)),
                positions.mean(axis=1), p2.steps, p2.repair_iters)
        if use_kernels:
            from repro.kernels.link_geometry.ops import fused_link_geometry
            dist, th, rate = fused_link_geometry(
                positions, params, active=active, gain_scale=gain_scale)
            return positions, dist, th, rate
        dist = pairwise_dist_batched(positions)
        th = power_threshold_batched(dist, params, gain_scale=gain_scale)
        pw = solve_power_batched(dist, params, active=active,
                                 gain_scale=gain_scale, threshold_matrix=th)
        rate = rate_matrix_batched(dist, pw.power, params, pw.link_feasible,
                                   gain_scale=gain_scale)
        return positions, dist, th, rate

    def solve(positions, source, active, gain_scale, p2_links):
        positions, dist, th, rate = geometry(positions, active, gain_scale,
                                             p2_links)
        assign, latency = _chain_dp_solve(
            compute, memory, act_bits, input_bits, mem_cap, compute_cap,
            throughput, rate, source, active, order,
            use_kernel=use_kernels)
        used = links_from_assignment_batched(assign, source, U)
        power = solve_power_batched(dist, params, links=used, active=active,
                                    threshold_matrix=th).power
        return positions, power, rate, assign, latency

    S = U if max_sources is None else max(1, min(U, int(max_sources)))
    L = int(np.asarray(compute).shape[0])

    def solve_multi(positions, n_req, active, gain_scale, p2_links):
        positions, dist, th, rate = geometry(positions, active, gain_scale,
                                             p2_links)
        B = positions.shape[0]
        n_req = jnp.asarray(n_req, jnp.float32)
        if S < U:
            # a frame with RQ total arrivals has at most RQ distinct
            # sources: gather the S largest counts, solve only those slots
            slot_src = jnp.argsort(-n_req, axis=-1)[:, :S] \
                .astype(jnp.int32)                          # [B, S]
        else:
            slot_src = jnp.broadcast_to(
                jnp.arange(U, dtype=jnp.int32), (B, U))
        slot_cnt = jnp.take_along_axis(n_req, slot_src, -1)  # [B, S]
        assign_s, lat_s = _chain_dp_solve_multi(
            compute, memory, act_bits, input_bits, mem_cap, compute_cap,
            throughput, rate, slot_src, active, order,
            use_kernel=use_kernels)                         # [B,S,L],[B,S]
        requested = slot_cnt > 0
        served = requested & jnp.isfinite(lat_s)
        # arrival-weighted per-request latency; a requested source the DP
        # could not place makes the whole frame infeasible (inf), exactly
        # like an INFEASIBLE placement in the legacy request loop
        weighted = jnp.where(requested, slot_cnt * lat_s, 0.0).sum(-1)
        latency = weighted / jnp.maximum(n_req.sum(-1), 1.0)
        # exact shared-cap pricing: the aggregate per-UAV MACs of the whole
        # stream against the eq. (11b) period budget
        load = placement_compute_load(
            assign_s, jnp.where(requested, slot_cnt, 0.0), compute, U)
        cap_ok = shared_cap_feasible(load, compute_cap)
        latency = jnp.where(cap_ok, latency, jnp.inf)
        # tighten P1 to the union of the links every SERVED source uses
        used = jax.vmap(
            lambda a, s: links_from_assignment_batched(a, s, U),
            in_axes=1, out_axes=1)(assign_s, slot_src)      # [B,S,U,U]
        used = (used & served[:, :, None, None]).any(1)
        power = solve_power_batched(dist, params, links=used, active=active,
                                    threshold_matrix=th).power
        if S < U:
            # scatter the solved slots back onto the U source axis;
            # unrequested sources report assign -1 / latency inf
            rows = jnp.arange(B)[:, None]
            lat_src = jnp.full((B, U), jnp.inf).at[rows, slot_src].set(
                jnp.where(requested, lat_s, jnp.inf))
            assign = jnp.full((B, U, L), -1, jnp.int32) \
                .at[rows, slot_src].set(
                    jnp.where(requested[..., None], assign_s, -1))
        else:
            lat_src, assign = lat_s, assign_s
        return positions, power, rate, assign, lat_src, latency, load, cap_ok

    return solve_multi if multi_source else solve


def _frame_energy(assign, source, power, rate, compute, act_bits,
                  input_bits):
    """Per-UAV energy of serving one frame's requests.

    * compute: MACs of the layers each UAV hosts (eq. 1-2 costs via the
      assignment one-hot), per request;
    * transmit: solved power x time-on-air, where airtime is the bits each
      used link carries (eq. 12/14: input bits into the first block,
      activation bits on every device change) over its eq. (5) rate.

    Returns (macs [B, U], tx_time [B, U]) for ONE request — callers scale
    by the frame's arrival count.  Infeasible frames (assign == -1)
    contribute zero MACs and zero airtime.
    """
    B, L = assign.shape
    U = power.shape[-1]
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, L))
    onehot = assign[..., None] == jnp.arange(U)           # [B, L, U]
    macs = (compute[None, :, None] * onehot).sum(1)       # [B, U]
    prev = jnp.concatenate([source[:, None], assign[:, :-1]], axis=1)
    bits_in = jnp.concatenate([input_bits[None], act_bits[:-1]])     # [L]
    hop = (prev >= 0) & (assign >= 0) & (prev != assign)
    a = jnp.clip(prev, 0, U - 1)
    b = jnp.clip(assign, 0, U - 1)
    r = rate[rows, a, b]                                  # [B, L]
    t_link = jnp.where(hop & (r > 0), bits_in[None, :] / r, 0.0)
    tx_time = jnp.zeros((B, U)).at[rows, a].add(t_link)   # transmitter pays
    return macs, tx_time


def _frame_tx_time_multi(assign, n_req, rate, act_bits, input_bits):
    """Arrival-weighted per-UAV time-on-air of a frame's WHOLE request
    stream: ``_frame_energy``'s transmit half vmapped over the source axis
    (every UAV is its own source) and summed with each source's arrival
    count.  ``assign`` [B, S=U, L], ``n_req`` [B, U] -> tx_time [B, U].
    The aggregate MAC half lives in the plan itself
    (``placement_compute_load``) because it also prices the shared cap.
    """
    B, S = n_req.shape
    sources = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    zero_pw = jnp.zeros((B, S))          # _frame_energy only reads its shape

    def one(a, s):
        _, tx = _frame_energy(a, s, zero_pw, rate, jnp.zeros_like(act_bits),
                              act_bits, input_bits)
        return tx

    tx_s = jax.vmap(one, in_axes=1, out_axes=1)(assign, sources)  # [B,S,U]
    return (tx_s * n_req[:, :, None]).sum(1)


# ---------------------------------------------------------------------------
# The rollout scan
# ---------------------------------------------------------------------------


def make_rollout_fn(on_trace, *, params: RadioParams, compute, memory,
                    act_bits, input_bits, mem_cap, compute_cap, throughput,
                    order: Tuple[int, ...], spec: RolloutSpec,
                    p2: Optional[PositionSpec] = None,
                    mesh=None, with_gain: bool = False,
                    with_drain: bool = False,
                    use_kernels: bool = False):
    """Compile the (B, T) fleet rollout: ONE jit call, zero host crossings.

    With ``mesh`` (a 1-D ``jax.sharding.Mesh``, e.g. from
    ``repro.parallel.sharding.fleet_mesh``) the trajectory axis B is SPMD-
    sharded over the mesh via ``shard_map``: every device runs the
    IDENTICAL frame scan on its B/n slice of the host-drawn random streams
    and arrival tensors (trajectories are embarrassingly independent — no
    collective ever runs inside the scan), so fleet Monte Carlo scales to
    the B the device count affords instead of what one device holds.  B
    must be divisible by the mesh size; ``FleetRollout.run`` pads ragged
    batches and threads the validity mask into every trace statistic.

    The returned callable takes

        pos0      [B, U, 2]  initial positions
        charge0   [B, U]     initial battery (J; inf = unlimited)
        alive0    [B, U]     initial failure state
        waypoint  [B, U, 2]  per-UAV drift targets
        jitter    [T, B, U, 2]  pre-drawn mobility noise
        fail_u    [T, B, U]  failure uniforms  (< failure_prob kills)
        recov_u   [T, B, U]  recovery uniforms (< recovery_prob revives)
        forced    [T, B, U]  bool, True = externally forced dead this frame
        arrivals  [T, B, U]  drawn request arrivals per capturing UAV

    plus, when the chaos flags are set, trailing per-frame fault streams
    (the ``runtime.chaos.FaultSchedule`` compilation targets; each flag is
    part of the compiled-rollout cache key, so the default no-chaos scan
    stays byte-identical to the program every existing caller compiled):

        gain      [T, B, U, U]  with_gain:  multiplicative link-gain
                                factor per frame (1.0 = nominal; a faded
                                link raises eq. (7) power thresholds and
                                lowers eq. (5) rates in-trace)
        drain     [T, B, U]     with_drain: extra battery drain (J) applied
                                at the end of each frame — scripted battery
                                drops; hits idle and active UAVs alike

    and returns per-frame stacks (leading T): positions, active, charge,
    arrival-weighted latency, total tightened power (masked to feasible
    frames), feasibility, the exact shared-cap verdict, the per-source
    assignment batch [B, U, L], per-source latencies [B, U], the served
    arrival counts (dead sources' arrivals remapped to the first survivor),
    and per-UAV transmit/compute energy.

    Frame order matters and is fixed: mobility -> failure/recovery ->
    battery gate -> plan -> energy drain.  The charge consumed serving a
    frame only gates the NEXT frame (a UAV that dies mid-frame still
    finishes its subtask), which gives the battery carry its two tested
    invariants: monotone non-increasing, and dead => excluded from the
    following frames' placements.
    """
    # a frame's RQ arrivals touch at most RQ distinct sources, so the scan
    # compiles min(U, RQ) DP slots — cost scales with the actual request
    # stream, not the swarm size (FleetRollout.run validates arrivals
    # against this bound host-side)
    solve = make_plan_fn(params=params, compute=compute, memory=memory,
                         act_bits=act_bits, input_bits=input_bits,
                         mem_cap=mem_cap, compute_cap=compute_cap,
                         throughput=throughput, order=order, p2=p2,
                         multi_source=True,
                         max_sources=spec.requests_per_frame,
                         use_kernels=use_kernels)
    act_j = jnp.asarray(act_bits, jnp.float32)
    input_j = jnp.float32(input_bits)
    U = int(np.asarray(mem_cap).shape[0])
    links_const = jnp.asarray(chain_links(U, order)) if p2 is not None \
        else None
    drift = jnp.float32(spec.drift_m_per_frame)
    hover_e = jnp.float32(spec.hover_watts * spec.frame_s)
    kappa = jnp.float32(spec.compute_j_per_mac)
    p_fail = jnp.float32(spec.failure_prob)
    p_recover = jnp.float32(spec.recovery_prob)

    def rollout(pos0, charge0, alive0, waypoint, jitter, fail_u, recov_u,
                forced, arrivals, *chaos):
        on_trace()
        B = pos0.shape[0]
        rows = jnp.arange(B)

        def frame(carry, xs):
            pos, alive, charge = carry
            jit_t, fail_t, rec_t, dead_t, arr_t = xs[:5]
            extra = xs[5:]
            gain_t = extra[0] if with_gain else None
            drain_t = extra[-1] if with_drain else None
            # 1. mobility: bounded step toward the waypoint, plus jitter
            to_wp = waypoint - pos
            nrm = jnp.linalg.norm(to_wp, axis=-1, keepdims=True)
            pos = pos + to_wp * jnp.minimum(1.0, drift / jnp.maximum(
                nrm, 1e-9)) + jit_t
            # 2. Bernoulli failure / recovery, then forced injections.
            # Recovery applies to UAVs that entered the frame dead — a UAV
            # failing THIS frame stays down at least one frame, so the
            # observed per-frame failure rate is the documented
            # failure_prob, not failure_prob * (1 - recovery_prob).
            revived = ~alive & (rec_t < p_recover)
            alive = (alive & (fail_t >= p_fail)) | revived
            alive = alive & ~dead_t
            # 3. battery gate: drained at the frame boundary => excluded
            powered = charge > 0.0
            active = alive & powered
            # 4. arrivals drawn on a dead UAV are captured by the FIRST
            # survivor (the legacy delegation maps a dead source to the
            # lowest-indexed one).  An all-dead fleet keeps the orphaned
            # counts on (inactive) UAV 0, so the frame prices as infeasible
            # instead of silently serving nobody.
            first_active = jnp.argmax(active, axis=-1).astype(jnp.int32)
            n_live = jnp.where(active, arr_t, 0.0)
            orphaned = (arr_t - n_live).sum(-1)
            n_eff = n_live.at[rows, first_active].add(orphaned)
            # 5. the fused multi-source planning tick, in-trace
            p2_links = None if links_const is None else \
                jnp.broadcast_to(links_const, (B, U, U))
            (pos, power, rate, assign, lat_src, latency, load,
             cap_ok) = solve(pos, n_eff, active, gain_t, p2_links)
            # 6. energy accounting + battery carry.  ``load`` is already
            # the arrival-weighted aggregate MACs; an infeasible frame is
            # not served, so it spends nothing beyond hover.
            feasible = jnp.isfinite(latency)
            tx_time = _frame_tx_time_multi(assign, n_eff, rate, act_j,
                                           input_j)
            e_cmp = jnp.where(feasible[:, None], kappa * load, 0.0)
            e_tx = jnp.where(feasible[:, None], power * tx_time, 0.0)
            drain = jnp.where(active, e_cmp + e_tx + hover_e, 0.0)
            if with_drain:
                # scripted battery drops (chaos): charged whether or not
                # the UAV served this frame — a physical energy loss
                drain = drain + drain_t
            charge = jnp.maximum(charge - drain, 0.0)
            out = (pos, active, charge, latency,
                   jnp.where(feasible, power.sum(-1), 0.0), feasible,
                   cap_ok, assign, lat_src, n_eff, e_tx, e_cmp)
            return (pos, alive, charge), out

        xs = (jitter, fail_u, recov_u, forced, arrivals) + chaos
        _, outs = jax.lax.scan(frame, (pos0, alive0, charge0), xs)
        return outs

    if mesh is None:
        return jax.jit(rollout)

    # SPMD over the trajectory axis: the [B, ...] initial-state arrays
    # shard on dim 0, the [T, B, ...] per-frame streams on dim 1, and every
    # output stack is [T, B, ...] again.  on_trace() fires once per XLA
    # trace exactly like the unsharded path, so retrace accounting is
    # mesh-transparent.
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    b_spec, tb_spec = P(axis), P(None, axis)
    n_chaos = int(with_gain) + int(with_drain)   # trailing [T, B, ...] streams
    # replication checking off: the per-shard scan's outputs are not
    # inferable as replicated or varying
    sharded = jax.shard_map(
        rollout, mesh=mesh,
        in_specs=(b_spec, b_spec, b_spec, b_spec,
                  tb_spec, tb_spec, tb_spec, tb_spec, tb_spec)
        + (tb_spec,) * n_chaos,
        out_specs=tb_spec, check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Shared statistics helpers
# ---------------------------------------------------------------------------


def percentile_with_inf(latency: np.ndarray, q: float) -> float:
    """Latency percentile across an ensemble, infeasible entries included as
    inf — an SLO statistic must see outages: if the q-th order statistic
    falls in the infeasible tail the result is inf, not a silently
    optimistic number over the survivors.  (np.percentile alone would
    interpolate with inf and return NaN.)"""
    lat = np.sort(np.asarray(latency, dtype=np.float64).ravel())
    if not lat.size:
        return float("inf")
    pos = q / 100.0 * (lat.size - 1)
    lo = int(np.floor(pos))
    frac = pos - lo
    if frac == 0.0:                      # lands exactly on an element
        return float(lat[lo])
    if not np.isfinite(lat[lo + 1]):     # interpolating into the outage tail
        return float("inf")
    return float(lat[lo] + frac * (lat[lo + 1] - lat[lo]))


__all__ = [
    "PositionSpec", "RolloutSpec", "make_plan_fn", "make_rollout_fn",
    "percentile_with_inf",
]
