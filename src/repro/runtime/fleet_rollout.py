"""FleetRollout — the host-facing runtime layer over the device-side
rollout scan (``repro.core.rollout``).

A ``FleetRollout`` is a ``ScenarioEngine`` (same constants, same compiled
fused plan, same ``PlanFnCache`` keys) that ALSO owns a compiled (B, T)
rollout: mobility, failure/recovery, battery drain, the frame's WHOLE
multi-source request stream (Section II-A: every UAV generates RQ_i
requests) and the fused P1->P2->P3 solve for every frame of every
trajectory, in ONE jit call with zero host crossings between frames.
``SwarmSim`` is its B = 1 wrapper; ``benchmarks/fig2_*..fig5_*`` call it
once per figure point; the ``PeriodicReplanner`` uses it as a lookahead
that prices a plan over the modelled dynamics, not just at the nominal
state.

All randomness is drawn host-side per ``run()`` (one ``numpy`` generator,
shipped to the scan as inputs), which keeps the legacy host loop replayable
as a per-frame parity oracle and makes a rollout reproducible from its seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.rollout import (RolloutSpec, make_rollout_fn,
                                percentile_with_inf)
from repro.parallel.sharding import (fleet_mesh, mesh_signature,
                                     pad_to_multiple)
from repro.runtime.scenario_engine import ScenarioEngine


@dataclass
class RolloutTrace:
    """The full (B, T) rollout record, trajectory-major.

    ``latency`` is the ARRIVAL-WEIGHTED per-request latency of each frame's
    whole request stream (inf = infeasible frame: a requested source the DP
    could not place, or an aggregate load over the eq. 11b period budget —
    see ``cap_feasible``).  ``source_latency`` holds every capturing UAV's
    own per-request latency and ``assign`` its placement, whether or not it
    drew arrivals that frame.  ``total_power`` is the tightened used-links
    transmit power (W), masked to 0 on infeasible frames (an unserved frame
    transmits nothing); ``charge`` the battery state AFTER each frame's
    drain; ``active`` the UAVs the frame actually planned over (alive AND
    powered); ``n_requests`` the served arrival counts (arrivals drawn on a
    dead UAV are captured by the first survivor).

    ``valid`` marks the trajectories the CALLER asked for.  A mesh-sharded
    run pads B up to a multiple of the device count (``shard_map`` needs
    the sharded axis divisible), and the padded rows — pure shard filler —
    stay in the arrays so the (B, T) layout matches what came off the
    devices; every aggregate statistic below masks them out, which is what
    makes the statistics shard-count invariant.  Unsharded runs have all
    rows valid."""

    latency: np.ndarray         # [B, T] arrival-weighted (inf = infeasible)
    total_power: np.ndarray     # [B, T] 0 on infeasible frames
    feasible: np.ndarray        # [B, T] bool
    cap_feasible: np.ndarray    # [B, T] bool — eq. 11b aggregate-load check
    source_latency: np.ndarray  # [B, T, U] per-request latency per source
    assign: np.ndarray          # [B, T, U, L] device ids (-1 = infeasible)
    positions: np.ndarray       # [B, T, U, 2] planned (post-P2) positions
    active: np.ndarray          # [B, T, U] bool
    charge: np.ndarray          # [B, T, U] J
    n_requests: np.ndarray      # [B, T, U] served arrivals per source
    energy_tx: np.ndarray       # [B, T, U] J
    energy_cmp: np.ndarray      # [B, T, U] J
    valid: Optional[np.ndarray] = None   # [B] bool; None = every row real

    def _valid(self) -> np.ndarray:
        """[B] mask of caller-requested trajectories (padding excluded)."""
        if self.valid is None:
            return np.ones(self.latency.shape[0], dtype=bool)
        return self.valid

    @property
    def n_trajectories(self) -> int:
        """Trajectories the caller asked for (mesh padding rows excluded —
        ``latency.shape[0]`` may be larger after a sharded ragged run)."""
        return int(self._valid().sum())

    @property
    def n_frames(self) -> int:
        return self.latency.shape[1]

    @property
    def feasibility_rate(self) -> float:
        """Fraction of VALID (trajectory, frame) points with a feasible
        plan."""
        feas = self.feasible[self._valid()]
        return float(feas.mean()) if feas.size else 0.0

    @property
    def mean_latency(self) -> float:
        """Mean arrival-weighted latency over FEASIBLE frames of valid
        trajectories (inf when none) — always read next to
        ``feasibility_rate``: the mean alone can hide an arbitrarily
        broken fleet."""
        m = self._valid()
        vals = self.latency[m][self.feasible[m]]
        return float(vals.mean()) if vals.size else float("inf")

    @property
    def mean_power(self) -> float:
        """Mean tightened transmit power over FEASIBLE frames of valid
        trajectories only (mirroring ``mean_latency``): an infeasible
        frame serves nothing, so its powers must not dilute or inflate the
        statistic."""
        m = self._valid()
        vals = self.total_power[m][self.feasible[m]]
        return float(vals.mean()) if vals.size else 0.0

    def latency_percentile(self, q: float) -> float:
        """Ensemble percentile over ALL valid (trajectory, frame) points,
        infeasible frames included as inf (outages must show up in SLOs)."""
        return percentile_with_inf(self.latency[self._valid()], q)

    def frame_stats(self, trajectory: int = 0) -> List["FrameStats"]:
        """One trajectory as the legacy ``SwarmSim`` per-frame records.

        ``n_requests`` is the frame's total served arrival count straight
        from the trace (per-source counts live in ``self.n_requests``);
        ``replanned`` marks frames where the planned-over UAV set shrank
        (failure or battery death) — the moment the contingency semantics
        absorbed a loss."""
        from repro.core.swarm import FrameStats
        b = trajectory
        if not self._valid()[b]:
            raise IndexError(
                f"trajectory {b} is mesh-padding filler, not a requested "
                f"trajectory (n_trajectories = {self.n_trajectories})")
        out: List[FrameStats] = []
        prev_active = None
        for t in range(self.n_frames):
            act = self.active[b, t]
            shrank = prev_active is not None and bool(
                (prev_active & ~act).any())
            prev_active = act
            out.append(FrameStats(
                t=t, latency=float(self.latency[b, t]),
                power=float(self.total_power[b, t]),
                breakdown={"e_tx": float(self.energy_tx[b, t].sum()),
                           "e_compute": float(self.energy_cmp[b, t].sum())},
                n_requests=int(self.n_requests[b, t].sum()),
                feasible=bool(self.feasible[b, t]), replanned=shrank))
        return out


class FleetRollout(ScenarioEngine):
    """Batched multi-frame swarm simulation, fully on device.

    Extends ``ScenarioEngine`` with a compiled rollout callable resolved
    through the same ``PlanFnCache``: the rollout's cache key is the fused
    plan's static signature plus the ``RolloutSpec`` dynamics constants
    PLUS the mesh signature (``repro.parallel.sharding.mesh_signature``) —
    a mesh-sharded scan and the single-device scan are different XLA
    executables and must never collide on one entry — so rebuilding a
    ``FleetRollout`` (a new ``SwarmSim``, a benchmark rerun, a replanner
    lookahead) never re-traces.  The scan length T comes from the input
    arrays — a different horizon re-executes the same callable (one
    retrace per new (B, T) shape, counted by ``trace_count``).

    ``mesh=`` / ``devices=`` (constructor default, overridable per
    ``run``) shard the trajectory axis over a 1-D device mesh
    (``fleet_mesh``): ragged B is padded up to the mesh size and masked
    back out via ``RolloutTrace.valid``.
    """

    def __init__(self, channel, devices, model, spec: RolloutSpec,
                 device_order=None, act_scale: float = 1.0,
                 plan_cache=None, position_spec=None, seed: int = 0,
                 mesh=None, mesh_devices: Union[None, int, Sequence] = None,
                 use_kernels: bool = False):
        super().__init__(channel, devices, model, device_order=device_order,
                         act_scale=act_scale, plan_cache=plan_cache,
                         position_spec=position_spec,
                         use_kernels=use_kernels)
        self.spec = spec
        self._rng = np.random.default_rng(seed)
        self._default_mesh = self._resolve_mesh(mesh, mesh_devices)
        self._rollout = self._rollout_fn(self._default_mesh)

    @staticmethod
    def _resolve_mesh(mesh, devices):
        """One mesh from the (mesh=, devices=) pair; None = single device.

        ``devices`` is an int (first n local devices) or a device
        sequence; a 1-device request collapses to the plain single-device
        jit (sharding over one device adds nothing but a distinct
        executable)."""
        if mesh is not None and devices is not None:
            raise ValueError("pass either mesh or devices, not both")
        if mesh is None and devices is None:
            return None
        if devices == 1:
            return None
        return fleet_mesh(mesh if mesh is not None else devices)

    def _rollout_fn(self, mesh, with_gain: bool = False,
                    with_drain: bool = False):
        """The compiled rollout for ``mesh``, through the shared cache.

        The key carries ``mesh_signature(mesh)``: a single-device rollout
        (signature None) and every distinct mesh each get their own entry
        and their own (exactly one) trace.  The chaos flags (per-frame
        ``gain_scale`` fades / ``extra_drain`` battery drops threaded
        through the scan) are part of the key too — a chaos run compiles
        its own program and the default scan stays untouched."""
        rollout_key = ("rollout", mesh_signature(mesh), with_gain,
                       with_drain, self.spec.key()) + self._cache_key()[1:]
        if rollout_key not in self._cache_keys_used:
            self._cache_keys_used = self._cache_keys_used + (rollout_key,)
        return self.plan_cache.get(rollout_key, partial(
            make_rollout_fn, params=self.params, compute=self.compute,
            memory=self.memory, act_bits=self.act_bits,
            input_bits=self.input_bits, mem_cap=self.mem_cap,
            compute_cap=self.compute_cap, throughput=self.throughput,
            order=self.order, spec=self.spec, p2=self.position_spec,
            mesh=mesh, with_gain=with_gain, with_drain=with_drain,
            use_kernels=self.use_kernels))

    # ------------------------------------------------------------------
    def _arrival_probs(self) -> np.ndarray:
        U = len(self.devices)
        if self.spec.arrival_weights is None:
            return np.full(U, 1.0 / U)
        w = np.asarray(self.spec.arrival_weights, np.float64)
        if w.shape != (U,) or (w < 0).any() or w.sum() <= 0:
            raise ValueError(f"arrival_weights must be {U} nonnegative "
                             "values with a positive sum")
        return w / w.sum()

    # ------------------------------------------------------------------
    def run(self, base_positions: np.ndarray, n_trajectories: int = 1,
            frames: Optional[int] = None,
            charge0: Optional[np.ndarray] = None,
            alive0: Optional[np.ndarray] = None,
            forced_failures: Optional[Sequence[Tuple[int, int]]] = None,
            sources: Optional[np.ndarray] = None,
            arrivals: Optional[np.ndarray] = None,
            waypoints: Optional[np.ndarray] = None,
            forced: Optional[np.ndarray] = None,
            gain_scale: Optional[np.ndarray] = None,
            extra_drain: Optional[np.ndarray] = None,
            mesh=None,
            devices: Union[None, int, Sequence] = None,
            rng: Optional[np.random.Generator] = None) -> RolloutTrace:
        """Roll B trajectories forward T frames in one device call.

        ``base_positions``: [U, 2] (tiled over trajectories) or [B, U, 2].
        ``forced_failures``: (frame, uav) pairs — the UAV is dead from that
        frame on in EVERY trajectory (the simulator's injection hook).
        ``forced``: the same hook as a full [T, B, U] bool tensor (what
        ``runtime.chaos.FaultSchedule`` compiles correlated bursts into —
        per-trajectory, per-frame forced deaths; OR-combined with
        ``forced_failures`` when both are given).
        ``gain_scale``: optional [T, B, U, U] (or [T, U, U] / [U, U],
        broadcast over missing axes) multiplicative link-gain factors —
        scripted link fades, applied in-trace to the eq. (7) thresholds
        and eq. (5) rates.  Must be positive.
        ``extra_drain``: optional [T, B, U] (or [T, U]) extra battery
        drain in joules per frame — scripted battery drops.  Must be
        nonnegative.  Either chaos tensor selects a separately compiled
        scan (its own ``PlanFnCache`` entry); the default rollout program
        is unchanged.
        ``arrivals``: optional [T, B, U] per-UAV request counts (the full
        Section II-A stream; default: ``requests_per_frame`` total arrivals
        drawn multinomially over the swarm with ``spec.arrival_weights``).
        ``sources``: optional [T, B] single capturing-UAV draws — sugar for
        an ``arrivals`` tensor with all ``requests_per_frame`` counts on
        the drawn UAV (the pre-multi-source API; mutually exclusive with
        ``arrivals``).  Both are validated host-side: indices outside
        [0, U) or negative counts raise instead of being silently clipped
        by the device gather.
        ``waypoints``: optional [B, U, 2] drift targets (default: drawn in
        ``spec.waypoint_range_m`` around each UAV's start, or the start
        itself when the range is 0 — pure jitter mobility).
        ``mesh`` / ``devices``: shard the trajectory axis over a 1-D device
        mesh for THIS run (overriding the constructor default; mutually
        exclusive with each other).  All randomness is drawn for the
        requested B BEFORE padding, so a sharded run consumes bit-identical
        host streams to the single-device run it is compared against; B is
        then edge-padded up to a mesh-size multiple and the filler rows
        masked out via ``RolloutTrace.valid``.
        ``rng``: optional ``numpy`` generator for THIS run's host draws
        (mobility jitter, failure/recovery uniforms, default arrivals),
        overriding the constructor-seeded stream.  Callers that replay
        windows independently of call order — the streaming gateway
        derives one child generator per serving window — pass it so a
        retried or reordered call consumes bit-identical draws.

        Under the JAX profiler the call shows as five host spans, in
        order: ``rollout.draws`` (host draws and input validation),
        ``rollout.put`` (host-to-device placement), ``rollout.scan`` (the
        device call, to its end), ``rollout.fetch`` (the copy back) and
        ``rollout.widen`` (to [B, T, ...] float64/int64).
        """
        import jax

        B = n_trajectories
        T = self.spec.frames if frames is None else frames
        with TraceAnnotation("rollout.draws"):
            inputs, bdims = self._host_inputs(
                base_positions, B, T, self._rng if rng is None else rng,
                charge0, alive0, forced_failures, sources, arrivals,
                waypoints, forced, gain_scale, extra_drain)
        with TraceAnnotation("rollout.put"):
            if mesh is not None or devices is not None:
                run_mesh = self._resolve_mesh(mesh, devices)
            else:
                run_mesh = self._default_mesh
            with_gain = gain_scale is not None
            with_drain = extra_drain is not None
            rollout = self._rollout \
                if (run_mesh is self._default_mesh
                    and not with_gain and not with_drain) \
                else self._rollout_fn(run_mesh, with_gain, with_drain)
            inputs, valid = self._place(inputs, bdims, B, run_mesh)
        with TraceAnnotation("rollout.scan"):
            outs = jax.block_until_ready(rollout(*inputs))
        with TraceAnnotation("rollout.fetch"):
            outs = [np.asarray(x) for x in outs]
        with TraceAnnotation("rollout.widen"):
            (pos, active, charge, latency, power, feasible, cap_ok, assign,
             lat_src, n_eff, e_tx, e_cmp) = outs

            def tm(arr, dtype=np.float64):      # [T, B, ...] -> [B, T, ...]
                return np.swapaxes(arr, 0, 1).astype(dtype)

            return RolloutTrace(
                latency=tm(latency), total_power=tm(power),
                feasible=tm(feasible, bool), cap_feasible=tm(cap_ok, bool),
                source_latency=tm(lat_src), assign=tm(assign, np.int64),
                positions=tm(pos), active=tm(active, bool),
                charge=tm(charge), n_requests=tm(n_eff, np.int64),
                energy_tx=tm(e_tx), energy_cmp=tm(e_cmp), valid=valid)

    def _host_inputs(self, base_positions, B, T, rng, charge0, alive0,
                     forced_failures, sources, arrivals, waypoints, forced,
                     gain_scale, extra_drain):
        """``run``'s host draws and input validation: the rollout's
        host-side inputs in argument order, with each one's batch axis
        (0 for [B, ...], 1 for [T, B, ...])."""
        U = len(self.devices)
        base = np.asarray(base_positions, np.float64)
        pos0 = np.broadcast_to(base, (B, U, 2)).astype(np.float32).copy() \
            if base.ndim == 2 else base.astype(np.float32)
        if waypoints is None:
            waypoints = pos0.copy()
            if self.spec.waypoint_range_m > 0:
                waypoints = waypoints + rng.uniform(
                    -self.spec.waypoint_range_m, self.spec.waypoint_range_m,
                    size=(B, U, 2)).astype(np.float32)
        jitter = np.zeros((T, B, U, 2), np.float32)
        if self.spec.jitter_sigma_m > 0:
            jitter = rng.normal(scale=self.spec.jitter_sigma_m,
                                size=(T, B, U, 2)).astype(np.float32)
        fail_u = rng.random((T, B, U)).astype(np.float32)
        recov_u = rng.random((T, B, U)).astype(np.float32)
        if forced is not None:
            forced = np.asarray(forced, dtype=bool)
            if forced.shape != (T, B, U):
                raise ValueError(f"forced must be [T={T}, B={B}, U={U}]; "
                                 f"got {forced.shape}")
            forced = forced.copy()
        else:
            forced = np.zeros((T, B, U), dtype=bool)
        for f, u in (forced_failures or ()):
            if 0 <= f < T:
                forced[f:, :, u] = True
        if gain_scale is not None:
            gain_scale = np.asarray(gain_scale, np.float32)
            if gain_scale.ndim == 2:
                gain_scale = np.broadcast_to(gain_scale, (T, B, U, U))
            elif gain_scale.ndim == 3:
                gain_scale = np.broadcast_to(gain_scale[:, None], (T, B, U, U))
            if gain_scale.shape != (T, B, U, U):
                raise ValueError(f"gain_scale must broadcast to [T={T}, "
                                 f"B={B}, U={U}, U]; got {gain_scale.shape}")
            if (gain_scale <= 0).any():
                raise ValueError("gain_scale factors must be positive")
            gain_scale = np.ascontiguousarray(gain_scale)
        if extra_drain is not None:
            extra_drain = np.asarray(extra_drain, np.float32)
            if extra_drain.ndim == 2:
                extra_drain = np.broadcast_to(extra_drain[:, None],
                                              (T, B, U))
            if extra_drain.shape != (T, B, U):
                raise ValueError(f"extra_drain must broadcast to [T={T}, "
                                 f"B={B}, U={U}]; got {extra_drain.shape}")
            if (extra_drain < 0).any():
                raise ValueError("extra_drain must be nonnegative joules")
            extra_drain = np.ascontiguousarray(extra_drain)
        if sources is not None and arrivals is not None:
            raise ValueError("pass either sources or arrivals, not both")
        if sources is not None:
            sources = np.asarray(sources, np.int64).reshape(T, B)
            if (sources < 0).any() or (sources >= U).any():
                raise ValueError(
                    f"sources must index UAVs in [0, {U}); got values in "
                    f"[{sources.min()}, {sources.max()}]")
            arrivals = np.zeros((T, B, U), np.float32)
            np.put_along_axis(arrivals, sources[..., None],
                              float(self.spec.requests_per_frame), axis=2)
        elif arrivals is None:
            arrivals = rng.multinomial(
                self.spec.requests_per_frame, self._arrival_probs(),
                size=(T, B)).astype(np.float32)
        else:
            arrivals = np.asarray(arrivals, np.float32)
            if arrivals.shape != (T, B, U):
                raise ValueError(f"arrivals must be [T={T}, B={B}, U={U}]; "
                                 f"got {arrivals.shape}")
            if (arrivals < 0).any():
                raise ValueError("arrivals must be nonnegative counts")
            slots = max(1, min(U, self.spec.requests_per_frame))
            widest = int(np.count_nonzero(arrivals, axis=-1).max())
            if widest > slots:
                raise ValueError(
                    f"arrivals touch up to {widest} distinct sources in a "
                    f"frame but the compiled rollout solves min(U, "
                    f"requests_per_frame) = {slots} source slots; raise "
                    f"RolloutSpec.requests_per_frame to at least {widest}")
        if charge0 is None:
            charge0 = np.full((B, U), self.spec.battery_j, np.float32)
        else:
            charge0 = np.broadcast_to(
                np.asarray(charge0, np.float32), (B, U)).copy()
        if alive0 is None:
            alive0 = np.ones((B, U), dtype=bool)

        inputs = [np.asarray(pos0, np.float32), charge0, alive0,
                  np.asarray(waypoints, np.float32), jitter, fail_u,
                  recov_u, forced, np.asarray(arrivals, np.float32)]
        bdims = [0, 0, 0, 0, 1, 1, 1, 1, 1]
        if gain_scale is not None:
            inputs.append(gain_scale)
            bdims.append(1)
        if extra_drain is not None:
            inputs.append(extra_drain)
            bdims.append(1)
        return inputs, bdims

    @staticmethod
    def _place(inputs, bdims, B, run_mesh):
        """The host-to-device placement of ``run``'s inputs, and the
        [B] validity mask (None when no row is padding)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if run_mesh is None:
            return [jnp.asarray(x) for x in inputs], None
        # pad ragged B up to the mesh size with edge rows (real data, so
        # the filler never produces NaN/inf surprises), record the
        # validity mask, and place every input under its NamedSharding so
        # the host->device transfer itself is already sharded — no full
        # replica ever materializes on one device.
        valid = None
        n_dev = run_mesh.devices.size
        Bpad = pad_to_multiple(B, n_dev)
        if Bpad != B:
            pad = Bpad - B
            inputs = [
                np.pad(x, [(0, pad) if d == bdim else (0, 0)
                           for d in range(x.ndim)], mode="edge")
                for x, bdim in zip(inputs, bdims)]
            valid = np.arange(Bpad) < B
        axis = run_mesh.axis_names[0]
        b_sh = NamedSharding(run_mesh, P(axis))
        tb_sh = NamedSharding(run_mesh, P(None, axis))
        return [jax.device_put(x, b_sh if bdim == 0 else tb_sh)
                for x, bdim in zip(inputs, bdims)], valid


__all__ = ["FleetRollout", "RolloutTrace", "RolloutSpec"]
