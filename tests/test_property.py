"""Hypothesis property tests on the system's invariants.

``hypothesis`` is an optional dev dependency (see README): the whole module
is skipped at collection when it isn't installed.
"""
import math

import numpy as np
import pytest

# `hypothesis` is deliberately NOT a runtime dependency (nothing in
# src/repro imports it) — it is a dev-only extra that CI installs in every
# tier-1 job, so these tests DO run on every push; the skip fires only in
# local environments that haven't installed it.  `pip install hypothesis`
# re-enables the module.  This is a reasoned environment guard, not a stale
# xfail: do not remove it without making hypothesis a hard dependency.
pytest.importorskip(
    "hypothesis",
    reason="property tests need the optional dev dependency `hypothesis` "
           "(CI installs it; `pip install hypothesis` re-enables locally)")
from hypothesis import given, settings, strategies as st

from repro.core import (Device, PlacementProblem, RadioChannel, RadioParams,
                        solve_bnb, solve_brute, solve_chain_dp_minmax,
                        solve_greedy, solve_positions, solve_positions_batched,
                        solve_positions_legacy, solve_power)
from repro.core.batch import coverage_radius
from repro.core.positions import hex_init

SETTINGS = dict(max_examples=25, deadline=None)


@st.composite
def placement_problems(draw, max_l=5, max_u=4):
    L = draw(st.integers(2, max_l))
    U = draw(st.integers(2, max_u))
    rng = np.random.default_rng(draw(st.integers(0, 2 ** 31)))
    compute = rng.uniform(1e4, 1e6, L)
    memory = rng.uniform(1e3, 1e5, L)
    act = rng.uniform(1e3, 1e5, L)
    tight = draw(st.booleans())
    devices = [Device(f"d{i}",
                      mem_cap=rng.uniform(5e4, 2e5) if tight else 1e9,
                      compute_cap=rng.uniform(5e5, 2e6) if tight else 1e12,
                      throughput=rng.uniform(1e8, 6e8)) for i in range(U)]
    rate = rng.uniform(1e7, 1e9, (U, U))
    rate = (rate + rate.T) / 2
    np.fill_diagonal(rate, np.inf)
    return PlacementProblem(compute, memory, act, devices, rate,
                            source=draw(st.integers(0, U - 1)),
                            input_bits=rng.uniform(1e3, 1e5))


def clone(p):
    return PlacementProblem(p.compute, p.memory, p.act_bits,
                            p.devices, p.rate, source=p.source,
                            input_bits=p.input_bits)


class TestPlacementProperties:
    @given(placement_problems())
    @settings(**SETTINGS)
    def test_bnb_is_exact(self, p):
        """Branch-and-bound == brute force on every instance."""
        s1 = solve_bnb(clone(p))
        s2 = solve_brute(clone(p))
        if not s2.assign:
            assert not s1.assign
        else:
            assert np.isclose(s1.latency, s2.latency, rtol=1e-9)

    @given(placement_problems())
    @settings(**SETTINGS)
    def test_exact_never_worse_than_greedy(self, p):
        s_exact = solve_bnb(clone(p))
        s_greedy = solve_greedy(clone(p))
        if s_greedy.assign and s_exact.assign:
            assert s_exact.latency <= s_greedy.latency + 1e-9
        if s_greedy.assign:
            assert s_exact.assign   # exact finds one whenever greedy does

    @given(placement_problems())
    @settings(**SETTINGS)
    def test_feasibility_of_solution(self, p):
        """Caps (11a/11b) hold; every layer placed exactly once (11c)."""
        sol = solve_bnb(clone(p))
        if not sol.assign:
            return
        assert len(sol.assign) == p.L
        mem = np.zeros(p.U)
        cmp_ = np.zeros(p.U)
        for j, i in enumerate(sol.assign):
            mem[i] += p.memory[j]
            cmp_[i] += p.compute[j]
        for i, d in enumerate(p.devices):
            assert mem[i] <= d.mem_cap + 1e-6
            assert cmp_[i] <= d.compute_cap + 1e-6

    @given(placement_problems(), st.integers(0, 100))
    @settings(**SETTINGS)
    def test_latency_objective_nonnegative_and_consistent(self, p, seed):
        rng = np.random.default_rng(seed)
        assign = tuple(int(x) for x in rng.integers(0, p.U, p.L))
        lat = p.latency(assign)
        assert lat >= 0.0
        # adding a device change can only add transfer time
        same = tuple([assign[0]] * p.L)
        if p.feasible(same):
            comp_only = p.transfer_time(p.source, same[0], p.input_bits) \
                + sum(p.compute_time(same[0], j) for j in range(p.L))
            assert p.latency(same) <= comp_only + 1e-9


class TestPowerProperties:
    @given(st.integers(2, 8), st.integers(0, 2 ** 31),
           st.sampled_from([5e6, 10e6, 20e6]))
    @settings(**SETTINGS)
    def test_power_monotone_in_bandwidth(self, n, seed, bw):
        """Fig. 4 trend as a property: more bandwidth => less power
        (comparable only when the lower-bandwidth swarm is fully
        connected — an infeasible swarm reports zero used power)."""
        rng = np.random.default_rng(seed)
        pos = rng.uniform(0, 120, (n, 2))
        d = np.sqrt(((pos[:, None] - pos[None, :]) ** 2).sum(-1))
        p_lo = solve_power(d, RadioChannel(RadioParams(bandwidth_hz=bw)))
        p_hi = solve_power(d, RadioChannel(RadioParams(bandwidth_hz=2 * bw)))
        if bool(np.all(p_lo.link_feasible)):
            assert p_hi.total_power <= p_lo.total_power + 1e-12

    @given(st.integers(2, 8), st.integers(0, 2 ** 31))
    @settings(**SETTINGS)
    def test_threshold_scales_with_distance_squared(self, n, seed):
        ch = RadioChannel()
        rng = np.random.default_rng(seed)
        d = rng.uniform(5, 100, n)
        th1 = ch.power_threshold(d)
        th2 = ch.power_threshold(2 * d)
        np.testing.assert_allclose(th2 / th1, 4.0, rtol=1e-9)


class TestMinmaxProperties:
    @given(placement_problems(max_l=6, max_u=3))
    @settings(**SETTINGS)
    def test_minmax_bottleneck_lower_bounds_sum(self, p):
        """Pipeline period <= end-to-end latency of the same partition."""
        n_stages = min(p.U, p.L)
        sol = solve_chain_dp_minmax(clone(p), n_stages)
        if not sol.assign:
            return
        assert sol.latency <= clone(p).latency(sol.assign) + 1e-9


class TestBatchedPositionProperties:
    """Invariants of the device-side P2 path (``solve_positions_batched``).

    Steps are held constant across examples so hypothesis never forces an
    XLA recompile (the scan length is a static argument); U varies, which
    costs at most one compile per swarm size.
    """

    P2_STEPS = 200

    def _inits(self, n, radius, seed, batch=4):
        """Mix of realistic inits: jittered hex packings and sparse uniform
        spreads (both inside the coverage circle)."""
        rng = np.random.default_rng(seed)
        cover = coverage_radius(n, radius)
        hexes = np.stack([hex_init(n, 2 * radius, jitter=radius / 3,
                                   seed=seed + i) for i in range(batch // 2)])
        spread = rng.uniform(-0.5 * cover, 0.5 * cover,
                             (batch - batch // 2, n, 2))
        return np.concatenate([hexes, spread])

    @given(st.integers(2, 6), st.floats(5.0, 25.0), st.integers(0, 2 ** 31))
    @settings(**SETTINGS)
    def test_repair_separation_and_coverage(self, n, radius, seed):
        """After the on-device repair: min pairwise distance >= 2R (small
        tolerance) and every UAV inside the coverage circle (eq. 8c/8d)."""
        pos0 = self._inits(n, radius, seed)
        sol = solve_positions_batched(pos0, RadioParams(), radius=radius,
                                      steps=self.P2_STEPS, center=(0.0, 0.0))
        d = np.sqrt(((sol.positions[:, :, None] -
                      sol.positions[:, None, :]) ** 2).sum(-1))
        d[:, np.eye(n, dtype=bool)] = np.inf
        assert d.min() >= 2 * radius - 0.5
        assert sol.max_violation.max() < 0.5
        r = np.linalg.norm(sol.positions, axis=-1)
        assert r.max() <= coverage_radius(n, radius) + 1e-3

    @given(st.integers(2, 6), st.floats(8.0, 25.0), st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_b1_parity_with_legacy(self, n, radius, seed):
        """The B = 1 slice and the legacy host-repair solver agree: both
        feasible, objectives within a constant factor (same trajectory;
        batched returns the best iterate, legacy the last)."""
        ch = RadioChannel()
        new = solve_positions(n, ch, radius=radius, steps=self.P2_STEPS,
                              seed=seed % 1000)
        old = solve_positions_legacy(n, ch, radius=radius,
                                     steps=self.P2_STEPS, seed=seed % 1000)
        for sol in (new, old):
            assert sol.max_violation < 0.5
        assert new.objective <= 2.0 * old.objective + 1e-12
        assert old.objective <= 2.0 * new.objective + 1e-12

    @given(st.integers(2, 6), st.floats(5.0, 25.0), st.integers(0, 2 ** 31))
    @settings(**SETTINGS)
    def test_objective_monotone_over_scan_steps(self, n, radius, seed):
        """The emitted objective trace never increases: the scan carries the
        best-so-far iterate, making the solver anytime-safe."""
        pos0 = self._inits(n, radius, seed)
        sol = solve_positions_batched(pos0, RadioParams(), radius=radius,
                                      steps=self.P2_STEPS)
        assert sol.objective_trace.shape == (pos0.shape[0], self.P2_STEPS)
        assert (np.diff(sol.objective_trace, axis=1) <= 0.0).all()


class TestRolloutBatteryProperties:
    """Invariants of the battery carry in the device-side fleet rollout.

    The rollout engine and its shapes are FIXED across examples (spec
    constants are baked into the trace, so varying them would force an XLA
    recompile per example); hypothesis varies only data — initial charge
    levels and the RNG draws behind mobility, failures, and sources.
    """

    B, T, U = 3, 5, 4
    _rollout = None

    @classmethod
    def rollout(cls):
        if cls._rollout is None:
            from repro.core import RolloutSpec, cnn_cost, make_devices
            from repro.configs.lenet import LENET
            from repro.runtime.fleet_rollout import FleetRollout
            from repro.runtime.scenario_engine import PlanFnCache
            spec = RolloutSpec(frames=cls.T, requests_per_frame=2,
                               jitter_sigma_m=2.0, failure_prob=0.15,
                               recovery_prob=0.2, hover_watts=0.05,
                               frame_s=1.0)
            cls._rollout = FleetRollout(
                RadioChannel(), make_devices(cls.U), cnn_cost(LENET), spec,
                plan_cache=PlanFnCache(), seed=0)
        return cls._rollout

    def _trace(self, charge_scale, seed):
        from repro.core.positions import hex_init
        rng = np.random.default_rng(seed)
        charge0 = (charge_scale *
                   rng.uniform(0.0, 1.0, (self.B, self.U))).astype(np.float32)
        trace = self.rollout().run(hex_init(self.U, 40.0, jitter=1.0,
                                            seed=seed % 1000),
                                   n_trajectories=self.B, charge0=charge0)
        return trace, charge0

    @given(st.floats(0.05, 10.0), st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_charge_monotone_nonincreasing_and_nonnegative(self, scale,
                                                           seed):
        trace, charge0 = self._trace(scale, seed)
        assert (trace.charge >= 0.0).all()
        assert (trace.charge[:, 0] <= charge0 + 1e-6).all()
        assert (np.diff(trace.charge, axis=1) <= 1e-6).all()

    @given(st.floats(0.05, 10.0), st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_dead_uav_excluded_from_placement(self, scale, seed):
        """A UAV entering a frame with zero charge is inactive there and
        never hosts a layer or captures the request."""
        trace, _ = self._trace(scale, seed)
        for b in range(self.B):
            for t in range(1, self.T):
                dead = trace.charge[b, t - 1] <= 0.0
                assert not trace.active[b, t][dead].any()
                for u in np.flatnonzero(dead):
                    assert (trace.assign[b, t] != u).all()
                    assert trace.n_requests[b, t, u] == 0 or not np.isfinite(
                        trace.latency[b, t])

    @given(st.floats(0.05, 10.0), st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_energy_nonnegative_and_only_from_active(self, scale, seed):
        trace, _ = self._trace(scale, seed)
        assert (trace.energy_tx >= 0.0).all()
        assert (trace.energy_cmp >= 0.0).all()
        # an inactive UAV spends nothing
        inactive = ~trace.active
        assert np.allclose(trace.energy_cmp[inactive], 0.0)

    @given(st.floats(0.05, 10.0), st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_shared_cap_never_exceeded_on_feasible_frames(self, scale,
                                                          seed):
        """Exact eq. (11b) pricing of the multi-source stream: on every
        FEASIBLE frame the aggregate per-UAV MACs — every source's
        placement weighted by its served arrival count — stay within the
        period compute budget; an over-budget frame must carry the
        cap-infeasible flag instead."""
        from repro.configs.lenet import LENET
        from repro.core import cnn_cost, make_devices
        trace, _ = self._trace(scale, seed)
        compute = np.array([l.flops for l in cnn_cost(LENET).layers])
        caps = np.array([d.compute_cap for d in make_devices(self.U)])
        onehot = trace.assign[..., None] == np.arange(self.U)  # [B,T,S,L,U]
        load = (onehot * compute[None, None, None, :, None]).sum(3)
        load = (load * trace.n_requests[..., None]).sum(2)     # [B,T,U]
        feas = trace.feasible
        assert (load[feas] <= caps[None, :] * (1 + 1e-6) + 1e-9).all()
        assert trace.cap_feasible[feas].all()
        # over-budget frames (if any were drawn) are flagged infeasible
        over = (load > caps[None, :] * (1 + 1e-6) + 1e-9).any(-1)
        assert not trace.feasible[over].any()


class TestShardInvarianceProperties:
    """Mesh-size invariance of the sharded fleet rollout (ISSUE 6).

    The trajectory axis is embarrassingly parallel, so every
    ``RolloutTrace`` aggregate statistic must be invariant to how B is
    sharded over a 1-D mesh — for ANY dynamics draw.  Hypothesis varies
    the data (B, initial charge, seed — hence mobility/failure/arrival
    streams); the engine constants and T are fixed per U so examples
    don't force an XLA recompile each (spec constants are baked into the
    trace), and B varies only within a small set (one trace per new
    (mesh, B-shard) shape, amortized across examples by the process-wide
    plan cache).  Mesh sizes are whatever the runtime offers: on a plain
    CPU run only {1}, under ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` (the tier1-multidevice CI job) {1, 2, 4, 8} —
    including ragged B/mesh combinations that exercise the padding mask.
    """

    T = 3

    @classmethod
    def mesh_sizes(cls):
        import jax
        n = jax.local_device_count()
        return [m for m in (1, 2, 4, 8) if m <= n]

    @classmethod
    def rollout(cls, u, seed):
        """A fresh FleetRollout per call (same seed => same host streams)
        over a per-U cached compile signature."""
        from repro.core import RolloutSpec, cnn_cost, make_devices
        from repro.configs.lenet import LENET
        from repro.runtime.fleet_rollout import FleetRollout
        spec = RolloutSpec(frames=cls.T, requests_per_frame=2,
                           jitter_sigma_m=2.0, failure_prob=0.2,
                           recovery_prob=0.3, hover_watts=0.05,
                           battery_j=2e3, frame_s=1.0)
        return FleetRollout(RadioChannel(), make_devices(u),
                            cnn_cost(LENET), spec, seed=seed)

    @given(st.integers(3, 4), st.sampled_from([1, 3, 5, 8]),
           st.floats(0.1, 5.0), st.integers(0, 2 ** 31))
    @settings(max_examples=8, deadline=None)
    def test_statistics_invariant_to_mesh_size(self, u, b, scale, seed):
        from repro.core.positions import hex_init
        rng = np.random.default_rng(seed)
        charge0 = (1e3 * scale *
                   rng.uniform(0.2, 1.0, (b, u))).astype(np.float32)
        base = hex_init(u, 40.0, jitter=1.0, seed=seed % 1000)
        stats = []
        for m in self.mesh_sizes():
            trace = self.rollout(u, seed % 97).run(
                base, n_trajectories=b, charge0=charge0, devices=m)
            assert trace.n_trajectories == b      # padding masked back out
            stats.append((trace.feasibility_rate, trace.mean_latency,
                          trace.mean_power, trace.latency_percentile(50.0),
                          trace.latency_percentile(95.0)))
        ref = stats[0]
        for got in stats[1:]:
            for a, c in zip(ref, got):
                if math.isinf(a) or math.isinf(c):
                    assert a == c
                else:
                    assert abs(a - c) <= 1e-6 * max(1.0, abs(a))


class TestRecoveryProperties:
    """Delegation safety (ISSUE 7): recovery ALWAYS lands on a plan whose
    assignment only addresses surviving devices — whether it came from the
    precomputed contingency table (single failure, survivor-normalized) or
    a live re-solve over the shrunk fleet.

    The engine shapes are cached at class scope: each survivor count
    compiles once across all hypothesis examples.
    """

    U = 5
    _cache = None

    @classmethod
    def cache(cls):
        if cls._cache is None:
            from repro.runtime.scenario_engine import PlanFnCache
            cls._cache = PlanFnCache()
        return cls._cache

    @given(st.integers(0, 2 ** 31),
           st.lists(st.integers(1, 2), min_size=1, max_size=3))
    @settings(max_examples=10, deadline=None)
    def test_recovery_addresses_only_survivors(self, seed, kill_sizes):
        from repro.configs.lenet import LENET
        from repro.core import cnn_cost
        from repro.core.swarm import make_devices
        from repro.runtime.fault_tolerance import FaultTolerantRunner
        from repro.runtime.scenario_engine import (ContingencyPlan,
                                                   ContingencyTable,
                                                   ScenarioBatch,
                                                   ScenarioEngine)
        cache = self.cache()
        ch = RadioChannel()
        mc = cnn_cost(LENET)
        devs = make_devices(self.U)
        base = hex_init(self.U, 40.0, jitter=0.5, seed=1)
        idx_of = {d.name: i for i, d in enumerate(devs)}

        def replan(survivors):
            eng = ScenarioEngine(ch, list(survivors), mc, plan_cache=cache)
            idx = [idx_of[d.name] for d in survivors]
            sb = ScenarioBatch(positions=base[idx][None],
                               source=np.zeros(1, np.int64))
            return eng.plan_batch(sb)

        engine = ScenarioEngine(ch, devs, mc, plan_cache=cache)
        table = ContingencyTable(engine, base, source=0)
        runner = FaultTolerantRunner(devs, replan, ".", contingency=table)
        rng = np.random.default_rng(seed)
        for size in kill_sizes:
            alive = [d.name for d in runner.state.devices]
            if len(alive) - size < 2:
                break
            dead = [str(n) for n in rng.choice(alive, size=size,
                                               replace=False)]
            plan = runner.on_failure(dead)
            n = len(runner.state.devices)
            if isinstance(plan, ContingencyPlan):
                # precomputed: already normalized to survivor index space
                assert plan.dead_index < 0
                assert max(plan.assign) < n
            else:
                used = set(int(x) for x in np.asarray(plan.assign).ravel()
                           if x >= 0)
                assert used <= set(range(n))


class TestCheckpointProperties:
    @given(st.lists(st.integers(1, 6), min_size=1, max_size=3),
           st.integers(0, 2 ** 31))
    @settings(max_examples=10, deadline=None)
    def test_roundtrip_arbitrary_trees(self, dims, seed):
        import tempfile
        from repro.runtime import checkpoint as ckpt
        rng = np.random.default_rng(seed)
        tree = {f"k{i}": rng.normal(size=tuple(dims)).astype(np.float32)
                for i in range(3)}
        tree["nested"] = {"s": np.asarray(seed % 1000)}
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 0, tree)
            got = ckpt.restore(d, 0, tree)
            for k in ("k0", "k1", "k2"):
                np.testing.assert_array_equal(got[k], tree[k])


class TestKernelParityProperties:
    """The Pallas planner kernels (ISSUE 9) are BITWISE drop-ins for their
    jnp oracles on arbitrary shapes and operand values — including the inf
    masking and first-argmin tie-breaks random integer grids produce in
    abundance.  Shapes are drawn from a small fixed pool so each example
    reuses a compiled program instead of forcing a fresh XLA trace."""

    _DP_SHAPES = [(1, 1, 2, 2), (2, 2, 3, 4), (3, 1, 5, 3), (2, 4, 4, 6)]
    _GEO_SHAPES = [(1, 2), (2, 4), (4, 3), (3, 6)]
    _dp_ref = None
    _geo_ref = None

    @classmethod
    def _refs(cls):
        import functools
        import jax
        from repro.core.channel import RadioParams
        from repro.kernels.link_geometry.ref import link_geometry_ref
        from repro.kernels.tropical_dp.ref import dp_step_ref
        if cls._dp_ref is None:
            cls._dp_ref = jax.jit(dp_step_ref)
            cls._geo_ref = jax.jit(functools.partial(
                link_geometry_ref, params=RadioParams()))
        return cls._dp_ref, cls._geo_ref

    @given(st.integers(0, len(_DP_SHAPES) - 1), st.integers(0, 2 ** 31),
           st.floats(0.0, 0.5))
    @settings(max_examples=10, deadline=None)
    def test_tropical_dp_step_bitwise(self, shape_i, seed, dead_frac):
        import jax.numpy as jnp
        from repro.kernels.tropical_dp.ops import dp_wavefront_step
        from repro.kernels.tropical_dp.tropical_dp import to_lanes
        dp_ref, _ = self._refs()
        B, M, L, S = self._DP_SHAPES[shape_i]
        rng = np.random.default_rng(seed)
        dp = rng.integers(0, 6, (B, M, L, S + 1)).astype(np.float32)
        tr = rng.integers(0, 4, (B, L, S, S + 1)).astype(np.float32)
        tr0 = rng.integers(0, 4, (B, M, S)).astype(np.float32)
        for arr in (dp, tr, tr0):
            arr[rng.random(arr.shape) < dead_frac] = np.inf
        dp[:, :, 0, :] = np.inf
        dp[:, :, 0, 0] = 0.0
        tr[:, 0] = np.inf
        ct = rng.integers(0, 3, (L, S)).astype(np.float32)
        ok = (rng.random((L, S)) > dead_frac).astype(np.float32)
        last = (lambda x: to_lanes(jnp.moveaxis(jnp.asarray(x), 0, -1)))
        args = [last(dp), last(tr), last(tr0), jnp.asarray(ct),
                jnp.asarray(ok)]
        ref = dp_ref(*args)
        got = dp_wavefront_step(*args, use_kernel=True)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @given(st.integers(0, len(_GEO_SHAPES) - 1), st.integers(0, 2 ** 31),
           st.booleans(), st.booleans())
    @settings(max_examples=10, deadline=None)
    def test_link_geometry_bitwise(self, shape_i, seed, with_gain,
                                   with_dead):
        import jax.numpy as jnp
        from repro.core.channel import RadioParams
        from repro.kernels.link_geometry.ops import fused_link_geometry
        _, geo_ref = self._refs()
        B, U = self._GEO_SHAPES[shape_i]
        rng = np.random.default_rng(seed)
        pos = jnp.asarray(rng.uniform(0, 400, (B, U, 2)), jnp.float32)
        active = np.ones((B, U), dtype=bool)
        if with_dead:
            active &= rng.random((B, U)) > 0.3
            active[~active.any(1), 0] = True
        gain = None
        if with_gain:
            g = rng.uniform(0.25, 2.0, (B, U, U))
            gain = jnp.asarray((g + g.transpose(0, 2, 1)) / 2, jnp.float32)
        active = jnp.asarray(active)
        ref = geo_ref(pos, active, gain)
        got = fused_link_geometry(pos, RadioParams(), active=active,
                                  gain_scale=gain, use_kernel=True)
        for name, a, b in zip(("dist", "threshold", "rate"), got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
