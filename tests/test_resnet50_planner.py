"""The planner on ResNet-50's 52-unit chain: the kernel and jnp paths of
``FleetRollout.run`` agree bitwise, the batched DP agrees with the scalar
``placement.solve_chain_dp`` on the same ``ModelCost``, and pricing the
residual side paths changes the cut a memory cap forces."""
import dataclasses

import numpy as np
import pytest

from repro.configs.resnet50 import RESNET50
from repro.core import (PlacementProblem, PositionSpec, RadioChannel,
                        RadioParams, RolloutSpec, cnn_cost, make_devices,
                        solve_chain_dp, solve_chain_dp_batched,
                        solve_power_batched)
from repro.core.batch import rate_matrix_batched
from repro.core.placement import Device
from repro.core.positions import hex_init
from repro.runtime.fleet_rollout import FleetRollout, RolloutTrace
from repro.runtime.scenario_engine import PlanFnCache

PARAMS = RadioParams()
MC = cnn_cost(RESNET50)
COMPUTE = np.array([l.flops for l in MC.layers])
MEMORY = np.array([l.weight_bytes for l in MC.layers])
ACT = np.array([l.act_bits for l in MC.layers])
#: 3% of 1 GB a UAV, so every chain spans at least four of the eight UAVs
#: (the benchmark deployment's 3.52% takes three)
MEM_FRAC = 0.03


def test_kernel_and_jnp_rollouts_agree_bitwise():
    U, B, T = 8, 8, 2
    spec = RolloutSpec(frames=T, requests_per_frame=8, jitter_sigma_m=2.0,
                       failure_prob=0.01, recovery_prob=0.5, battery_j=5e3)
    pos = hex_init(U, 40.0, jitter=0.5, seed=1)
    traces = []
    for use_kernels in (False, True):
        ro = FleetRollout(RadioChannel(PARAMS),
                          make_devices(U, mem_frac=MEM_FRAC), MC, spec,
                          position_spec=PositionSpec(steps=30,
                                                     repair_iters=25),
                          plan_cache=PlanFnCache(), use_kernels=use_kernels)
        traces.append(ro.run(pos, n_trajectories=B, frames=T,
                             rng=np.random.default_rng(11)))
    jnp_tr, kern_tr = traces
    fields = [f.name for f in dataclasses.fields(RolloutTrace)
              if f.name != "valid"]
    assert len(fields) == 12
    for name in fields:
        np.testing.assert_array_equal(getattr(kern_tr, name),
                                      getattr(jnp_tr, name), err_msg=name)
    assert jnp_tr.feasible.any()
    assert jnp_tr.assign.shape == (B, T, U, 52)


def _rates(n, U, seed, spread=90.0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, spread, (n, U, 2))
    dist = np.sqrt(((pos[:, :, None] - pos[:, None, :]) ** 2).sum(-1))
    sol = solve_power_batched(dist, PARAMS)
    rate = np.asarray(rate_matrix_batched(dist, sol.power, PARAMS,
                                          sol.link_feasible))
    return rate, rng.integers(0, U, n)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_batched_dp_matches_the_scalar_solver(use_kernel):
    U, n = 8, 6
    devs = make_devices(U, mem_frac=MEM_FRAC)
    rate, src = _rates(n, U, seed=4)
    assign, lat = solve_chain_dp_batched(
        COMPUTE, MEMORY, ACT, MC.input_bits,
        np.array([d.mem_cap for d in devs]),
        np.array([d.compute_cap for d in devs]),
        np.array([d.throughput for d in devs]), rate, src,
        use_kernel=use_kernel)
    assert np.isfinite(lat).any()
    for i in range(n):
        sol = solve_chain_dp(PlacementProblem(
            COMPUTE, MEMORY, ACT, devs, rate[i], source=int(src[i]),
            input_bits=MC.input_bits))
        assert np.isfinite(lat[i]) == np.isfinite(sol.latency)
        if np.isfinite(sol.latency):
            np.testing.assert_allclose(lat[i], sol.latency, rtol=1e-5)
            assert tuple(assign[i]) == sol.assign
            assert len(set(sol.assign)) >= 4


def test_side_paths_move_the_forced_cut_to_a_block_boundary():
    """Two equal UAVs, each able to hold 60% of the weights: conv5's 68 MB
    must be cut.  Priced without its side paths, the cheapest cut is
    after conv5_1a (0.80 Mbit); priced as executed, that cut also carries
    the 3.21 Mbit projection, and the block boundary after conv5_1c
    (3.21 Mbit) wins."""
    cap = 0.6 * MEMORY.sum()
    devs = [Device(f"uav{i}", cap, 1e12, 5e8) for i in range(2)]
    rate = np.array([[np.inf, 1e6], [1e6, np.inf]])
    blind = np.array([l.act_bits - l.skip_bits for l in MC.layers])

    def plan(act):
        return solve_chain_dp(PlacementProblem(
            COMPUTE, MEMORY, act, devs, rate, source=0,
            input_bits=MC.input_bits))

    def cut_after(sol):
        return MC.layers[sol.assign.index(1) - 1].name

    seen, priced = plan(blind), plan(ACT)
    assert cut_after(seen) == "conv5_1a"
    assert cut_after(priced) == "conv5_1c"
    truth = PlacementProblem(COMPUTE, MEMORY, ACT, devs, rate, source=0,
                             input_bits=MC.input_bits)
    assert truth.latency(seen.assign) > truth.latency(priced.assign)
    assert truth.latency(priced.assign) == pytest.approx(priced.latency)


def test_resnet50_dp_tile_fits_without_shrinking():
    """At the ResNet-50 cell's step (B = 4096, M = 8, L = 52, S = 8) the
    TPU row does not fit the VMEM budget; the shrink rule takes the slot
    tile to 1 before it takes the state tile below 2, so the tile timed
    at that shape (1 slot x 2 states) runs under the compiler's default
    VMEM limit, with no raised limit and no shape-keyed row."""
    from repro.kernels import autotune
    from repro.kernels.tropical_dp.tropical_dp import (
        _VMEM_BLOCK_BYTES, _cell_bytes, _tiles, lane_split)
    R, C = lane_split(4096)
    row = autotune.lookup("tropical_dp", U=8, L=52, S=8, backend="tpu")
    assert row == autotune.TABLE[("tropical_dp", "tpu")]
    assert _cell_bytes(row["block_m"], 8, row["block_b"] // C, C, 52,
                       8) > _VMEM_BLOCK_BYTES
    tiles = _tiles(8, R, C, 52, 8, row["block_b"], row["block_m"],
                   row["block_s"], interpret=False)
    assert tiles == (row["block_b"] // C, 1, 2, None)


@pytest.mark.parametrize("U,L,M,B,want", [
    # the AlexNet cell's step: the TPU row fits as it stands
    pytest.param(8, 11, 8, 8192, (8, 2, 8, None), id="8-11-8"),
    # U = L = 32: one slot and one state, under the raised limit
    pytest.param(32, 32, 32, 64, (1, 1, 1, 64 << 20), id="32-32-32")])
def test_dp_tile_rule_at_compile_shapes(U, L, M, B, want):
    """The shrink rule leaves the tiles of the other compiled shapes
    (``tests/test_tpu_compile.py``) as they were."""
    from repro.kernels import autotune
    from repro.kernels.tropical_dp.tropical_dp import _tiles, lane_split
    R, C = lane_split(B)
    row = autotune.lookup("tropical_dp", U=U, L=L, S=U, backend="tpu")
    assert _tiles(M, R, C, L, U, row["block_b"], row["block_m"],
                  row["block_s"], interpret=False) == want
