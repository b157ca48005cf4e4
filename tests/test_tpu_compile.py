"""Compile the planner's Pallas kernels and the rollout program for a TPU v5e
that is described, not attached.

Interpret mode accepts tiles that Mosaic refuses (blocks off the (8, 128)
tiling, size-2 minor axes, more VMEM than a kernel may use), so these
cases lower and compile with ``interpret=False`` against the v5e compiler
installed with jaxlib.  Nothing runs: results and speed need a chip.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and a test module that
touched it while being collected would break the other xdist workers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.kernels as kernels
from repro.core.channel import RadioParams
from repro.kernels import autotune
from repro.kernels.link_geometry.link_geometry import link_geometry
from repro.kernels.tropical_dp.tropical_dp import lane_split, tropical_dp_step

PARAMS = RadioParams()
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # an entry compiled for a described chip cannot be read back without
    # one, so keep these compiles out of the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def compiled_text(fn, one_chip, *shapes) -> str:
    """Lower and compile ``fn`` for the described chip; the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("U,L,M,B", [
    pytest.param(8, 7, 1, 64, id="8-7-1"),
    pytest.param(8, 7, 2, 64, id="8-7-2"),
    # U = L = 32 takes one state per cell and the raised VMEM limit
    pytest.param(32, 32, 32, 64, id="32-32-32"),
    # the AlexNet kernel cell's step: 8 slots, L = 11, B = 8192 on lanes
    pytest.param(8, 11, 8, 8192, id="8-11-8")])
def test_tropical_dp_compiles(one_chip, U, L, M, B):
    S = U
    R, C = lane_split(B)
    blocks = autotune.lookup("tropical_dp", U=U, L=L, S=S, backend="tpu")

    def step(dp, tr, tr0, ct, ok):
        return tropical_dp_step(dp, tr, tr0, ct, ok, interpret=False,
                                **blocks)

    # the whole [L+1]-row table, as the solver's scan passes it
    text = compiled_text(step, one_chip, ((M, L + 1, S + 1, R, C), F32),
                         ((L, S, S + 1, R, C), F32), ((M, S, R, C), F32),
                         ((L, S), F32), ((L, S), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,U", [(1, 5), (1, 8), (1, 32), (256, 5),
                                 (256, 8), (256, 32), (100, 8)])
def test_link_geometry_compiles(one_chip, B, U):
    blocks = autotune.lookup("link_geometry", U=U, backend="tpu")

    def geometry(pos, active, gain):
        return link_geometry(pos, active, gain, params=PARAMS,
                             interpret=False, **blocks)

    text = compiled_text(geometry, one_chip, ((B, U, 2), F32),
                         ((B, U), F32), ((B, U, U), F32))
    assert "tpu_custom_call" in text


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the kernel dispatch resolve as it does on a TPU (compiled
    Pallas, TPU autotune rows) while this CPU process traces.  Traces are
    cleared on both sides so no CPU-resolved program leaks in or out."""
    jax.clear_caches()
    monkeypatch.setattr(kernels, "_DEFAULT_BACKEND", "tpu")
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("use_kernels", [False, True])
def test_rollout_program_compiles(one_chip, tpu_backend, use_kernels):
    """The rollout-paper program (U = 8, LeNet, RQ = 2, warm-started P2)
    at B = 256 — the scan that ``FleetRollout.run`` executes."""
    from repro.configs.lenet import LENET
    from repro.core import (PositionSpec, RadioChannel, RolloutSpec,
                            cnn_cost, make_devices)
    from repro.core.rollout import make_rollout_fn
    from repro.runtime.scenario_engine import PlanFnCache, ScenarioEngine

    B, T, U = 256, 32, 8
    eng = ScenarioEngine(RadioChannel(PARAMS), make_devices(U),
                         cnn_cost(LENET), plan_cache=PlanFnCache())
    spec = RolloutSpec(frames=T, requests_per_frame=2, jitter_sigma_m=2.0,
                       battery_j=5e3)
    rollout = make_rollout_fn(
        lambda: None, params=eng.params, compute=eng.compute,
        memory=eng.memory, act_bits=eng.act_bits,
        input_bits=eng.input_bits, mem_cap=eng.mem_cap,
        compute_cap=eng.compute_cap, throughput=eng.throughput,
        order=eng.order, spec=spec,
        p2=PositionSpec(steps=30, repair_iters=25), use_kernels=use_kernels)
    b, tb = (B, U), (T, B, U)
    text = compiled_text(
        rollout, one_chip, (b + (2,), F32), (b, F32), (b, np.bool_),
        (b + (2,), F32), (tb + (2,), F32), (tb, F32), (tb, F32),
        (tb, np.bool_), (tb, F32))
    assert ("tpu_custom_call" in text) == use_kernels
