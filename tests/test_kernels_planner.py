"""Planner Pallas kernels vs their jnp oracles — bitwise.

The tropical-DP wavefront kernel and the fused link-geometry kernel
(ISSUE 9) must reproduce the planner's jnp hot loops EXACTLY: same
latencies, same first-argmin tie-breaks, same parent pointers, same
masking of failed UAVs.  Comparisons here are ``assert_array_equal`` —
bit equality, not tolerance — because the kernel path is advertised as a
drop-in program swap (``use_kernels``) whose plans must be
indistinguishable from the jnp path's.

Both sides of every comparison run under ``jax.jit``: XLA fuses
elementwise chains (with FMA on CPU) differently in an eager op-by-op
run, so jit-vs-eager can differ in the last ulp while jit-vs-jit — the
only configuration the planner ever runs — is exact.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.batch as batch
import repro.kernels as kernels
from repro.core.batch import solve_chain_dp_batched, solve_chain_dp_multisource
from repro.core.channel import RadioParams
from repro.kernels import autotune, default_backend, resolve_interpret
from repro.kernels.link_geometry.ops import fused_link_geometry
from repro.kernels.link_geometry.ref import link_geometry_ref
from repro.kernels.tropical_dp.ops import dp_wavefront_step
from repro.kernels.tropical_dp.ref import dp_step_ref, dp_step_rows
from repro.kernels.tropical_dp.tropical_dp import from_lanes, to_lanes

PARAMS = RadioParams()
INF = np.inf


# ---------------------------------------------------------------------------
# operand builders
# ---------------------------------------------------------------------------


def lanes(dp, tr, tr0, ct, ok):
    """Scenario-first step operands -> the kernel's layout (scenarios last,
    split (R, C) and inf-padded: ``tropical_dp.to_lanes``)."""
    last = (lambda x: to_lanes(jnp.moveaxis(jnp.asarray(x), 0, -1)))
    return [last(dp), last(tr), last(tr0), jnp.asarray(ct), jnp.asarray(ok)]


def scenario_rows(out, B):
    """Kernel-layout results [M, S, R, C] -> scenario-first [B, M, S]."""
    return np.moveaxis(np.asarray(from_lanes(out, B)), -1, 0)


def dp_step_operands(seed, B=3, M=2, L=5, S=4, dead_frac=0.15):
    """Random wavefront-step operands with the solver's structure: dp row 0
    = [0, inf...], dead a = 0 row in tr, a sprinkling of inf (dead UAV /
    infeasible link) entries, and a coarse value grid so ties occur
    naturally on top of the crafted ones.  In the kernel's layout."""
    rng = np.random.default_rng(seed)
    dp = rng.integers(0, 8, (B, M, L, S + 1)).astype(np.float32)
    dp[:, :, 0, :] = INF
    dp[:, :, 0, 0] = 0.0
    tr = rng.integers(0, 5, (B, L, S, S + 1)).astype(np.float32)
    tr[:, 0] = INF                       # dead placeholder row
    tr0 = rng.integers(0, 5, (B, M, S)).astype(np.float32)
    for arr in (dp, tr, tr0):
        arr[rng.random(arr.shape) < dead_frac] = INF
    dp[:, :, 0, 0] = 0.0
    ct = rng.integers(0, 3, (L, S)).astype(np.float32)
    ok = (rng.random((L, S)) > 0.25).astype(np.float32)
    return lanes(dp, tr, tr0, ct, ok)


def crafted_step_operands(seed, B, M, L, S):
    """Operands whose scenarios cycle through three crafted kinds: all-inf
    (no finite candidate at any a, the source row dead too), exact ties
    across every s0 AND every a (each candidate costs 6), and dense ties
    from values in {0, 1}.  Every (a, s) is feasible, so the ties reach
    the outer min."""
    rng = np.random.default_rng(seed)
    dp = rng.integers(0, 2, (B, M, L, S + 1)).astype(np.float32)
    tr = rng.integers(0, 2, (B, L, S, S + 1)).astype(np.float32)
    tr0 = rng.integers(0, 2, (B, M, S)).astype(np.float32)
    kind = np.arange(B) % 3
    dp[kind == 0] = INF
    tr0[kind == 0] = INF
    dp[kind == 1, :, :, :] = 2.0
    tr[kind == 1] = 4.0
    tr0[kind == 1] = 6.0
    dp[:, :, 0, :] = INF
    dp[:, :, 0, 0] = 0.0
    tr[:, 0] = INF
    ct = np.zeros((L, S), np.float32)
    ok = np.ones((L, S), np.float32)
    return lanes(dp, tr, tr0, ct, ok)


def geometry_operands(seed, B=4, U=6, with_gain=True, dead=True):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 300, (B, U, 2)).astype(np.float32)
    active = np.ones((B, U), dtype=bool)
    if dead:
        active[rng.integers(0, B, 2), rng.integers(0, U, 2)] = False
    gain = None
    if with_gain:
        g = rng.uniform(0.5, 1.5, (B, U, U))
        gain = jnp.asarray((g + g.transpose(0, 2, 1)) / 2, jnp.float32)
    return jnp.asarray(pos), jnp.asarray(active), gain


# ---------------------------------------------------------------------------
# tropical-DP wavefront step
# ---------------------------------------------------------------------------


class TestTropicalDpStep:
    REF = staticmethod(jax.jit(dp_step_ref))

    def assert_step_parity(self, args, **blocks):
        row_r, pa_r, ps_r = self.REF(*args)
        row_k, pa_k, ps_k = dp_wavefront_step(*args, use_kernel=True,
                                              **blocks)
        np.testing.assert_array_equal(np.asarray(row_k), np.asarray(row_r))
        np.testing.assert_array_equal(np.asarray(pa_k), np.asarray(pa_r))
        np.testing.assert_array_equal(np.asarray(ps_k), np.asarray(ps_r))

    @pytest.mark.parametrize("seed", range(6))
    def test_bitwise_parity_random(self, seed):
        self.assert_step_parity(dp_step_operands(seed))

    @pytest.mark.parametrize("shape", [(1, 1, 2, 2), (2, 4, 3, 5),
                                       (5, 1, 8, 3), (2, 3, 4, 8),
                                       # the AlexNet cell's step: 8 slots,
                                       # L = 11, S = 8, two lane rows
                                       (256, 8, 11, 8),
                                       # B off the 128-lane multiple
                                       # (inf-padded), with 8 and 1 slots
                                       (200, 8, 11, 8), (200, 1, 11, 8)])
    def test_bitwise_parity_shapes(self, shape):
        B, M, L, S = shape
        self.assert_step_parity(dp_step_operands(99, B=B, M=M, L=L, S=S))

    @pytest.mark.parametrize("shape", [(3, 2, 5, 4), (256, 8, 11, 8),
                                       (200, 1, 11, 8)])
    def test_bitwise_parity_crafted(self, shape):
        """All-inf scenarios and exact ties in both s0 and a, scenario by
        scenario across the lanes: the running minima must keep the
        oracle's first-argmin and all-inf -> index 0 conventions."""
        B, M, L, S = shape
        args = crafted_step_operands(13, B, M, L, S)
        self.assert_step_parity(args)
        row, pa, ps = (scenario_rows(o, B) for o in
                       dp_wavefront_step(*args, use_kernel=True))
        dead, tied = np.arange(B) % 3 == 0, np.arange(B) % 3 == 1
        assert np.isinf(row[dead]).all()
        np.testing.assert_array_equal(pa[dead], 0)
        np.testing.assert_array_equal(ps[dead], 0)
        # every candidate costs 6: the source row (a = 0, s0 = 0) wins
        np.testing.assert_array_equal(row[tied], 6.0)
        np.testing.assert_array_equal(pa[tied], 0)
        np.testing.assert_array_equal(ps[tied], 0)

    @pytest.mark.parametrize("blocks", [dict(block_b=128),
                                        dict(block_m=1),
                                        dict(block_s=2),
                                        dict(block_b=128, block_m=1,
                                             block_s=2),
                                        dict(block_s=3)])  # snaps 3 -> 2
    def test_tiled_grids_match(self, blocks):
        """Multi-cell grids (interpret mode runs them sequentially) emit
        the same tiles as the whole-axis launch; B = 256 is two lane rows,
        so 128 scenarios a cell tiles the scenario axis."""
        self.assert_step_parity(dp_step_operands(7, B=256, M=2, L=4, S=4),
                                **blocks)

    def test_first_argmin_tie_breaks(self):
        """Equal-cost candidates across BOTH reduction axes: the winner
        must be the lexicographically first (a, s0), exactly jnp.argmin's
        first-occurrence rule in the oracle's two-stage order."""
        B, M, L, S = 1, 1, 3, 3
        dp = np.full((B, M, L, S + 1), INF, np.float32)
        dp[:, :, 0, 0] = 0.0
        dp[0, 0, 1] = [INF, 2.0, 2.0, 2.0]       # s0 = 1, 2, 3 all tie
        dp[0, 0, 2] = [INF, 1.0, 1.0, INF]
        tr = np.full((B, L, S, S + 1), INF, np.float32)
        tr[0, 1, :, 1:] = 3.0                     # a = 1: every s0 ties
        tr[0, 2, :, 1:] = 4.0                     # a = 2: 1 + 4 = 2 + 3 tie
        tr0 = np.full((B, M, S), 5.0, np.float32)
        ct = np.zeros((L, S), np.float32)
        ok = np.ones((L, S), np.float32)
        args = lanes(dp, tr, tr0, ct, ok)
        row_k, pa_k, ps_k = (scenario_rows(o, B) for o in
                             dp_wavefront_step(*args, use_kernel=True))
        # candidates: a=0 -> 0+5=5; a=1 -> 2+3=5; a=2 -> 1+4=5: a=0 wins
        np.testing.assert_array_equal(row_k[0, 0], 5.0)
        np.testing.assert_array_equal(pa_k[0, 0], 0)
        np.testing.assert_array_equal(ps_k[0, 0], 0)
        # kill the a=0 candidate: a=1 wins over the equal a=2, s0 first-min
        tr0[:] = INF
        args = lanes(dp, tr, tr0, ct, ok)
        self.assert_step_parity(args)
        row_k, pa_k, ps_k = (scenario_rows(o, B) for o in
                             dp_wavefront_step(*args, use_kernel=True))
        np.testing.assert_array_equal(pa_k[0, 0], 1)
        np.testing.assert_array_equal(ps_k[0, 0], 1)

    def test_all_infeasible_matches_oracle(self):
        """Fully masked steps (dead fleet) keep argmin's all-inf -> index 0
        convention on both paths."""
        args = dp_step_operands(3)
        args[4] = jnp.zeros_like(args[4])         # ok = 0 everywhere
        self.assert_step_parity(args)
        row_k, pa_k, ps_k = dp_wavefront_step(*args, use_kernel=True)
        assert np.isinf(np.asarray(row_k)).all()
        np.testing.assert_array_equal(np.asarray(pa_k), 0)

    def test_ref_lifts_the_solver_step(self):
        """``dp_step_ref`` in the kernel's layout is the scenario-first
        step ``dp_step_rows`` moved by transposes, padding sliced off."""
        B, M, L, S = 200, 2, 6, 5
        rng = np.random.default_rng(21)
        dp = rng.integers(0, 8, (B, M, L, S + 1)).astype(np.float32)
        dp[:, :, 0, :] = INF
        dp[:, :, 0, 0] = 0.0
        tr = rng.integers(0, 5, (B, L, S, S + 1)).astype(np.float32)
        tr0 = rng.integers(0, 5, (B, M, S)).astype(np.float32)
        ct = rng.integers(0, 3, (L, S)).astype(np.float32)
        ok = (rng.random((L, S)) > 0.25).astype(np.float32)
        rows = jax.jit(dp_step_rows)(dp, tr, tr0, ct, ok)
        lifted = self.REF(*lanes(dp, tr, tr0, ct, ok))
        for a, b in zip(lifted, rows):
            np.testing.assert_array_equal(scenario_rows(a, B), np.asarray(b))

    def test_compiled_mode_or_skip(self):
        """interpret=False must agree bitwise wherever the backend compiles
        Pallas (TPU/GPU); CPU refuses — skip, don't fail."""
        args = dp_step_operands(5, B=2, M=1, L=3, S=3)
        ref = dp_wavefront_step(*args, use_kernel=True, interpret=True)
        try:
            got = dp_wavefront_step(*args, use_kernel=True, interpret=False)
        except Exception:
            pytest.skip("backend does not compile Pallas kernels "
                        "(CPU supports interpret mode only)")
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# fused link geometry
# ---------------------------------------------------------------------------


class TestLinkGeometryKernel:
    REF = staticmethod(jax.jit(
        functools.partial(link_geometry_ref, params=PARAMS)))

    def assert_geometry_parity(self, pos, active, gain, **blocks):
        ref = self.REF(pos, active, gain)
        got = fused_link_geometry(pos, PARAMS, active=active,
                                  gain_scale=gain, use_kernel=True,
                                  **blocks)
        for name, a, b in zip(("dist", "threshold", "rate"), got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("with_gain", [False, True])
    def test_bitwise_parity(self, seed, with_gain):
        self.assert_geometry_parity(
            *geometry_operands(seed, with_gain=with_gain))

    def test_dead_uav_masking(self):
        """A failed UAV transmits nothing and anchors no pair feasibility
        — its rate rows/cols must match the oracle's masked solve."""
        pos, active, gain = geometry_operands(11, dead=False)
        active = np.asarray(active).copy()
        active[:, 2] = False                    # one UAV down everywhere
        active[0, :] = False                    # one scenario fully down
        self.assert_geometry_parity(pos, jnp.asarray(active), gain)

    @pytest.mark.parametrize("blocks", [dict(block_b=2),
                                        dict(block_u=3),
                                        dict(block_b=1, block_u=2),
                                        dict(block_u=4)])  # snaps 4 -> 3
    def test_tiled_grids_match(self, blocks):
        self.assert_geometry_parity(*geometry_operands(2), **blocks)

    def test_ref_equals_oracle_stage(self):
        """The ref IS the planner's current geometry stage — pin it to the
        four batch.py passes so kernel parity transitively reaches them."""
        from repro.core.batch import (pairwise_dist_batched,
                                      power_threshold_batched,
                                      rate_matrix_batched,
                                      solve_power_batched)
        pos, active, gain = geometry_operands(4)

        @jax.jit
        def staged(pos, active, gain):
            dist = pairwise_dist_batched(pos)
            th = power_threshold_batched(dist, PARAMS, gain_scale=gain)
            pw = solve_power_batched(dist, PARAMS, active=active,
                                     gain_scale=gain, threshold_matrix=th)
            rate = rate_matrix_batched(dist, pw.power, PARAMS,
                                       pw.link_feasible, gain_scale=gain)
            return dist, th, rate

        for a, b in zip(self.REF(pos, active, gain),
                        staged(pos, active, gain)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_direct_body_equals_pallas_launch(self):
        """On CPU the default dispatch skips the Pallas interpreter and
        runs the kernel body directly (``link_geometry_fused``); it must
        be bit-identical to the explicit ``pallas_call`` launch."""
        pos, active, gain = geometry_operands(8)
        for g in (gain, None):
            direct = fused_link_geometry(pos, PARAMS, active=active,
                                         gain_scale=g, use_kernel=True)
            launch = fused_link_geometry(pos, PARAMS, active=active,
                                         gain_scale=g, use_kernel=True,
                                         interpret=True)
            for a, b in zip(direct, launch):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_compiled_mode_or_skip(self):
        pos, active, gain = geometry_operands(6, B=2, U=4)
        ref = fused_link_geometry(pos, PARAMS, active=active,
                                  gain_scale=gain, interpret=True)
        try:
            got = fused_link_geometry(pos, PARAMS, active=active,
                                      gain_scale=gain, interpret=False)
        except Exception:
            pytest.skip("backend does not compile Pallas kernels "
                        "(CPU supports interpret mode only)")
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# solver wrappers: kernel path vs jnp path through the public API
# ---------------------------------------------------------------------------


def dp_problem(seed, B=5, U=5, L=6, symmetric=False):
    """A full chain-DP problem over a real rate matrix.  ``symmetric``
    collapses every device and every link to identical constants, so MANY
    placements tie exactly — the adversarial case for tie-break parity."""
    rng = np.random.default_rng(seed)
    pos, active, gain = geometry_operands(seed, B=B, U=U, with_gain=False)
    rate = np.array(link_geometry_ref(pos, active, gain,
                                      params=PARAMS)[2])
    if symmetric:
        off = ~np.eye(U, dtype=bool)
        rate[:, off] = 2e7                      # every live link identical
        rate[np.asarray(~active)] = 0.0
        rate[:, :, :][~np.asarray(active)[:, None, :]
                      .repeat(U, 1)] = 0.0
        rate[:, np.eye(U, dtype=bool)] = np.inf
    mk = (lambda n, lo, hi: np.full(n, lo) if symmetric
          else rng.uniform(lo, hi, n))
    return dict(compute=mk(L, 1e6, 5e6), memory=mk(L, 1e4, 1e5),
                act_bits=mk(L, 1e4, 1e5), input_bits=5e4,
                mem_cap=mk(U, 2e5, 6e5), compute_cap=mk(U, 1e7, 4e7),
                throughput=mk(U, 1e8, 5e8), rate=rate,
                active=np.asarray(active),
                source=rng.integers(0, U, B),
                sources=rng.integers(0, U, (B, 3)))


class TestSolverKernelPath:
    @pytest.mark.parametrize("seed,symmetric", [(0, False), (1, False),
                                                (2, True), (3, True)])
    def test_single_source_bitwise(self, seed, symmetric):
        p = dp_problem(seed, symmetric=symmetric)
        args = (p["compute"], p["memory"], p["act_bits"], p["input_bits"],
                p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
                p["source"], p["active"])
        a0, l0 = solve_chain_dp_batched(*args)
        a1, l1 = solve_chain_dp_batched(*args, use_kernel=True)
        np.testing.assert_array_equal(a1, a0)
        np.testing.assert_array_equal(l1, l0)

    @pytest.mark.parametrize("seed,symmetric", [(4, False), (5, True)])
    def test_multi_source_bitwise(self, seed, symmetric):
        """The kernel's native slot axis vs the oracle's vmap — one launch
        per step must equal M independent solves, tie-breaks included."""
        p = dp_problem(seed, symmetric=symmetric)
        args = (p["compute"], p["memory"], p["act_bits"], p["input_bits"],
                p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
                p["sources"], p["active"])
        a0, l0 = solve_chain_dp_multisource(*args)
        a1, l1 = solve_chain_dp_multisource(*args, use_kernel=True)
        np.testing.assert_array_equal(a1, a0)
        np.testing.assert_array_equal(l1, l0)

    @pytest.mark.parametrize("multi", [False, True])
    def test_ragged_batch_bitwise(self, multi):
        """B = 200 is padded to two 128-lane rows of inf scenarios inside
        the kernel path; the padding must not reach the plans."""
        p = dp_problem(8, B=200)
        args = (p["compute"], p["memory"], p["act_bits"], p["input_bits"],
                p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
                p["sources"] if multi else p["source"], p["active"])
        solve = solve_chain_dp_multisource if multi else \
            solve_chain_dp_batched
        a0, l0 = solve(*args)
        a1, l1 = solve(*args, use_kernel=True)
        np.testing.assert_array_equal(a1, a0)
        np.testing.assert_array_equal(l1, l0)

    def test_dead_uav_never_hosts(self):
        p = dp_problem(6)
        active = p["active"].copy()
        active[:, 1] = False
        a1, _ = solve_chain_dp_batched(
            p["compute"], p["memory"], p["act_bits"], p["input_bits"],
            p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
            p["source"], active, use_kernel=True)
        assert (a1 != 1).all()


# ---------------------------------------------------------------------------
# backtrack: the row-by-row walk against the gather walk
# ---------------------------------------------------------------------------


def gather_walk(pa, ps, s_best, order_arr, state_axis):
    """The chain DP's backtrack as a gather: ``_chain_dp_backtrack``'s
    signature and result, computed the way the solvers once did it.  The
    tables are laid out as ``[L, R, S+1]`` rows with a zero column for
    state 0, and every step gathers ``pa[b-1, row, s]``."""
    L, S = pa.shape[0], order_arr.shape[0]

    def rows(p):                     # [L, ..., S, ...] -> [L, R, S+1]
        p = jnp.moveaxis(p, state_axis + 1, -1).reshape(L, -1, S)
        return jnp.concatenate([jnp.zeros_like(p[..., :1]), p], -1)

    pa, ps = rows(pa), rows(ps)
    R = pa.shape[1]
    ix = jnp.arange(R)

    def backward(carry, j):
        b, s = carry
        dev = order_arr[jnp.maximum(s - 1, 0)]
        bi = jnp.clip(b - 1, 0, L - 1)
        a = pa[bi, ix, s]
        s0 = ps[bi, ix, s]
        at_start = j == a
        return (jnp.where(at_start, a, b), jnp.where(at_start, s0, s)), dev

    init = (jnp.full((R,), L, jnp.int32), s_best.reshape(R))
    _, devs = jax.lax.scan(backward, init, jnp.arange(L - 1, -1, -1))
    return devs[::-1].reshape((L,) + s_best.shape)


def parent_tables(seed, L, S, n):
    """Random valid parent tables ``[L, S, n]`` (``pa[b-1] <= b-1``, ``ps``
    in 0..S) and ``s_best`` [n], the rows cycling through four kinds: any
    pointers; ``s_best = 0``; all-infeasible (zero pointers and
    ``s_best = 0``, as an all-inf DP writes them); and ties (every state
    of a row carries the same parents, as on a symmetric swarm)."""
    rng = np.random.default_rng(seed)
    bound = np.arange(1, L + 1)[:, None, None]
    pa = (rng.random((L, S, n)) * bound).astype(np.int32)
    ps = rng.integers(0, S + 1, (L, S, n)).astype(np.int32)
    s_best = rng.integers(0, S + 1, n).astype(np.int32)
    kind = np.arange(n) % 4
    s_best[kind == 1] = 0
    pa[:, :, kind == 2] = 0
    ps[:, :, kind == 2] = 0
    s_best[kind == 2] = 0
    pa[:, :, kind == 3] = pa[:, :1, kind == 3]
    ps[:, :, kind == 3] = ps[:, :1, kind == 3]
    order = rng.permutation(S).astype(np.int32)
    return pa, ps, s_best, order


def laid_out(layout, pa, ps, s_best, M=3):
    """The tables in a solver's layout, with the helper's ``state_axis``:
    ``rows`` is the jnp path's [L, S, B]; ``lanes`` the kernel path's
    [L, M, S, Bt, C] (``to_lanes``, B ragged so lanes are padded)."""
    if layout == "rows":
        return jnp.asarray(pa), jnp.asarray(ps), jnp.asarray(s_best), 0
    L, S, n = pa.shape
    lane = (lambda p: to_lanes(jnp.asarray(
        p.reshape(L, S, M, n // M).transpose(0, 2, 1, 3)), 0))
    return (lane(pa), lane(ps),
            to_lanes(jnp.asarray(s_best.reshape(M, n // M)), 0), 1)


class TestBacktrack:
    WALK = staticmethod(jax.jit(batch._chain_dp_backtrack,
                                static_argnames=("state_axis",)))
    GATHER = staticmethod(jax.jit(gather_walk,
                                  static_argnames=("state_axis",)))

    @pytest.mark.parametrize("L", [1, 2, 7, 11, 52])
    @pytest.mark.parametrize("layout", ["rows", "lanes"])
    def test_walk_matches_gather_walk(self, layout, L):
        """Bit for bit against the gather walk on random valid tables, in
        both solvers' layouts; 600 rows = 3 slots x a ragged B = 200."""
        pa, ps, s_best, order = parent_tables(L, L, 8, 600)
        pa, ps, s_best, axis = laid_out(layout, pa, ps, s_best)
        order = jnp.asarray(order)
        got = self.WALK(pa, ps, s_best, order, state_axis=axis)
        want = self.GATHER(pa, ps, s_best, order, state_axis=axis)
        assert got.shape == (L,) + s_best.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("use_kernel", [False, True])
    @pytest.mark.parametrize("symmetric", [False, True])
    def test_solvers_walk_as_the_gather_walk(self, monkeypatch, use_kernel,
                                             symmetric):
        """On the tables the solvers' own forward scans write — exact ties
        (symmetric swarm) and scenarios with no live UAV (all-infeasible)
        — each solver plans the same with the gather walk swapped in."""
        p = dp_problem(12 + symmetric, B=6, L=7, symmetric=symmetric)
        p["active"] = p["active"].copy()
        p["active"][:2] = False
        args, order = batch._as_dp_args(
            p["compute"], p["memory"], p["act_bits"], p["input_bits"],
            p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
            p["sources"] if use_kernel else p["source"], p["active"], None)
        fn = (batch._chain_dp_solve_kernelized if use_kernel
              else batch._chain_dp_solve).__wrapped__

        def solve():                 # a fresh trace picks up the patch
            return jax.jit(lambda *a: fn(*a, order=order))(*args)

        a0, l0 = solve()
        monkeypatch.setattr(batch, "_chain_dp_backtrack", gather_walk)
        a1, l1 = solve()
        assert (np.asarray(a0)[:2] == -1).all()
        np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))

    @pytest.mark.parametrize("layout", ["rows", "lanes"])
    def test_walk_lowers_without_gather(self, layout):
        """The walk reads its rows as scan slices and picks states by
        select: its lowering holds no gather (the gather walk's does)."""
        pa, ps, s_best, order = parent_tables(0, 11, 8, 600)
        *tables, axis = laid_out(layout, pa, ps, s_best)
        args = (*tables, jnp.asarray(order))
        text = self.WALK.lower(*args, state_axis=axis).as_text()
        assert "gather" not in text
        assert "gather" in self.GATHER.lower(*args, state_axis=axis).as_text()

    def test_kernel_solve_holds_no_row_tables(self):
        """The kernel path walks its parents in the lane layout: no int32
        ``[L, B*M, S+1]`` table is built (L = 7, B = 200, M = 3, S = 5)."""
        p = dp_problem(3, B=200, L=7)
        args, order = batch._as_dp_args(
            p["compute"], p["memory"], p["act_bits"], p["input_bits"],
            p["mem_cap"], p["compute_cap"], p["throughput"], p["rate"],
            p["sources"], p["active"], None)
        text = batch._chain_dp_solve_kernelized.lower(
            *args, order=order).as_text()
        assert "tensor<7x600x6xi32>" not in text
        assert "tensor<7x3x2x128xi32>" in text          # [L, M, Bt, C]


# ---------------------------------------------------------------------------
# engine plumbing: cache keys, retraces, rollout parity
# ---------------------------------------------------------------------------


class TestEngineKernelPath:
    @classmethod
    def _fixture(cls):
        from repro.configs.lenet import LENET
        from repro.core import RadioChannel, cnn_cost, make_devices
        return RadioChannel(PARAMS), make_devices(4), cnn_cost(LENET)

    def test_cache_two_misses_zero_retraces(self):
        """Mixing kernel and jnp engines is exactly 2 cache misses (one
        program each) and re-planning on either is 0 retraces; the flag is
        part of the key, so the two programs never collide."""
        from repro.runtime.scenario_engine import (PlanFnCache,
                                                   ScenarioEngine,
                                                   ScenarioGenerator)
        ch, devs, mc = self._fixture()
        cache = PlanFnCache()
        e0 = ScenarioEngine(ch, devs, mc, plan_cache=cache)
        e1 = ScenarioEngine(ch, devs, mc, plan_cache=cache,
                            use_kernels=True)
        assert e0._cache_key() != e1._cache_key()
        assert (cache.misses, cache.hits) == (2, 0)
        batch = ScenarioGenerator(np.full((4, 2), 30.0) +
                                  np.arange(8).reshape(4, 2),
                                  pos_sigma_m=5.0, seed=3).draw(4)
        p0, p1 = e0.plan_batch(batch), e1.plan_batch(batch)
        np.testing.assert_array_equal(p0.assign, p1.assign)
        np.testing.assert_array_equal(p0.latency, p1.latency)
        np.testing.assert_array_equal(p0.power, p1.power)
        traces = cache.trace_count()
        # same-config engines hit the cache and re-planning never retraces
        ScenarioEngine(ch, devs, mc, plan_cache=cache,
                       use_kernels=True).plan_batch(batch)
        assert cache.hits == 1
        assert cache.trace_count() == traces

    def test_rollout_bitwise_parity(self):
        """A full (B, T) fleet rollout — mobility, failures, battery, the
        multi-source stream — is bitwise identical under use_kernels."""
        from repro.core import RolloutSpec
        from repro.core.positions import hex_init
        from repro.runtime.fleet_rollout import FleetRollout
        from repro.runtime.scenario_engine import PlanFnCache
        ch, devs, mc = self._fixture()
        spec = RolloutSpec(frames=3, requests_per_frame=2,
                           jitter_sigma_m=2.0, failure_prob=0.2,
                           recovery_prob=0.3, battery_j=2e3,
                           hover_watts=0.05, frame_s=1.0)
        cache = PlanFnCache()
        base = hex_init(4, 40.0, jitter=1.0, seed=5)
        kw = dict(plan_cache=cache, seed=13)
        r0 = FleetRollout(ch, devs, mc, spec, **kw).run(
            base, n_trajectories=2)
        r1 = FleetRollout(ch, devs, mc, spec, use_kernels=True, **kw).run(
            base, n_trajectories=2)
        for f in ("latency", "total_power", "feasible", "cap_feasible",
                  "source_latency", "assign", "positions", "active",
                  "charge", "n_requests", "energy_tx", "energy_cmp"):
            np.testing.assert_array_equal(getattr(r0, f), getattr(r1, f),
                                          err_msg=f)


# ---------------------------------------------------------------------------
# resolve_interpret memoization + autotune table
# ---------------------------------------------------------------------------


class TestResolveInterpret:
    def test_backend_memoized_once_per_process(self, monkeypatch):
        """After the first probe the module never asks jax again — the
        per-pallas_call backend query was measurable overhead in the
        per-step kernel launches."""
        kernels._DEFAULT_BACKEND = None
        calls = []
        real = jax.default_backend

        def probe():
            calls.append(1)
            return real()

        monkeypatch.setattr(jax, "default_backend", probe)
        first = default_backend()
        for _ in range(5):
            assert default_backend() == first
            resolve_interpret(None)
        assert len(calls) == 1

    def test_explicit_override_beats_backend(self, monkeypatch):
        """A monkeypatched backend changes the default resolution but an
        explicit interpret= flag always wins."""
        monkeypatch.setattr(kernels, "_DEFAULT_BACKEND", "tpu")
        assert resolve_interpret(None) is False
        assert resolve_interpret(True) is True
        monkeypatch.setattr(kernels, "_DEFAULT_BACKEND", "cpu")
        assert resolve_interpret(None) is True
        assert resolve_interpret(False) is False

    def test_resolved_default_matches_live_backend(self):
        kernels._DEFAULT_BACKEND = None
        assert resolve_interpret(None) is (jax.default_backend() != "tpu")


class TestAutotune:
    def test_divisor_snapping(self):
        assert autotune.divisor_leq(12, 5) == 4
        assert autotune.divisor_leq(12, 6) == 6
        assert autotune.divisor_leq(7, 3) == 1     # prime: whole or 1
        assert autotune.divisor_leq(8, 100) == 8   # clamp to the axis
        assert autotune.divisor_leq(8, 0) == 1
        # TPU tiling: a multiple of the alignment, else the whole axis
        assert autotune.divisor_leq(32, 8, 8) == 8
        assert autotune.divisor_leq(2, 8, 8) == 2
        assert autotune.divisor_leq(100, 8, 8) == 100
        assert autotune.divisor_leq(256, 64, 128) == 256

    def test_lookup_fallback_chain(self, monkeypatch):
        key = ("tropical_dp", "tpu", 32, 32, 32, "float32")
        monkeypatch.setitem(autotune.TABLE, key,
                            {"block_b": 2, "block_m": 8, "block_s": 0})
        exact = autotune.lookup("tropical_dp", U=32, L=32, S=32,
                                dtype="float32", backend="tpu")
        assert exact == autotune.TABLE[key]
        generic = autotune.lookup("tropical_dp", U=999, L=1, S=999,
                                  dtype="float32", backend="tpu")
        assert generic == autotune.TABLE[("tropical_dp", "tpu")]
        assert autotune.lookup("no_such_kernel", U=4, dtype="float32",
                               backend="cpu") == {}

    def test_cpu_rows_request_whole_axes(self):
        """On CPU (interpret mode runs grid cells sequentially) the tuned
        choice is one cell — whole axes — so the kernel body vectorizes
        exactly like the jnp oracle."""
        for kernel in ("tropical_dp", "link_geometry"):
            tuned = autotune.lookup(kernel, U=16, L=8, S=16,
                                    dtype="float32", backend="cpu")
            assert tuned and all(v == 0 for v in tuned.values())
