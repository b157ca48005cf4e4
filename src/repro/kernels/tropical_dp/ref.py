"""Pure-jnp oracle for the tropical-DP wavefront step.

``dp_step_rows`` IS the forward-step body of
``repro.core.batch._chain_dp_solve`` (the two-stage masked min with
``jnp.argmin`` parent pointers), lifted to a (scenario, source slot)
operand layout with scenarios first: the full [B, M, L, S, S+1]
candidate tensor is materialized per call — exactly the intermediate the
Pallas kernel avoids — and the a = 0 placeholder row is replaced by the
per-slot source transfer row the same way the solver's ``tr_src``
override does.  ``dp_step_ref`` takes the kernel's layout (scenarios
last, split ``(R, C)``) and lifts ``dp_step_rows`` by transposes, which
move values without touching them.  The kernel must match it bitwise,
tie-breaks included.
"""
from __future__ import annotations

import jax.numpy as jnp


def dp_step_rows(dp: jnp.ndarray, tr: jnp.ndarray, tr0: jnp.ndarray,
                 ct: jnp.ndarray, ok: jnp.ndarray):
    """Scenarios-first step: dp [B, M, L, S+1], tr [B, L, S, S+1],
    tr0 [B, M, S], ct/ok [L, S] -> (row [B, M, S], pa [B, M, S] int32,
    ps [B, M, S] int32)."""
    INF = jnp.inf
    L = tr.shape[1]
    m1 = dp[:, :, :, None, :] + tr[:, None]          # [B, M, L, S, S+1]
    s0_best = jnp.argmin(m1, 4).astype(jnp.int32)    # [B, M, L, S]
    mmin = m1.min(4)
    # a = 0: the per-slot source row; only dp[0, 0] is finite there, so
    # the first-argmin predecessor is state 0
    a_ix = jnp.arange(L)[None, None, :, None]
    m0 = dp[:, :, 0, 0][..., None] + tr0             # [B, M, S]
    mmin = jnp.where(a_ix == 0, m0[:, :, None, :], mmin)
    s0_best = jnp.where(a_ix == 0, 0, s0_best)
    cand = mmin + ct[None, None]
    cand = jnp.where(ok[None, None] > 0, cand, INF)
    a_best = jnp.argmin(cand, 2).astype(jnp.int32)   # [B, M, S]
    row = cand.min(2)
    ps = jnp.take_along_axis(s0_best, a_best[:, :, None, :], 2)[:, :, 0]
    return row, a_best, ps


def dp_step_ref(dp: jnp.ndarray, tr: jnp.ndarray, tr0: jnp.ndarray,
                ct: jnp.ndarray, ok: jnp.ndarray):
    """Same contract as ``tropical_dp.tropical_dp_step``:

    dp [M, L', S+1, R, C] (rows 0..L-1 read), tr [L, S, S+1, R, C],
    tr0 [M, S, R, C], ct/ok [L, S] -> (row, pa, ps), each [M, S, R, C].
    """
    L = tr.shape[0]
    M, R, C = dp.shape[0], dp.shape[-2], dp.shape[-1]

    def rows_first(x):                       # [..., R, C] -> [R*C, ...]
        return jnp.moveaxis(x.reshape(x.shape[:-2] + (R * C,)), -1, 0)

    out = dp_step_rows(rows_first(dp[:, :L]), rows_first(tr),
                       rows_first(tr0), ct, ok)
    return tuple(jnp.moveaxis(o, 0, -1).reshape(M, -1, R, C) for o in out)
