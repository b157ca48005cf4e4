"""Block-tiled min-plus ("tropical") matmul-with-argmin Pallas kernel for
the chain-DP forward wavefront step.

One step of the ``_chain_dp_solve`` scan relaxes, for every scenario row
and every device state s, the candidates over (block start a, predecessor
state s0):

    row[s]  = min_a [ min_s0 ( dp[a, s0] + tr[a, s, s0] ) + ct[a, s] ]

with first-argmin parent pointers over the lexicographic (a, s0) order —
a min-plus matrix product against the transfer tensor, then a masked
min-plus contraction against the compute-time column.  The jnp oracle
materializes the full [B, L, S, S+1] sum per step; this kernel tiles the
(scenario, source-slot, state) axes across the grid so each cell only
ever holds a [block_b, block_m, L, block_s, S+1] slab — on TPU the tiles
stay VMEM-resident and the full intermediate never exists.

The source-slot axis M is first-class in the grid: multi-source frames
(``solve_chain_dp_multisource``) share ONE kernel launch per step, with
the source-independent transfer tensor ``tr`` fetched once per scenario
tile (its block index ignores the slot axis) and only the per-slot
source row ``tr0`` varying along M.  The a = 0 row of ``tr`` is a dead
placeholder (the oracle overwrites it with the source row); the kernel
instead folds ``tr0`` in-register, which is what keeps ``tr``
slot-invariant and the launch shared.

Tie-break parity: ``jnp.argmin`` returns the FIRST minimum.  The kernel
reproduces it exactly with an iota-compare-min (values equal bitwise to
the oracle's, so the comparisons tie identically), staged s0-first then
a — first-argmin over the lexicographic (a, s0) order, the scalar
solver's loop order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.autotune import divisor_leq, lookup

#: Largest candidate slab a compiled cell may hold: 4 MiB compiles on
#: v5e (U = L = 32, 8 slots), 8 MiB exhausts its default scoped VMEM.
_VMEM_SLAB_BYTES = 4 << 20


def _dp_step_kernel(dp_ref, tr_ref, tr0_ref, ct_ref, ok_ref,
                    row_ref, pa_ref, ps_ref, *, n_layers: int,
                    n_states: int):
    """One (scenario, slot, state) tile of the wavefront step.

    dp  [bb, bm, L, S+1]   current dp rows (table rows 0..L-1)
    tr  [bb, L, bs, S+1]   masked transfer tensor, slot-invariant
    tr0 [bb, bm, bs]       per-slot source transfer row (a = 0)
    ct  [L, bs]            block compute time, shared across scenarios
    ok  [L, bs]            0/1 feasibility mask (caps + a < b)
    ->  row/pa/ps [bb, bm, bs]
    """
    INF = jnp.inf
    dp = dp_ref[...]
    tr = tr_ref[...]
    # min-plus product over the predecessor state, tie-broken first-min
    m = dp[:, :, :, None, :] + tr[:, None, :, :, :]  # [bb,bm,L,bs,S+1]
    mmin = m.min(axis=4)                             # [bb, bm, L, bs]
    i_s0 = jax.lax.broadcasted_iota(jnp.int32, m.shape, 4)
    s0b = jnp.where(m == mmin[..., None], i_s0, n_states + 1).min(axis=4)
    # a = 0: the source row replaces the placeholder; dp[0, 0] is the only
    # finite predecessor there, so the first-argmin parent is s0 = 0
    i_a = jax.lax.broadcasted_iota(jnp.int32, mmin.shape, 2)
    m0 = dp[:, :, 0, 0][..., None] + tr0_ref[...]    # [bb, bm, bs]
    mmin = jnp.where(i_a == 0, m0[:, :, None, :], mmin)
    s0b = jnp.where(i_a == 0, 0, s0b)
    # fold the s0-independent compute-time / feasibility terms, then the
    # outer min-plus contraction over the block start a
    cand = mmin + ct_ref[...][None, None]
    cand = jnp.where(ok_ref[...][None, None] > 0, cand, INF)
    best = cand.min(axis=2)                          # [bb, bm, bs]
    ab = jnp.where(cand == best[:, :, None, :], i_a, n_layers).min(axis=2)
    # gather s0b at the winning a via a one-hot max (TPU-safe gather)
    sel = jnp.where(i_a == ab[:, :, None, :], s0b, 0).max(axis=2)
    row_ref[...] = best
    pa_ref[...] = ab
    ps_ref[...] = sel


@functools.partial(jax.jit, static_argnames=(
    "block_b", "block_m", "block_s", "interpret"))
def tropical_dp_step(dp: jnp.ndarray, tr: jnp.ndarray, tr0: jnp.ndarray,
                     ct: jnp.ndarray, ok: jnp.ndarray, *,
                     block_b: int | None = None, block_m: int | None = None,
                     block_s: int | None = None,
                     interpret: bool | None = None):
    """One chain-DP wavefront step over every (scenario, source slot).

    dp  [B, M, L, S+1] float32 — dp table rows 0..L-1
    tr  [B, L, S, S+1] float32 — masked transfer tensor (a = 0 row dead)
    tr0 [B, M, S]      float32 — per-slot masked source transfer row
    ct  [L, S]         float32 — block compute time for this step
    ok  [L, S]         float32 — 1.0 where (a, s) is feasible this step

    Returns ``(row [B, M, S], pa [B, M, S] int32, ps [B, M, S] int32)``:
    the new dp row (state column 0 excluded — the caller pads it with
    inf) and the first-argmin parent pointers.  Block sizes default to
    the autotune table (``kernels.autotune``); 0/None = whole axis, and
    requests are snapped down to divisors so tiles are never ragged.
    Compiled (non-interpret) launches also snap to Mosaic's tiling: the
    slot block sits on sublanes of the ``tr0``/output blocks (a multiple
    of 8 or all of M) and the state block on lanes (a multiple of 128 or
    all of S).
    """
    interpret = resolve_interpret(interpret)
    B, M, L, Sp1 = dp.shape
    S = Sp1 - 1
    tuned = lookup("tropical_dp", U=S, L=L, S=S, dtype=str(dp.dtype))
    block_b = tuned.get("block_b", 0) if block_b is None else block_b
    block_m = tuned.get("block_m", 0) if block_m is None else block_m
    block_s = tuned.get("block_s", 0) if block_s is None else block_s
    sublane, lane = (1, 1) if interpret else (8, 128)
    bb = divisor_leq(B, block_b or B)
    bm = divisor_leq(M, block_m or M, sublane)
    bs = divisor_leq(S, block_s or S, lane)
    if not interpret:
        # one cell's [bb, bm, L, bs, S+1] candidate slab, S+1 padded to
        # whole lanes, must leave the scoped VMEM room for its temporaries
        slab = bm * L * bs * (-(-Sp1 // lane) * lane) * dp.dtype.itemsize
        while bb > 1 and bb * slab > _VMEM_SLAB_BYTES:
            bb = divisor_leq(B, bb - 1)
    grid = (B // bb, M // bm, S // bs)
    kernel = functools.partial(_dp_step_kernel, n_layers=L, n_states=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bm, L, Sp1), lambda bi, mi, si: (bi, mi, 0, 0)),
            pl.BlockSpec((bb, L, bs, Sp1), lambda bi, mi, si: (bi, 0, si, 0)),
            pl.BlockSpec((bb, bm, bs), lambda bi, mi, si: (bi, mi, si)),
            pl.BlockSpec((L, bs), lambda bi, mi, si: (0, si)),
            pl.BlockSpec((L, bs), lambda bi, mi, si: (0, si)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bm, bs), lambda bi, mi, si: (bi, mi, si)),
            pl.BlockSpec((bb, bm, bs), lambda bi, mi, si: (bi, mi, si)),
            pl.BlockSpec((bb, bm, bs), lambda bi, mi, si: (bi, mi, si)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, S), dp.dtype),
            jax.ShapeDtypeStruct((B, M, S), jnp.int32),
            jax.ShapeDtypeStruct((B, M, S), jnp.int32),
        ],
        interpret=interpret,
    )(dp, tr, tr0, ct, ok)
