"""Min-plus ("tropical") matmul-with-argmin Pallas kernel for the chain-DP
forward wavefront step.

One step of the ``_chain_dp_solve`` scan relaxes, for every scenario row
and every device state s, the candidates over (block start a, predecessor
state s0):

    row[s]  = min_a [ min_s0 ( dp[a, s0] + tr[a, s, s0] ) + ct[a, s] ]

with first-argmin parent pointers over the lexicographic (a, s0) order —
a min-plus matrix product against the transfer tensor, then a masked
min-plus contraction against the compute-time column.

Layout: the scenario axis B is the wide, independent one, so it sits on
the minor (lane) axes of every operand, split row-major as ``(R, C)``
(``lane_split``: C = 128 lanes and R a sublane count once B > 128).  Both
contractions then run over leading, untiled axes: each (slot, a, s, s0)
term is one elementwise add over whole (8, 128) vregs of scenarios, the
min over s0 and over a are running minima, and no cross-lane reduction,
re-layout or gather remains.  ``ct`` and ``ok`` are shared by every
scenario and live in SMEM as scalars.

The source-slot axis M is a leading axis, innermost in the grid:
multi-source frames (``solve_chain_dp_multisource``) share ONE launch
per step, and the slot-invariant ``tr`` block (its index ignores the
slot) is fetched once per scenario tile.  The a = 0 row of ``tr`` is a
dead placeholder (the oracle overwrites it with the source row); the
kernel reads it with the rest and folds the per-slot source row ``tr0``
in-register instead, which is what keeps ``tr`` slot-invariant.

Memory: compiled launches pin ``dp``, ``tr``, ``tr0`` and the results to
HBM, so that each launch reads every operand once and writes every
result once, at any B.  Unpinned, XLA keeps the solver's whole table and
transfer tensor (about 60 MB at B = 8192) in the v5e's VMEM between
launches, and the benchmark's ``tropical_dp_roofline``, which counts
those bytes as HBM traffic (``work_counts.tropical_dp``), read 127% on
one TPU v5e; pinned it read 76.8%, for a rollout 1.5% slower.

Tie-break parity: ``jnp.argmin`` returns the FIRST minimum.  The running
minima update only on a strict ``<``, first over s0 (from s0 = 0), then
over a (from a = 0), carrying the winning s0 with the winning a — the
first-argmin over the lexicographic (a, s0) order, the scalar solver's
loop order.  The float32 adds are the oracle's, in its order, so the
results are bitwise equal to ``ref.dp_step_ref``, ties and all-``inf``
rows included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.autotune import divisor_leq, lookup

#: Lanes of one vreg: the scenario axis is split into rows of this many.
LANES = 128
#: Double-buffered blocks of one compiled cell stay within this many
#: bytes of VMEM (tiles padded to whole (8, 128) vregs) where the shape
#: allows; tiles shrink until they fit.
_VMEM_BLOCK_BYTES = 12 << 20
#: Scoped-VMEM limit of a launch whose smallest tiles still need more
#: (U = L = 32: one slot's [L, S+1] dp rows and one state's [L, S+1]
#: transfer rows are 4.3 MB each); v5e has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 64 << 20


def lane_split(B: int) -> tuple[int, int]:
    """``(R, C)``: the scenario axis B laid row-major over R rows of C
    lanes.  Up to 128 scenarios fill one row (a full-extent block);
    beyond that rows are 128 lanes wide and, past 8 rows, a whole number
    of (8, 128) vregs.  R * C >= B; the caller pads the rest."""
    if B <= LANES:
        return 1, B
    R = -(-B // LANES)
    return (R if R <= 8 else -(-R // 8) * 8), LANES


def to_lanes(x: jnp.ndarray, fill: float | int = jnp.inf) -> jnp.ndarray:
    """[..., B] -> [..., R, C] (``lane_split``), padded with ``fill``."""
    B = x.shape[-1]
    R, C = lane_split(B)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, R * C - B)]
    return jnp.pad(x, pad, constant_values=fill).reshape(
        x.shape[:-1] + (R, C))


def from_lanes(x: jnp.ndarray, B: int) -> jnp.ndarray:
    """[..., R, C] -> [..., B]: the inverse of ``to_lanes``."""
    return x.reshape(x.shape[:-2] + (-1,))[..., :B]


def _dp_step_kernel(dp_ref, tr_ref, tr0_ref, ct_ref, ok_ref,
                    row_ref, pa_ref, ps_ref, *, n_layers: int,
                    n_states: int, block_s: int):
    """One (scenario tile, state tile, slot tile) cell of the step.

    dp  [bm, L, S+1, rb, C]   current dp rows 0..L-1
    tr  [L, bs, S+1, rb, C]   masked transfer tensor, slot-invariant
    tr0 [bm, bs, rb, C]       per-slot source transfer row (a = 0)
    ct  [L, S] (SMEM)         block compute time, shared by all scenarios
    ok  [L, S] (SMEM)         0/1 feasibility mask (caps + a < b)
    ->  row/pa/ps [bm, bs, rb, C]
    """
    INF = jnp.inf
    s_base = pl.program_id(1) * block_s

    def masked(run, a, s):
        cand = run + ct_ref[a, s_base + s]
        return jnp.where(ok_ref[a, s_base + s] > 0, cand, INF)

    # a = 0: the source row replaces the placeholder; dp[0, 0] is the only
    # finite predecessor there, so the first-argmin parent is s0 = 0
    dp00 = dp_ref[:, 0, 0]
    best, pa, ps = [], [], []
    for s in range(block_s):
        best.append(masked(dp00 + tr0_ref[:, s], 0, s))
        pa.append(jnp.zeros(dp00.shape, jnp.int32))
        ps.append(jnp.zeros(dp00.shape, jnp.int32))

    def block_start(a, carry):
        best, pa, ps = (list(c) for c in carry)
        dpa = [dp_ref[:, a, s0] for s0 in range(n_states + 1)]
        for s in range(block_s):
            # min-plus product over the predecessor state, first-min
            run = dpa[0] + tr_ref[a, s, 0]
            arg = jnp.zeros(run.shape, jnp.int32)
            for s0 in range(1, n_states + 1):
                c = dpa[s0] + tr_ref[a, s, s0]
                lt = c < run
                run = jnp.where(lt, c, run)
                arg = jnp.where(lt, s0, arg)
            # fold the s0-independent compute time and mask, then the
            # outer min-plus contraction over the block start a
            cand = masked(run, a, s)
            lt = cand < best[s]
            best[s] = jnp.where(lt, cand, best[s])
            pa[s] = jnp.where(lt, a, pa[s])
            ps[s] = jnp.where(lt, arg, ps[s])
        return tuple(best), tuple(pa), tuple(ps)

    best, pa, ps = jax.lax.fori_loop(1, n_layers, block_start,
                                     (tuple(best), tuple(pa), tuple(ps)))
    for s in range(block_s):
        row_ref[:, s] = best[s]
        pa_ref[:, s] = pa[s]
        ps_ref[:, s] = ps[s]


def _cell_bytes(bm: int, bs: int, rb: int, C: int, L: int, S: int) -> int:
    """VMEM of one compiled cell's double-buffered blocks, each trailing
    (rb, C) tile padded to whole (8, 128) vregs."""
    tile = -(-rb // 8) * 8 * -(-C // LANES) * LANES * 4
    vregs = bm * L * (S + 1) + L * bs * (S + 1) + 4 * bm * bs
    return 2 * vregs * tile


def _tiles(M: int, R: int, C: int, L: int, S: int, block_b: int,
          block_m: int, block_s: int, interpret: bool):
    """``(rb, bm, bs, vmem_limit)``: lane rows, slots and states per cell
    and the scoped-VMEM limit to ask for (None = the compiler's default).

    Requests (0 = whole axis) snap down to divisors.  Compiled cells take
    whole (8, 128) vregs of scenarios (rows a multiple of 8, or all R)
    and shrink the state tile, then the scenario and slot tiles, until
    their blocks fit ``_VMEM_BLOCK_BYTES``; a cell that still does not
    fit asks for ``_VMEM_LIMIT_BYTES``."""
    rb = divisor_leq(R, max(1, block_b // C) if block_b else R,
                     1 if interpret else 8)
    bm = divisor_leq(M, block_m or M)
    bs = divisor_leq(S, block_s or S)
    if interpret:
        return rb, bm, bs, None
    while _cell_bytes(bm, bs, rb, C, L, S) > _VMEM_BLOCK_BYTES:
        if bs > 1:
            bs = divisor_leq(S, bs - 1)
        elif rb > 8 and divisor_leq(R, rb - 1, 8) < rb:
            rb = divisor_leq(R, rb - 1, 8)
        elif bm > 1:
            bm = divisor_leq(M, bm - 1)
        else:
            return rb, bm, bs, _VMEM_LIMIT_BYTES
    return rb, bm, bs, None


@functools.partial(jax.jit, static_argnames=(
    "block_b", "block_m", "block_s", "interpret"))
def tropical_dp_step(dp: jnp.ndarray, tr: jnp.ndarray, tr0: jnp.ndarray,
                     ct: jnp.ndarray, ok: jnp.ndarray, *,
                     block_b: int | None = None, block_m: int | None = None,
                     block_s: int | None = None,
                     interpret: bool | None = None):
    """One chain-DP wavefront step over every (scenario, source slot),
    scenarios last, split as ``(R, C)`` (``lane_split``):

    dp  [M, L', S+1, R, C] float32 — dp table, L' >= L rows; rows 0..L-1
                                      are read (the caller passes its
                                      whole [L+1]-row table, uncopied)
    tr  [L, S, S+1, R, C]  float32 — masked transfer tensor (a = 0 dead)
    tr0 [M, S, R, C]       float32 — per-slot masked source transfer row
    ct  [L, S]             float32 — block compute time for this step
    ok  [L, S]             float32 — 1.0 where (a, s) is feasible this step

    Returns ``(row, pa, ps)``, each [M, S, R, C] (``pa``/``ps`` int32):
    the new dp row without state column 0 and the first-argmin parent
    pointers.  Block sizes default to the autotune table
    (``kernels.autotune``): ``block_b`` scenarios, ``block_m`` slots and
    ``block_s`` states per cell, fitted to VMEM by ``_tiles``.  The grid
    runs the slot axis innermost, so the slot-invariant ``tr`` block is
    fetched once per (scenario, state) tile.
    """
    interpret = resolve_interpret(interpret)
    M, _, Sp1, R, C = dp.shape
    L, S = tr.shape[:2]
    tuned = lookup("tropical_dp", U=S, L=L, S=S, dtype=str(dp.dtype))
    rb, bm, bs, vmem_limit = _tiles(
        M, R, C, L, S,
        tuned.get("block_b", 0) if block_b is None else block_b,
        tuned.get("block_m", 0) if block_m is None else block_m,
        tuned.get("block_s", 0) if block_s is None else block_s, interpret)
    params = {} if vmem_limit is None else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit)}
    shapes = [((M, S, R, C), dp.dtype), ((M, S, R, C), jnp.int32),
              ((M, S, R, C), jnp.int32)]
    if interpret:
        out_shape = [jax.ShapeDtypeStruct(*sh) for sh in shapes]
    else:
        # operands and results stay in HBM, so that each launch reads and
        # writes every byte once (see the module docstring)
        dp, tr, tr0 = (pltpu.with_memory_space_constraint(x, pltpu.HBM)
                       for x in (dp, tr, tr0))
        out_shape = [pltpu.HBM(*sh) for sh in shapes]
    kernel = functools.partial(_dp_step_kernel, n_layers=L, n_states=S,
                               block_s=bs)
    out_block = pl.BlockSpec((bm, bs, rb, C),
                             lambda ri, si, mi: (mi, si, ri, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(R // rb, S // bs, M // bm),
        in_specs=[
            pl.BlockSpec((bm, L, Sp1, rb, C),
                         lambda ri, si, mi: (mi, 0, 0, ri, 0)),
            pl.BlockSpec((L, bs, Sp1, rb, C),
                         lambda ri, si, mi: (0, si, 0, ri, 0)),
            out_block, smem, smem,
        ],
        out_specs=[out_block] * 3,
        out_shape=out_shape,
        interpret=interpret,
        **params,
    )(dp, tr, tr0, ct, ok)
