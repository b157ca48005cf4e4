"""Fused link-geometry Pallas kernel: pairwise distance -> eq. (4) gain
-> eq. (7) power threshold -> first-pass P1 power -> eq. (5) rate.

The jnp planner runs four separate [B, U, U] passes
(``pairwise_dist_batched``, ``power_threshold_batched``,
``rate_matrix_batched`` and the ``solve_power_batched`` row
reduction), each a full HBM round trip.
This kernel computes all of them in ONE pass over row tiles of the link
matrix: each grid cell holds the [block_b, block_u] x / y coordinate
planes of its rows against ALL U column coordinates, derives distance,
gain and threshold in registers, reduces the first-pass P1 power
row-locally (the eq. (6) row max over feasible links, clamped to P_max —
power is a per-ROW quantity, so a cell that owns whole rows needs no
cross-cell reduction), and emits the distance, threshold and rate tiles.
The gain matrix is never materialized at all.  Coordinates travel as
separate [B, U] planes rather than a [B, U, 2] array: Mosaic cannot
lay out a reduction over a size-2 minor axis.

Bitwise parity with the jnp oracle (``ref.link_geometry_ref``) holds
because every elementwise op runs in the oracle's exact order and the
row max is exact; the radio constants are the oracle's own Python floats
(``core.batch.link_constants``), so no divide by a constant is left for
XLA to rewrite into a differently rounded multiply.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.batch import link_constants
from repro.core.channel import RadioParams
from repro.kernels import resolve_interpret
from repro.kernels.autotune import divisor_leq, lookup


def _geometry_math(xr, yr, xa, ya, act_r, act_a, gs, eye, *, p_max: float,
                   th_c: float, snr_c: float, rate_c: float):
    """The fused geometry computation on one row slab.

    ``xr``/``yr``/``act_r`` [bb, bu] row coordinates and 0/1 liveness vs
    ``xa``/``ya``/``act_a`` [bb, U] all of them, ``eye`` the [.., bu, U]
    diagonal mask of the slab.  Shared verbatim by the Pallas kernel body
    (tiles) and ``link_geometry_fused`` (whole arrays), so the two
    execution paths are the same traced program; the distance is
    ``pairwise_dist_batched``'s expression.  Liveness stays float and the
    row max keeps its axis because Mosaic cannot reshape a boolean mask
    or a lane reduction's result into the [.., bu, 1] column form.
    """
    dx = xr[:, :, None] - xa[:, None, :]
    dy = yr[:, :, None] - ya[:, None, :]
    dist = jnp.sqrt(dx ** 2 + dy ** 2)               # [bb, bu, U]
    d2 = jnp.maximum(dist, 1.0) ** 2                 # d0 = 1 m clamp
    d2g = d2 if gs is None else d2 / gs              # eq. (4) gain: h0/d2g
    th = d2g * th_c                                  # eq. (7)
    # first-pass P1 (solve_power_batched with links=None), row-local
    th_z = jnp.where(eye, 0.0, th)
    feas = th_z <= p_max                             # diag: th=0 -> True
    pair = act_r[:, :, None] * act_a[:, None, :] > 0
    feas = feas & (pair | eye)
    threshold = jnp.where(feas & ~eye, th_z, 0.0).max(
        -1, keepdims=True)                           # [bb, bu, 1]
    power = jnp.minimum(threshold, p_max)
    power = jnp.where(act_r[:, :, None] > 0, power, 0.0)
    # eq. (5) at the solved powers; 0 on infeasible links, inf diagonal
    snr = power * snr_c
    if gs is not None:
        snr = snr * gs
    rate = jnp.log(1.0 + snr / d2) * rate_c
    rate = jnp.where(feas, rate, 0.0)
    rate = jnp.where(eye, jnp.inf, rate)
    return dist, th, rate


def _link_geometry_kernel(x_row_ref, y_row_ref, act_row_ref, x_all_ref,
                          y_all_ref, act_all_ref, *refs, block_u: int,
                          has_gain: bool, **consts):
    """One [block_b, block_u(rows), U(cols)] tile of the link matrices."""
    if has_gain:
        gs_ref, dist_ref, th_ref, rate_ref = refs
        gs = gs_ref[...]
    else:
        dist_ref, th_ref, rate_ref = refs
        gs = None
    shape = dist_ref.shape
    i_row = pl.program_id(1) * block_u + \
        jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    i_col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    dist, th, rate = _geometry_math(
        x_row_ref[...], y_row_ref[...], x_all_ref[...], y_all_ref[...],
        act_row_ref[...], act_all_ref[...], gs, i_row == i_col, **consts)
    dist_ref[...] = dist
    th_ref[...] = th
    rate_ref[...] = rate


def _radio_constants(params: RadioParams) -> dict:
    th_c, snr_c, rate_c = link_constants(params)
    return dict(p_max=params.p_max_watts, th_c=th_c, snr_c=snr_c,
                rate_c=rate_c)


@functools.partial(jax.jit, static_argnames=("params",))
def link_geometry_fused(positions: jnp.ndarray, active: jnp.ndarray,
                        gain_scale: jnp.ndarray | None, *,
                        params: RadioParams):
    """The kernel body executed directly on whole arrays.

    Backends without native Pallas lowering (CPU today) run ``pallas_call``
    through the interpreter, which round-trips every ref through a padded
    block copy — pure memory-traffic overhead for a kernel whose autotuned
    CPU launch is a single whole-axis grid cell anyway.  This entry runs
    the SAME body (``_geometry_math``) as one jitted program, so it is
    bit-identical to the kernel launch while skipping the copies; the ops
    dispatcher selects it automatically (``fused_link_geometry``).
    """
    U = positions.shape[1]
    x, y = positions[..., 0], positions[..., 1]
    act = active.astype(positions.dtype)
    eye = jnp.eye(U, dtype=bool)[None]
    return _geometry_math(x, y, x, y, act, act, gain_scale, eye,
                          **_radio_constants(params))


@functools.partial(jax.jit, static_argnames=(
    "params", "block_b", "block_u", "interpret"))
def link_geometry(positions: jnp.ndarray, active: jnp.ndarray,
                  gain_scale: jnp.ndarray | None, *, params: RadioParams,
                  block_b: int | None = None, block_u: int | None = None,
                  interpret: bool | None = None):
    """positions [B, U, 2] f32, active [B, U] f32 (0/1), gain_scale
    [B, U, U] f32 or None -> (dist, threshold, rate), each [B, U, U].

    Block sizes default to the autotune table (``kernels.autotune``,
    keyed on (U, dtype, backend)); 0/None = whole axis, snapped down to
    divisors.  Row tiles always span all U columns — the P1 power is a
    row reduction and stays cell-local.  Compiled (non-interpret)
    launches put B on sublanes and U on lanes of the [B, U] planes, so
    the scenario block is a multiple of 8 or all of B (B is padded with
    dead scenarios up to whole blocks when it has no such divisor), and
    the row block a multiple of 128 or all of U.
    """
    interpret = resolve_interpret(interpret)
    B, U, _ = positions.shape
    tuned = lookup("link_geometry", U=U, dtype=str(positions.dtype))
    block_b = tuned.get("block_b", 0) if block_b is None else block_b
    block_u = tuned.get("block_u", 0) if block_u is None else block_u
    sublane, lane = (1, 1) if interpret else (8, 128)
    bb = divisor_leq(B, block_b or B, sublane)
    if bb == B and B > (block_b or B):
        # no aligned divisor: pad instead of one whole-B cell, which
        # would not fit VMEM at large B
        bb = -(-block_b // sublane) * sublane
    Bp = -(-B // bb) * bb
    bu = divisor_leq(U, block_u or U, lane)
    x, y = positions[..., 0], positions[..., 1]
    if Bp != B:
        pad = ((0, Bp - B), (0, 0))
        x, y, active = (jnp.pad(a, pad) for a in (x, y, active))
        if gain_scale is not None:
            gain_scale = jnp.pad(gain_scale, pad + ((0, 0),),
                                 constant_values=1.0)
    kernel = functools.partial(
        _link_geometry_kernel, block_u=bu,
        has_gain=gain_scale is not None, **_radio_constants(params))
    rows = pl.BlockSpec((bb, bu), lambda bi, ui: (bi, ui))
    whole = pl.BlockSpec((bb, U), lambda bi, ui: (bi, 0))
    tile = pl.BlockSpec((bb, bu, U), lambda bi, ui: (bi, ui, 0))
    in_specs = [rows, rows, rows, whole, whole, whole]
    args = [x, y, active, x, y, active]
    if gain_scale is not None:
        in_specs.append(tile)
        args.append(gain_scale)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // bb, U // bu),
        in_specs=in_specs,
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((Bp, U, U), positions.dtype)] * 3,
        interpret=interpret,
    )(*args)
    return tuple(o[:B] for o in out)
