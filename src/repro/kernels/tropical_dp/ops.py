"""Public entry for the tropical-DP wavefront step kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.tropical_dp.ref import dp_step_ref
from repro.kernels.tropical_dp.tropical_dp import tropical_dp_step


def dp_wavefront_step(dp: jnp.ndarray, tr: jnp.ndarray, tr0: jnp.ndarray,
                      ct: jnp.ndarray, ok: jnp.ndarray, *,
                      use_kernel: bool = True,
                      block_b: int | None = None, block_m: int | None = None,
                      block_s: int | None = None,
                      interpret: bool | None = None):
    """One chain-DP wavefront step over every (scenario, source slot).

    One contract for kernel and oracle, scenarios last and split as
    ``(R, C)`` (``tropical_dp.lane_split`` / ``to_lanes``):
    ``dp`` [M, L', S+1, R, C] (rows 0..L-1 read), ``tr`` [L, S, S+1, R, C]
    (a = 0 row dead), ``tr0`` [M, S, R, C], ``ct``/``ok`` [L, S] ->
    (row, pa, ps), each [M, S, R, C].  ``use_kernel`` selects the
    block-tiled Pallas kernel (interpret-mode on CPU via
    ``resolve_interpret``) or the jnp oracle; both are bitwise-identical
    (tested).
    """
    if use_kernel:
        return tropical_dp_step(dp, tr, tr0, ct, ok, block_b=block_b,
                                block_m=block_m, block_s=block_s,
                                interpret=interpret)
    return dp_step_ref(dp, tr, tr0, ct, ok)
