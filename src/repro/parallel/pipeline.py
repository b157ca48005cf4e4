"""Pipeline-parallel runtime: LLHR-planned stages executed with
shard_map + collective_permute.

This is the TPU materialization of the paper's placement: ``StagePlan``
(from core.pipeline_opt — P3's minmax chain DP + P2's torus assignment)
says which contiguous blocks live on which stage group; this module runs
the resulting pipeline with GPipe-style microbatching:

  for t in range(n_micro + n_stages - 1):          # pipeline schedule
      x = ppermute(x, stage s -> s+1)              # activation hand-off
      x = stage_fn(params_local, x)  if active

Every device holds ONLY its stage's parameters (stage-sharded pytree);
activations move with a single collective_permute per tick — the
one-hop hand-off P2 placed on the torus.  The partition-invariance test
asserts the pipelined forward equals the monolithic forward exactly.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any


def stage_params(params_per_block: Sequence[Pytree],
                 boundaries: Sequence[int]) -> List[Pytree]:
    """Group per-block params into per-stage lists per a StagePlan."""
    out = []
    for a, b in zip(boundaries[:-1], boundaries[1:]):
        out.append(list(params_per_block[a:b]))
    return out


def _stack_stage_params(per_stage: List[Pytree]) -> Pytree:
    """Stack per-stage pytrees along a leading 'stage' axis.

    Stages may hold different block counts; they are right-padded with
    zero-params to the max depth and a per-stage depth vector controls
    how many blocks actually run (padding blocks are skipped)."""
    depth = max(len(s) for s in per_stage)
    padded = []
    for blocks in per_stage:
        blocks = list(blocks)
        while len(blocks) < depth:
            blocks.append(jax.tree.map(jnp.zeros_like, blocks[-1]))
        padded.append(jax.tree.map(lambda *xs: jnp.stack(xs), *blocks))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
    depths = jnp.asarray([len(s) for s in per_stage], jnp.int32)
    return stacked, depths, depth


def pipelined_forward(block_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
                      per_stage_params: List[Pytree],
                      x: jnp.ndarray,
                      mesh: Mesh,
                      axis: str = "stage",
                      n_micro: Optional[int] = None) -> jnp.ndarray:
    """Run ``x`` through the staged blocks with a ppermute pipeline.

    ``block_fn(params, x) -> x`` applies ONE block.  ``x``: [B, ...] with
    B divisible by n_micro.  The mesh must have a ``stage`` axis whose
    size equals len(per_stage_params).
    """
    n_stages = len(per_stage_params)
    n_micro = n_micro or n_stages
    stacked, depths, depth = _stack_stage_params(per_stage_params)
    b = x.shape[0]
    assert b % n_micro == 0
    micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    def stage_fn(params_stk, depths_l, micro_l):
        # params_stk: this stage's stacked blocks [depth, ...] (leading
        # stage dim removed by shard_map); micro_l: all microbatches
        # (replicated over the stage axis).
        sid = jax.lax.axis_index(axis)
        my_depth = depths_l[0]
        buf = jnp.zeros_like(micro_l[0])

        def apply_blocks(x):
            def body(i, x):
                # leading dim 1 = this shard's slice of the stage axis
                p_i = jax.tree.map(lambda a: a[0, i], params_stk)
                return jnp.where(i < my_depth, block_fn(p_i, x), x)
            return jax.lax.fori_loop(0, depth, body, x)

        outs = jnp.zeros_like(micro_l)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t; others take the permuted buf
            feed = jnp.where(t < n_micro, micro_l[jnp.minimum(t, n_micro - 1)],
                             jnp.zeros_like(buf))
            x_in = jnp.where(sid == 0, feed, buf)
            active = (t >= sid) & (t - sid < n_micro)
            y = jnp.where(active, apply_blocks(x_in), x_in)
            # last stage emits microbatch t - (n_stages - 1)
            emit = active & (sid == n_stages - 1)
            k = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            outs = jnp.where(emit,
                             outs.at[k].set(y), outs)
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_micro + n_stages - 1, tick,
                                    (buf, outs))
        # only the last stage holds real outputs; share them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    # replication checking off: the last-stage psum install is not
    # inferable
    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=P(), check_vma=False)
    outs = fn(stacked, depths, micro)
    return outs.reshape(b, *x.shape[1:])
