"""Logical sharding rules (MaxText-style) + a context-scoped constraint
helper, so model code is mesh-agnostic: ``sc(x, "act_btd")`` is a no-op in
smoke tests and a ``with_sharding_constraint`` under a launch mesh.

Axis vocabulary
  batch axes   -> ("pod", "data")   (pod present only on the multi-pod mesh)
  model axes   -> "model"           (heads / ffn / vocab / experts / kv-seq)

Logical names
  act_btd    activations [batch, seq, d_model]
  act_btf    mlp hiddens [batch, seq, ffn]
  act_bthd   attention   [batch, seq, heads, head_dim]
  act_btv    logits      [batch, seq, vocab]
  kv_bskd    KV cache    [batch, kv_seq, kv_heads, head_dim]  (seq-sharded)
  w_df/w_fd  mlp weights, w_qkv attention weights, w_vd embeddings
  moe_ecd    expert-dispatched tokens [experts, capacity, d]
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


# ---------------------------------------------------------------------------
# 1-D fleet meshes (the rollout's trajectory axis) + topology signatures
# ---------------------------------------------------------------------------

#: Axis name of the 1-D fleet-rollout mesh (the B trajectory axis).
FLEET_AXIS = "traj"


def fleet_mesh(devices: Union[None, int, Sequence, Mesh] = None,
               axis: str = FLEET_AXIS) -> Optional[Mesh]:
    """A 1-D mesh over ``devices`` for batch-axis (trajectory) sharding.

    ``devices`` may be an existing ``Mesh`` (returned unchanged — callers
    can build fancier topologies themselves), an int n (the first n local
    devices; n must not exceed ``jax.device_count()``), an explicit device
    sequence, or None (all local devices).  On CPU, multiple devices exist
    only under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    if isinstance(devices, Mesh):
        return devices
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        avail = jax.devices()
        if devices < 1 or devices > len(avail):
            raise ValueError(
                f"requested a {devices}-device mesh but {len(avail)} "
                f"device(s) are available (on CPU, force more with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devs = avail[:devices]
    else:
        devs = list(devices)
        if not devs:
            raise ValueError("fleet_mesh needs at least one device")
    return Mesh(np.array(devs), (axis,))


def mesh_signature(mesh: Optional[Mesh]) -> Optional[tuple]:
    """Hashable device-topology token for compiled-program cache keys.

    Two programs compiled under different meshes (or one under a mesh and
    one without) are DIFFERENT XLA executables even when every traced op
    matches — the mesh is baked into the lowering.  Cache keys must carry
    this signature so they never collide (``PlanFnCache``)."""
    if mesh is None:
        return None
    devs = tuple(int(d.id) for d in mesh.devices.flat)
    platform = mesh.devices.flat[0].platform
    return ("mesh", mesh.axis_names, tuple(mesh.devices.shape), platform,
            devs)


def pad_to_multiple(n: int, multiple: int) -> int:
    """Smallest padded size >= n divisible by ``multiple`` (shard_map needs
    the sharded axis divisible by the mesh axis size)."""
    if multiple <= 1:
        return n
    return ((n + multiple - 1) // multiple) * multiple


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def default_rules(mesh: Mesh, seq_shard_kv: bool = False,
                  fsdp: bool = True,
                  attn_seq_shard: bool = False,
                  kv_batch_shard: bool = True) -> Dict[str, P]:
    """FSDP(data) x TP(model) rules.

    ``seq_shard_kv``: shard decode KV caches along the sequence dim on the
    model axis (flash-decode layout for long contexts / few KV heads).
    ``attn_seq_shard``: heads don't divide the model axis (e.g. 36 heads on
    a 16-wide axis) — shard attention q-rows on "model" instead (row-
    parallel flash layout; softmax stays fully local).
    """
    b = _batch_axes(mesh)
    bb = b if len(b) > 1 else (b[0] if b else None)
    fs = b[-1] if (fsdp and b) else None    # FSDP shard axis for weights
    kv_b = bb if kv_batch_shard else None
    kv_spec = P(kv_b, "model", None, None) if seq_shard_kv \
        else P(kv_b, None, "model", None)
    # attn_seq_shard: FULL sequence parallelism — heads don't divide the
    # model axis (36/20/24/12/6-head archs on a 16-wide axis), so instead
    # of TP the whole residual stream is row-sharded [B, S("model"), ...]:
    # norms/MLP/projections are rowwise (zero per-layer activation
    # collectives), attention gathers only K/V, weights are FSDP-only
    # (§Perf: 16.5s -> ~2s of collective time on minicpm train_4k).
    bthd = P(bb, "model", None, None) if attn_seq_shard \
        else P(bb, None, "model", None)
    q_chunk = P(bb, "model", None, None) if attn_seq_shard \
        else P(bb, None, "model", None)
    seq = "model" if attn_seq_shard else None
    return {
        # activations
        "act_btd": P(bb, seq, None),
        "act_btf": P(bb, seq, "model" if not attn_seq_shard else None),
        "act_bthd": bthd,
        "attn_q_chunk": q_chunk,           # [B, C, H, D] inside chunk scan
        "act_btv": P(bb, seq, "model" if not attn_seq_shard else None),
        "act_bd": P(bb, None),
        # KV cache [batch, seq, kv_heads, head_dim]
        "kv_bskd": kv_spec,
        # recurrent state [batch, width]
        "state_bw": P(bb, "model"),
        "state_bhij": P(bb, "model", None, None),
        # weights (stacked block weights have a leading layer dim -> None)
        "w_df": P(fs, "model"),
        "w_fd": P("model", fs),
        "w_dd": P(fs, "model"),
        "w_qkv": P(fs, "model", None),      # [d, heads, head_dim]
        "w_o": P("model", None, fs),        # [heads, head_dim, d]
        "w_vd": P("model", fs),             # embedding [vocab, d]
        "w_edf": P("model", fs, None),      # experts [E, d, ff]
        "w_efd": P("model", None, fs),      # experts [E, ff, d]
        "w_bias": P(None),
        "w_scan": P(None),                  # per-layer scalars
        # MoE dispatch buffer [experts, capacity, d]
        "moe_ecd": P("model", bb, None),
        "moe_ted": P(bb, None, None),
    }


@contextmanager
def use_mesh_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, P]] = None,
                   **kw):
    """Activate sharding rules for model code executed in this thread."""
    prev = getattr(_state, "ctx", None)
    if mesh is None:
        _state.ctx = None
    else:
        _state.ctx = (mesh, rules or default_rules(mesh, **kw))
    try:
        yield
    finally:
        _state.ctx = prev


def logical_spec(name: str) -> Optional[P]:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return None
    return ctx[1].get(name)


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_state, "ctx", None)
    return None if ctx is None else ctx[0]


def sc(x, name: str):
    """Constrain ``x`` to the logical sharding ``name`` (no-op w/o mesh)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = rules.get(name)
    if spec is None:
        return x
    # leading stacked-layer dim support: pad spec with None on the left
    nd = x.ndim
    if len(spec) < nd:
        spec = P(*([None] * (nd - len(spec)) + list(spec)))
    elif len(spec) > nd:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def batch_spec(mesh: Mesh) -> P:
    b = _batch_axes(mesh)
    return P(b if len(b) > 1 else (b[0] if b else None))
