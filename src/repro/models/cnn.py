"""The paper's own CNNs (LeNet, AlexNet) and ResNet-50 in JAX, built
directly from the same ``CNNConfig`` layer specs the cost model reads — so
the simulator's placement units correspond 1:1 to executable layers.

``apply_layers`` executes an arbitrary contiguous slice, which is what the
distributed-inference runtime uses: each UAV/device runs its assigned slice
and hands the activation to the next (the partition-invariance test asserts
sliced execution == monolithic execution exactly).  Inside a residual
block the hand-off is the pair ``(x, skip)``: the main activation and the
block's side path (its input, or its projection), which is what
``cost_model.cnn_cost`` prices at that cut.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import CNNConfig, ConvLayerSpec

Params = Dict[str, Any]
#: what crosses a cut: the activation, or (activation, side path) inside a
#: residual block
Carry = Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]


def _conv_out(s: int, k: int, stride: int, pad: int) -> int:
    return (s + 2 * pad - k) // stride + 1


def _conv_w(key, k: int, n_in: int, n_out: int) -> jnp.ndarray:
    return jax.random.truncated_normal(
        key, -2, 2, (k, k, n_in, n_out)) / math.sqrt(n_in * k ** 2)


def init_cnn(key, cfg: CNNConfig) -> List[Params]:
    """One params dict per layer spec (pools get empty dicts); a unit that
    opens a residual block with a projection also holds ``proj_w`` and
    ``proj_b``."""
    params: List[Params] = []
    spatial, channels = cfg.input_hw, cfg.input_channels
    flat: Optional[int] = None
    keys = jax.random.split(key, len(cfg.layers))
    for spec, k in zip(cfg.layers, keys):
        if spec.kind == "conv":
            n_in = spec.in_channels or channels
            p = {"w": _conv_w(k, spec.kernel, n_in, spec.out_channels),
                 "b": jnp.zeros((spec.out_channels,))}
            if spec.residual == "open" and spec.proj_channels:
                p["proj_w"] = _conv_w(jax.random.fold_in(k, 1), 1, n_in,
                                      spec.proj_channels)
                p["proj_b"] = jnp.zeros((spec.proj_channels,))
            params.append(p)
            spatial = _conv_out(spatial, spec.kernel, spec.stride,
                                spec.padding)
            channels = spec.out_channels
        elif spec.kind == "pool":
            params.append({})
            spatial = _conv_out(spatial, spec.kernel, spec.stride,
                                spec.padding)
        else:
            n_in = spec.in_features or (flat if flat is not None
                                        else channels * spatial ** 2)
            w = jax.random.truncated_normal(
                k, -2, 2, (n_in, spec.out_features)) / math.sqrt(n_in)
            params.append({"w": w, "b": jnp.zeros((spec.out_features,))})
            flat = spec.out_features
    return params


def _conv(x, w, stride: int, pad: int) -> jnp.ndarray:
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def apply_layer(spec: ConvLayerSpec, p: Params, x: Carry,
                last_fc: bool) -> Carry:
    """x: NHWC for conv/pool, [B, F] for fc (auto-flattened); inside a
    residual block, the pair (x, skip).  A unit that opens a block keeps
    its input (or projects it) as the side path; the unit that closes it
    adds the side path before its ReLU."""
    skip = None
    if isinstance(x, tuple):
        x, skip = x
    if spec.residual == "open":
        skip = x if not spec.proj_channels else \
            _conv(x, p["proj_w"], spec.stride, 0) + p["proj_b"]
    if spec.kind == "conv":
        y = _conv(x, p["w"], spec.stride, spec.padding) + p["b"]
        if spec.residual == "close":
            y = y + skip
        y = jax.nn.relu(y)
    elif spec.kind == "pool":
        window = dict(window_dimensions=(1, spec.kernel, spec.kernel, 1),
                      window_strides=(1, spec.stride, spec.stride, 1),
                      padding=[(0, 0)] + [(spec.padding,) * 2] * 2
                      + [(0, 0)])
        if spec.pool_op == "avg":
            y = jax.lax.reduce_window(x, 0.0, jax.lax.add, **window) \
                / spec.kernel ** 2
        else:
            y = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, **window)
    else:
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = x @ p["w"] + p["b"]
        y = y if last_fc else jax.nn.relu(y)
    if skip is None or spec.residual == "close":
        return y
    return y, skip


def apply_layers(cfg: CNNConfig, params: Sequence[Params], x: Carry,
                 start: int = 0, stop: Optional[int] = None) -> Carry:
    """Execute layers [start, stop) — a placement slice.  Returns what
    crosses the cut after layer stop - 1."""
    stop = len(cfg.layers) if stop is None else stop
    last_fc_idx = max(i for i, s in enumerate(cfg.layers) if s.kind == "fc")
    for i in range(start, stop):
        x = apply_layer(cfg.layers[i], params[i], x, last_fc=i == last_fc_idx)
    return x


def forward(cfg: CNNConfig, params: Sequence[Params],
            x: jnp.ndarray) -> jnp.ndarray:
    return apply_layers(cfg, params, x)


def distributed_forward(cfg: CNNConfig, params: Sequence[Params],
                        x: jnp.ndarray,
                        assign: Sequence[int]) -> Tuple[jnp.ndarray, int]:
    """Execute the model as the LLHR placement would: one contiguous run
    per device change, counting hand-offs.  Numerically identical to
    ``forward`` by construction (the invariance test asserts it)."""
    transfers = 0
    i = 0
    while i < len(cfg.layers):
        j = i
        while j < len(cfg.layers) and assign[j] == assign[i]:
            j += 1
        x = apply_layers(cfg, params, x, i, j)
        if j < len(cfg.layers):
            transfers += 1
        i = j
    return x, transfers
