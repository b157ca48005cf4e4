"""Plain float32 reference forward of ResNet-50, written from the bottleneck
formulation of He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, 50-layer column, with option B shortcuts
(a 1x1 projection where a block changes the width or the map, identity
elsewhere) and the downsampling stride on each stage's first 1x1.

No slicing, no cost model, no kernels: one function over nested params

    {"stem": (w, b),
     "stages": [[{"conv1": (w, b), "conv2": (w, b), "conv3": (w, b),
                  "proj": (w, b)  # first block of a stage only
                  }, ...], ...],
     "fc": (w, b)}

with conv weights HWIO and images NHWC.  Departures from the paper: batch
norm is folded into each conv's bias (inference form); no softmax (logits).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: (bottleneck width m, blocks, stride of the stage's first block)
STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _conv(x, wb, stride: int, pad: int) -> jnp.ndarray:
    w, b = wb
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b


def bottleneck(x, p, stride: int) -> jnp.ndarray:
    """[1x1 m, 3x3 m, 1x1 4m] plus the shortcut, then ReLU."""
    shortcut = _conv(x, p["proj"], stride, 0) if "proj" in p else x
    h = jax.nn.relu(_conv(x, p["conv1"], stride, 0))
    h = jax.nn.relu(_conv(h, p["conv2"], 1, 1))
    return jax.nn.relu(_conv(h, p["conv3"], 1, 0) + shortcut)


def forward(params, x: jnp.ndarray) -> jnp.ndarray:
    """Logits [N, classes] of images x [N, H, W, 3]."""
    with jax.default_matmul_precision("highest"):
        h = jax.nn.relu(_conv(x, params["stem"], 2, 3))
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 3, 3, 1), (1, 2, 2, 1),
                                  [(0, 0), (1, 1), (1, 1), (0, 0)])
        for blocks, (_, _, stride) in zip(params["stages"], STAGES):
            for i, p in enumerate(blocks):
                h = bottleneck(h, p, stride if i == 0 else 1)
        h = h.mean(axis=(1, 2))
        w, b = params["fc"]
        return h @ w + b
