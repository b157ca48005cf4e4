"""ResNet-50 — He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, 50-layer column, as L = 52 placeable units:
conv1, pool1, the 48 convs of the 16 bottleneck blocks, avgpool and fc.

Each bottleneck block [1x1 m, 3x3 m, 1x1 4m] is three units: the first
opens the block's side path, the last adds it before its ReLU.  The
downsampling stride sits on the first 1x1 of conv3_1, conv4_1 and conv5_1;
the first block of every stage changes the width and carries a 1x1
projection shortcut (option B), priced on the unit that opens the block,
which reads the same input.

Departures from the paper: batch norm is folded into each conv's bias, as
at inference; the global average pool is a pool unit whose window is the
last stage's whole map.
"""
from repro.configs.base import CNNConfig, ConvLayerSpec

#: (bottleneck width m, blocks, stride of the stage's first block)
STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _conv_out(hw: int, k: int, stride: int, pad: int) -> int:
    return (hw + 2 * pad - k) // stride + 1


def resnet50(input_hw: int = 224, n_classes: int = 1000) -> CNNConfig:
    """ResNet-50 at a square ``input_hw`` input (224 as published)."""
    layers = [
        ConvLayerSpec("conv1", "conv", in_channels=3, out_channels=64,
                      kernel=7, stride=2, padding=3),
        ConvLayerSpec("pool1", "pool", kernel=3, stride=2, padding=1),
    ]
    hw = _conv_out(_conv_out(input_hw, 7, 2, 3), 3, 2, 1)
    c_in = 64
    for stage, (m, n_blocks, stride) in enumerate(STAGES, start=2):
        for blk in range(1, n_blocks + 1):
            s = stride if blk == 1 else 1
            name = f"conv{stage}_{blk}"
            layers += [
                ConvLayerSpec(name + "a", "conv", in_channels=c_in,
                              out_channels=m, kernel=1, stride=s,
                              residual="open",
                              proj_channels=4 * m if blk == 1 else 0),
                ConvLayerSpec(name + "b", "conv", in_channels=m,
                              out_channels=m, kernel=3, padding=1),
                ConvLayerSpec(name + "c", "conv", in_channels=m,
                              out_channels=4 * m, kernel=1,
                              residual="close"),
            ]
            hw = _conv_out(hw, 1, s, 0)
            c_in = 4 * m
    layers += [
        ConvLayerSpec("avgpool", "pool", kernel=hw, stride=1,
                      pool_op="avg"),
        ConvLayerSpec("fc", "fc", in_features=c_in, out_features=n_classes),
    ]
    return CNNConfig(name="resnet50", input_hw=input_hw, input_channels=3,
                     layers=tuple(layers))


RESNET50 = resnet50()

CONFIG = RESNET50
