"""ResNet-50 as a placeable chain: its cost model (eq. (1)-(3) with residual
side paths priced at every cut) and its executable units against the plain
float32 reference (``models/resnet_ref.py``).

The model runs at a 64 x 64 input with the published widths, on seeded
random weights and biases (batch norm folded into the biases)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alexnet import ALEXNET
from repro.configs.lenet import LENET
from repro.configs.registry import CNN_ARCHS, get_arch
from repro.configs.resnet50 import RESNET50, STAGES, resnet50
from repro.core import cnn_cost
from repro.models import resnet_ref
from repro.models.cnn import apply_layers, distributed_forward, init_cnn

HW = 64


def test_resnet50_cost_matches_table_1():
    mc = cnn_cost(RESNET50)
    assert len(mc.layers) == 52 and "resnet50" in CNN_ARCHS
    assert get_arch("resnet50") is RESNET50
    assert mc.total_flops == 3_857_973_248.0          # Table 1: 3.8e9
    assert mc.total_weight_bytes / 4 == 25_530_472.0   # 25.53 M weights
    assert mc.layers[-1].act_bits == 1000 * 32


def _cut(name):
    return next(l for l in cnn_cost(RESNET50).layers if l.name == name)


def test_cuts_inside_a_block_carry_the_side_path():
    # conv4: a 1x1 reduce's 256 x 14 x 14 output crosses with the
    # 1024 x 14 x 14 block input (identity) -- more than the block output
    mid, end = _cut("conv4_2a"), _cut("conv4_2c")
    assert mid.skip_bits == end.act_bits == 1024 * 14 ** 2 * 32
    assert mid.act_bits == (256 + 1024) * 14 ** 2 * 32 > end.act_bits
    assert end.skip_bits == 0
    # conv5_1 downsamples: the projection's 2048 x 7 x 7 output crosses,
    # and its MACs and weights are priced on the block's opening unit
    first = _cut("conv5_1a")
    assert first.skip_bits == 2048 * 7 ** 2 * 32
    assert first.flops == 1024 * 512 * 7 ** 2 + 1024 * 2048 * 7 ** 2
    assert first.weight_bytes == (1024 * 512 + 512 + 1024 * 2048 + 2048) * 4


# cnn_cost's output for the paper's CNNs, from before side paths existed:
# (name, flops, weight bytes, act bits, kind); input bits
LENET_COST = (24576.0, (
    ("conv1", 352800.0, 1824.0, 150528.0, "conv"),
    ("pool1", 0.0, 0.0, 37632.0, "pool"),
    ("conv2", 240000.0, 9664.0, 51200.0, "conv"),
    ("pool2", 0.0, 0.0, 12800.0, "pool"),
    ("fc1", 48000.0, 192480.0, 3840.0, "fc"),
    ("fc2", 10080.0, 40656.0, 2688.0, "fc"),
    ("fc3", 840.0, 3400.0, 320.0, "fc")))
ALEXNET_COST = (1236696.0, (
    ("conv1", 105415200.0, 139776.0, 9292800.0, "conv"),
    ("pool1", 0.0, 0.0, 2239488.0, "pool"),
    ("conv2", 447897600.0, 2458624.0, 5971968.0, "conv"),
    ("pool2", 0.0, 0.0, 1384448.0, "pool"),
    ("conv3", 149520384.0, 3540480.0, 2076672.0, "conv"),
    ("conv4", 224280576.0, 5309952.0, 2076672.0, "conv"),
    ("conv5", 149520384.0, 3539968.0, 1384448.0, "conv"),
    ("pool5", 0.0, 0.0, 294912.0, "pool"),
    ("fc1", 37748736.0, 151011328.0, 131072.0, "fc"),
    ("fc2", 16777216.0, 67125248.0, 131072.0, "fc"),
    ("fc3", 4096000.0, 16388000.0, 32000.0, "fc")))


@pytest.mark.parametrize("cfg,want", [(LENET, LENET_COST),
                                      (ALEXNET, ALEXNET_COST)],
                         ids=["lenet", "alexnet"])
def test_chain_cnn_costs_are_bitwise_unchanged(cfg, want):
    mc = cnn_cost(cfg)
    assert mc.input_bits == want[0]
    got = tuple((l.name, l.flops, l.weight_bytes, l.act_bits, l.kind)
                for l in mc.layers)
    assert got == want[1]
    assert all(l.skip_bits == 0.0 and l.state_bytes == 0.0
               for l in mc.layers)


# ---------------------------------------------------------------------------
# execution against the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small():
    """ResNet-50 at 64 x 64 with seeded weights and nonzero biases."""
    cfg = resnet50(HW)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    for p in params:
        for name in [k for k in p if k.endswith("b")]:
            key, sub = jax.random.split(key)
            p[name] = 0.1 * jax.random.normal(sub, p[name].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, HW, HW, 3))
    return cfg, params, x


def _ref_params(cfg, params):
    """The unit list's weights in the reference's nested form."""
    units = dict(zip((s.name for s in cfg.layers), params))
    stages = []
    for stage, (_, n_blocks, _) in enumerate(STAGES, start=2):
        blocks = []
        for blk in range(1, n_blocks + 1):
            name = f"conv{stage}_{blk}"
            p = {f"conv{i}": (units[name + c]["w"], units[name + c]["b"])
                 for i, c in enumerate("abc", start=1)}
            if "proj_w" in units[name + "a"]:
                p["proj"] = (units[name + "a"]["proj_w"],
                             units[name + "a"]["proj_b"])
            blocks.append(p)
        stages.append(blocks)
    return {"stem": (units["conv1"]["w"], units["conv1"]["b"]),
            "stages": stages,
            "fc": (units["fc"]["w"], units["fc"]["b"])}


def _random_placements(L, n, rng):
    """n contiguous placements: blocks of units on increasing devices."""
    out = []
    for _ in range(n):
        cuts = np.sort(rng.choice(np.arange(1, L), rng.integers(1, 9),
                                  replace=False))
        out.append(np.searchsorted(cuts, np.arange(L), side="right"))
    return out


def test_distributed_forward_matches_the_reference(small):
    """Both paths are float32 convolutions of the same weights, which may
    differ by how XLA fuses each slice: float32 rounding carried through
    52 layers, held to 1e-5 of the largest logit (on the CPU they agree
    bitwise; the same weights and input rounded to bfloat16 miss by
    2.8e-3)."""
    cfg, params, x = small
    with jax.default_matmul_precision("highest"):
        want = np.asarray(resnet_ref.forward(_ref_params(cfg, params), x))
        rng = np.random.default_rng(3)
        for assign in _random_placements(len(cfg.layers), 4, rng):
            got, hops = distributed_forward(cfg, params, x, assign)
            assert hops == len(np.unique(assign)) - 1
            got = np.asarray(got)
            assert got.shape == (2, 1000)
            scale = np.abs(want).max()
            assert np.abs(got - want).max() <= 1e-5 * scale


def test_every_cut_hands_over_what_the_cost_model_prices(small):
    """At every cut j the slice [0, j] hands the next one exactly
    K'_j / 32 float32 elements per image: the activation, plus the side
    path inside a block."""
    cfg, params, x = small
    mc = cnn_cost(cfg)
    carry = x
    for j, (spec, cost) in enumerate(zip(cfg.layers, mc.layers)):
        carry = apply_layers(cfg, params, carry, j, j + 1)
        leaves = jax.tree_util.tree_leaves(carry)
        assert all(a.dtype == jnp.float32 for a in leaves)
        per_image = sum(a.size for a in leaves) // x.shape[0]
        assert per_image * 32 == cost.act_bits, spec.name
        assert (len(leaves) == 2) == (cost.skip_bits > 0), spec.name
