"""The ResNet-50 deployment and the two kernel-path cells: the benchmark's
own float64 pricing of residual side paths agrees with the program's cost
model, the check fails when the reference forgets the side paths, and both
cells run end to end on the CPU at a shrunk size."""
import json
from pathlib import Path

import numpy as np
import pytest

from test_bench_chip_check import SEED, _run

CHIP = Path(__file__).resolve().parents[1]
CFG = json.loads((CHIP / "configs" / "resnet50-u8-split.json").read_text())
SMALL_R50 = {"trajectories": 16, "frames": 3, "sample_rows": 8}
SMALL_LENET = {"trajectories": 16, "frames": 6, "sample_rows": 8}


def test_file_holds_the_published_units_and_prices_like_the_program():
    import residual_costs
    from program import make_rollout
    from repro.configs.resnet50 import RESNET50
    names = [ly["name"] for ly in CFG["cnn"]["layers"]]
    assert names == [s.name for s in RESNET50.layers] and len(names) == 52
    want = make_rollout(CFG, use_kernels=False)
    comp, mem, act, inp = residual_costs.cnn_costs(CFG["cnn"])
    for got, ref in ((comp, want.compute), (mem, want.memory),
                     (act, want.act_bits)):
        np.testing.assert_allclose(got, np.asarray(ref, np.float64),
                                   rtol=1e-6)
    assert inp == want.input_bits
    assert comp.sum() == 3_857_973_248.0


@pytest.mark.parametrize("workload,overrides", [
    ("rollout-resnet50-split-kernels", SMALL_R50),
    ("rollout-lenet-split-kernels", SMALL_LENET)],
    ids=["resnet50", "lenet"])
def test_kernel_cells_run_and_are_correct_on_cpu(workload, overrides):
    res = _run(workload, overrides)
    assert res["correct"] is True, res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"rollout_frames_per_s", "setup_s"}


def test_reference_without_side_paths_is_not_correct(monkeypatch):
    # the reference prices ResNet-50 as a plain chain: no skip tensor at
    # the cuts inside a block, no projection
    import reference
    import residual_costs
    monkeypatch.setattr(residual_costs, "cnn_costs", reference.cnn_costs)
    res = _run("rollout-resnet50-split-kernels", SMALL_R50, seed=SEED + 1)
    assert res["correct"] is False, res["check"]
