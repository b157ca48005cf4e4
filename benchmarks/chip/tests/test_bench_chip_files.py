"""Every file the benchmark finds by name loads: each configuration,
traffic mix, driver, check and per-layer metric that ``BENCHMARK.json``
names, and the peaks table."""
import json
import re
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads(conf):
    import reference
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["name"] == conf["name"] and NAME.match(conf["name"])
    dep = reference.Deployment.from_config(cfg)
    assert dep.U == cfg["uavs"] and len(dep.compute) == len(
        cfg["cnn"]["layers"])
    assert cfg["precision"] == "float32" and cfg["assumed"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_loads(cell):
    import run_cell
    spec = run_cell.load_cell(cell["name"])
    tr = spec["traffic"]
    assert (CHIP / "drivers" / f"{tr['driver']}.py").is_file()
    limits = json.loads((CHIP / "checks" / f"{tr['driver']}.json")
                        .read_text())["limits"]
    assert limits
    run_cell.load_module(CHIP / "drivers" / f"{tr['driver']}.py").Driver
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    assert cell["chips"] in (1, 4) and tr["devices"] == cell["chips"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads(metric):
    import run_cell
    reader = run_cell.load_module(CHIP / "metrics" / f"{metric['name']}.py")
    assert callable(reader.read)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e


def test_peaks_have_the_v5e():
    peaks = json.loads((CHIP / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
