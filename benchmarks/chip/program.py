"""Build the system under test from a deployment file.

The only place the benchmark constructs program objects: the CNN, the
UAVs, the radio and the rollout spec are read from ``configs/<name>.json``
as they are run, and handed to ``repro.runtime.fleet_rollout.FleetRollout``.
"""
from __future__ import annotations

from repro.configs.base import CNNConfig, ConvLayerSpec
from repro.core import PositionSpec, RadioParams, RolloutSpec, cnn_cost
from repro.core.placement import Device
from repro.runtime.fleet_rollout import FleetRollout

#: trace fields the comparison reads, by ``RolloutTrace`` attribute name
TRACE_FIELDS = ("positions", "charge", "latency", "total_power",
                "feasible", "assign", "source_latency", "n_requests",
                "energy_tx", "energy_cmp")


def make_rollout(cfg: dict, use_kernels: bool, plan_cache=None) -> FleetRollout:
    cnn = cfg["cnn"]
    model = cnn_cost(
        CNNConfig(cnn["name"], cnn["input_hw"], cnn["input_channels"],
                  tuple(ConvLayerSpec(**ly) for ly in cnn["layers"]),
                  cnn["weight_bits"]),
        act_bits_per_elem=cnn["act_bits_per_elem"])
    dev, rs = cfg["devices"], cfg["rollout_spec"]
    thr = dev["throughputs_macs_per_s"]
    uavs = [Device(f"uav{i}", dev["mem_bytes"] * dev["mem_frac"],
                   thr[i % len(thr)] * rs["frame_s"], thr[i % len(thr)])
            for i in range(int(cfg["uavs"]))]
    return FleetRollout(RadioParams(**cfg["radio"]), uavs, model,
                        RolloutSpec(**rs),
                        position_spec=PositionSpec(**cfg["position_spec"]),
                        plan_cache=plan_cache, use_kernels=use_kernels)


def trace_rows(trace, rows) -> dict:
    """The compared fields of a ``RolloutTrace`` for trajectory ``rows``."""
    return {k: getattr(trace, k)[rows] for k in TRACE_FIELDS}
