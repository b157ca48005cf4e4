"""Plain float64 costs of a CNN with residual side paths, in NumPy.

It imports nothing of the program.  ``reference.cnn_costs`` prices a plain
chain by the paper's eq. (1)-(3); a residual block adds two things, both
read from the deployment file's layer entries:

* the unit that opens a block (``"residual": "open"``) also computes the
  block's 1x1 projection shortcut when it has one (``proj_channels`` wide,
  at the unit's own stride), and pays its MACs and weights (plus bias) by
  eq. (1)/(3);
* every cut from that unit up to the one that closes the block
  (``"residual": "close"``) carries the side path beside the activation:
  the block's input for an identity shortcut, the projection's output
  otherwise.  The side path is relayed along the chain, so a cut's price
  depends on the cut alone.

A pool whose ``pool_op`` is ``"avg"`` costs what a max pool costs: no MACs
and no weights.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from reference import Deployment


def _out(z: int, k: int, stride: int, pad: int) -> int:
    return (z + 2 * pad - k) // stride + 1


def cnn_costs(cnn: dict):
    """(compute MACs [L], weight bytes [L], bits crossing the cut after
    each unit [L], input bits), side paths and projections included."""
    z, c = cnn["input_hw"], cnn["input_channels"]
    wb, ab = cnn.get("weight_bits", 32), cnn.get("act_bits_per_elem", 32)
    comp, mem, act = [], [], []
    skip = 0.0                    # elements of the open side path
    for ly in cnn["layers"]:
        kind, role = ly["kind"], ly.get("residual", "")
        z_in, c_in = z, c
        macs = weights = 0.0
        if kind in ("conv", "pool"):
            z = _out(z, ly["kernel"], ly.get("stride", 1),
                     ly.get("padding", 0))
            if kind == "conv":
                k, n = ly["kernel"], ly["out_channels"]
                macs = float(c_in) * k * k * n * z * z
                weights = float(c_in) * k * k * n + n
                c = n
            out = float(c) * z * z
        elif kind == "fc":
            n_prev, n = ly["in_features"], ly["out_features"]
            macs = float(n_prev) * n
            weights = float(n_prev) * n + n
            out = float(n)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        if role == "open":
            p = ly.get("proj_channels", 0)
            if p:
                zp = _out(z_in, 1, ly.get("stride", 1), 0)
                macs += float(c_in) * p * zp * zp
                weights += float(c_in) * p + p
                skip = float(p) * zp * zp
            else:
                skip = float(c_in) * z_in * z_in
        elif role == "close":
            skip = 0.0
        comp.append(macs)
        mem.append(weights * wb / 8.0)
        act.append((out + skip) * ab)
    input_bits = float(cnn["input_hw"] ** 2 * cnn["input_channels"] * 8)
    return np.array(comp), np.array(mem), np.array(act), input_bits


def deployment(cfg: dict) -> Deployment:
    """The reference's ``Deployment`` of ``cfg`` with the CNN priced here."""
    comp, mem, act, inp = cnn_costs(cfg["cnn"])
    return dataclasses.replace(Deployment.from_config(cfg), compute=comp,
                               memory=mem, act_bits=act, input_bits=inp)


__all__ = ["cnn_costs", "deployment"]
