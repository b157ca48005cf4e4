"""The numbers that decide ``correct``: a candidate rollout (the program's
output, or the control) against the float64 reference replayed on the
candidate's own placements.

Every number is a worst case over the sample, and each has a limit in
``checks/<driver>.json``:

* ``p2_gap_rel``      largest relative gap between the penalized eq. (9)
                      objective of the candidate's P2 positions and that
                      of the reference's P2 run from the same start (the
                      best-iterate rule picks between near-equal iterates,
                      so positions themselves may differ by a step);
* ``lat_rel``         largest relative error of a requested source's
                      per-request latency (the DP optimum, so a near-tie
                      broken the other way costs nothing) and of the
                      arrival-weighted frame latency; infinite where the
                      candidate and the reference disagree on whether a
                      requested source, or the frame, can be served;
* ``assign_gap_rel``  by how much the candidate's placement is slower than
                      the reference's optimum, both priced at the
                      reference's rates;
* ``power_rel``       largest error of the frame's tightened P1 power, as
                      a share of the sample's largest;
* ``energy_rel``      the same for transmit and compute energy per UAV;
* ``charge_rel``      largest battery error, as a share of the battery.
"""
from __future__ import annotations

import numpy as np


def _rel(a, b):
    """|a - b| / |b| where both are finite and b != 0 (0 elsewhere)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    ok = np.isfinite(a) & np.isfinite(b) & (b != 0)
    out = np.zeros(np.broadcast(a, b).shape)
    out[ok] = np.abs(a[ok] - b[ok]) / np.abs(b[ok])
    return out


def _scaled(a, b):
    """Largest |a - b| over the largest |b|."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = np.abs(b).max() if b.size else 0.0
    return float(np.abs(a - b).max() / scale) if scale > 0 else 0.0


def compare(cand: dict, ref: dict, battery_j: float) -> dict:
    """Worst-case numbers of ``cand`` against ``ref`` (both [N, T, ...]
    trajectory-major; ``ref`` from ``simulate`` with ``forced`` = ``cand``)."""
    n_req = np.asarray(ref["n_requests"])
    if not np.array_equal(np.asarray(cand["n_requests"], np.float64),
                          np.asarray(n_req, np.float64)):
        # the delegation of arrivals is exact; any difference is wrong
        return {"p2_gap_rel": float("inf"), "lat_rel": float("inf"),
                "assign_gap_rel": float("inf"),
                "power_rel": float("inf"), "energy_rel": float("inf"),
                "charge_rel": float("inf")}
    req = n_req > 0
    c_src = np.asarray(cand["source_latency"], np.float64)
    r_src = np.asarray(ref["source_latency"], np.float64)
    c_fin, r_fin = np.isfinite(c_src) & req, np.isfinite(r_src) & req
    c_feas = np.asarray(cand["feasible"], bool)
    r_feas = np.asarray(ref["feasible"], bool)
    mismatch = int((c_fin != r_fin).sum() + (c_feas != r_feas).sum())
    both = c_fin & r_fin
    lat = max(float(_rel(c_src, r_src)[both].max(initial=0.0)),
              float(_rel(cand["latency"], ref["latency"])[
                  c_feas & r_feas].max(initial=0.0)))
    if mismatch:
        lat = float("inf")
    cost = np.asarray(ref["assign_cost"], np.float64)
    gap = _rel(cost, r_src)[both]
    gap = float(gap.max(initial=0.0))
    if (np.isinf(cost) & both).any():
        gap = float("inf")
    feas = c_feas & r_feas
    p2 = float(_rel(ref["p2_obj"], ref["p2_obj_ref"]).max(initial=0.0))
    power = _scaled(np.asarray(cand["total_power"])[feas],
                    np.asarray(ref["total_power"])[feas])
    energy = max(_scaled(np.asarray(cand[k])[feas], np.asarray(ref[k])[feas])
                 for k in ("energy_tx", "energy_cmp"))
    charge = float(np.abs(np.asarray(cand["charge"], np.float64)
                          - np.asarray(ref["charge"], np.float64)).max()
                   / battery_j)
    return {"p2_gap_rel": p2, "lat_rel": lat, "assign_gap_rel": gap,
            "power_rel": power,
            "energy_rel": energy, "charge_rel": charge}


def judge(numbers: dict, limits: dict) -> bool:
    """True when every number is within its limit (a NaN fails)."""
    return all(float(numbers[k]) <= float(limits[k]) for k in limits)


def check_lines(numbers: dict, limits: dict) -> list:
    """One printed line per compared number, beside its limit."""
    return [f"check {k}: {numbers[k]!r} limit {limits[k]!r}"
            for k in limits]
