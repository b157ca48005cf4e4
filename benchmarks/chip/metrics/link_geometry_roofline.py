"""Roofline share of the fused link-geometry kernel
(``kernels/link_geometry``): one launch per frame computes the [B, U, U]
distance, threshold and rate matrices."""
from work_counts import roofline, link_geometry

NAMES = ("link_geometry",)


def read(summary, ctx):
    cfg, tr = ctx["cfg"], ctx["traffic"]
    if not tr.get("use_kernels"):
        return None
    B = int(tr["trajectories"]) // int(tr["devices"])
    Bp = -(-B // 8) * 8
    return roofline(summary, ctx, NAMES, link_geometry(Bp, int(cfg["uavs"])))
