"""Roofline share of the chain-DP wavefront kernel
(``kernels/tropical_dp``): one launch per DP step relaxes all B x M
(trajectory, source slot) rows at [L, S] (block start, state)."""
from work_counts import roofline, tropical_dp

NAMES = ("tropical_dp_step",)


def read(summary, ctx):
    cfg, tr = ctx["cfg"], ctx["traffic"]
    if not tr.get("use_kernels"):
        return None
    U = int(cfg["uavs"])
    M = max(1, min(U, int(cfg["rollout_spec"]["requests_per_frame"])))
    B = int(tr["trajectories"]) // int(tr["devices"])
    work = tropical_dp(B, M, len(cfg["cnn"]["layers"]), U)
    return roofline(summary, ctx, NAMES, work)
