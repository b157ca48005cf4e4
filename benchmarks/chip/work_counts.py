"""Operations and HBM bytes of one launch of each Pallas kernel of the
planner, computed from its shapes.

Bytes count every operand of the ``pallas_call`` read once and every
result written once, in float32 / int32 (4 bytes an element).  Operations
count the arithmetic the algorithm needs, one per add, multiply, divide,
compare, select, square root or log, on each element it touches.
"""
from __future__ import annotations

WORD = 4


def tropical_dp(B: int, M: int, L: int, S: int) -> dict:
    """One chain-DP wavefront step (``kernels/tropical_dp``):
    dp [B, M, L, S+1], tr [B, L, S, S+1], tr0 [B, M, S], ct and ok
    [L, S] in; row, pa, ps [B, M, S] out.

    Per (row, slot, block start, state): S + 1 adds and S + 1 compares
    for the min-plus product over the predecessor state, then one add for
    the compute time, one select for the mask and one compare for the
    min over block starts."""
    bytes_in = (B * M * L * (S + 1) + B * L * S * (S + 1) + B * M * S
                + 2 * L * S) * WORD
    bytes_out = 3 * B * M * S * WORD
    ops = B * M * L * S * (2 * (S + 1) + 3)
    return {"ops": ops, "bytes": bytes_in + bytes_out}


#: operations per (row, column) element of the fused geometry: two
#: subtracts, two squares, an add and a root for the distance; the 1 m
#: clamp and its square; the eq. (7) scale; the diagonal select; the
#: threshold compare, the liveness product and compare and two logical
#: ops; the masked select and the row max; the power clamp and liveness
#: select; the SNR scale, divide, add, log and rate scale; two selects
OPS_PER_LINK = 26


def link_geometry(B: int, U: int, gain: bool = False) -> dict:
    """One fused link-geometry launch (``kernels/link_geometry``): x, y and
    liveness [B, U] in twice (row tiles and whole rows), an optional gain
    [B, U, U]; distance, threshold and rate [B, U, U] out.  B is the
    padded scenario count the launch runs."""
    bytes_in = 6 * B * U * WORD + (B * U * U * WORD if gain else 0)
    bytes_out = 3 * B * U * U * WORD
    return {"ops": OPS_PER_LINK * B * U * U, "bytes": bytes_in + bytes_out}


def least_time_s(work: dict, peak: dict) -> tuple:
    """(seconds, binding bound) of the least time one launch can take at
    the chip's peaks."""
    t_ops = work["ops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def roofline(summary, ctx, names, work):
    """A kernel's share of its roofline, in %, from a reduced trace.

    Its launches are the device operations whose HLO instruction is named
    after the kernel's jitted entry (``tropical_dp_step.8``: the name
    before the numeric suffix is one of ``names``).  The share is the least time those launches could take at
    the chip's peaks (``work``: one launch's operations and bytes) over
    their summed device time; the bound that binds is returned beside it.
    None when the trace holds no launch or the chip has no peaks."""
    launches = [(n, s, e) for ops in summary.ops.values()
                for n, s, e in ops if n.rsplit(".", 1)[0] in names]
    if not launches or ctx.get("peak") is None:
        return None
    spent = sum(e - s for _, s, e in launches) / 1e9
    least, bound = least_time_s(work, ctx["peak"])
    return {"value": 100.0 * least * len(launches) / spent, "bound": bound,
            "launches": len(launches)}
