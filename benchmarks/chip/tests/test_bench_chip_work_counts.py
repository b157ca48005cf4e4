"""Kernel operation and byte counts against counts made by hand."""
import work_counts


def test_tropical_dp_small():
    # B=2, M=1, L=3, S=2: dp 2*1*3*3=18, tr 2*3*2*3=36, tr0 2*1*2=4,
    # ct+ok 2*3*2=12 -> 70 words in; row/pa/ps 3*2*1*2=12 words out
    w = work_counts.tropical_dp(B=2, M=1, L=3, S=2)
    assert w["bytes"] == (70 + 12) * 4
    # per (row, slot, a, s): 2*(S+1)+3 = 9 ops over 2*1*3*2 = 12 points
    assert w["ops"] == 108


def test_link_geometry_small():
    # B=8, U=2: x, y, act twice = 6*16 words in; 3 [8,2,2] out = 96 words
    w = work_counts.link_geometry(B=8, U=2)
    assert w["bytes"] == (96 + 96) * 4
    assert w["ops"] == work_counts.OPS_PER_LINK * 32
    g = work_counts.link_geometry(B=8, U=2, gain=True)
    assert g["bytes"] == w["bytes"] + 32 * 4


def test_least_time_picks_the_binding_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work_counts.least_time_s({"ops": 1e3, "bytes": 1e6}, peak) \
        == (1e-3, "bytes")
    assert work_counts.least_time_s({"ops": 1e10, "bytes": 1e3}, peak) \
        == (1e-2, "ops")
