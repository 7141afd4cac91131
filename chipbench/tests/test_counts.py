"""FLOP/byte counts against paper Table 3 and the served kernel calls."""
import json
import os

import pytest

from chipbench import counts, peaks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs")


def _net(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    return cfg, cfg["networks"][0]


@pytest.mark.parametrize("name,macs,params", [
    ("mnv2_fuse_half", 286_963_328, 3_462_056),
    ("mnv3l_fuse_full", 294_011_648, 10_591_920),
])
def test_table3(name, macs, params):
    """The counts equal the repo's count of the same networks, and lie
    within 10% (MACs) and 2% (parameters) of paper Table 3, the bounds
    the repo's own Table 3 test holds."""
    cfg, net = _net(name)
    c = counts.network_counts(net)
    assert c == {"macs": macs, "params": params}
    t3 = cfg["table3"]
    assert abs(c["macs"] / 1e6 - t3["macs_millions"]) \
        / t3["macs_millions"] < 0.10
    assert abs(c["params"] / 1e6 - t3["params_millions"]) \
        / t3["params_millions"] < 0.02


def test_kernel_calls_follow_the_served_path():
    _, v2 = _net("mnv2_fuse_half")
    calls = counts.kernel_totals(v2, 32)
    # 17 fused blocks; 16 expands (the first block has none) + the 1x1 head
    assert calls["fuseconv_fused"]["calls"] == 17
    assert calls["matmul"]["calls"] == 17
    assert "fuse1d" not in calls
    _, v3 = _net("mnv3l_fuse_full")
    calls = counts.kernel_totals(v3, 32)
    se = sum(1 for b in v3["blocks"] if b.get("se"))
    assert calls["fuse1d"]["calls"] == 2 * se
    assert calls["fuseconv_fused"]["calls"] == 15 - se


def test_kernel_flops_cover_the_macs():
    """Every MAC of a FuSe block without SE is in a kernel call: the
    kernels' FLOPs are twice the network's MACs less the stem, the SE
    blocks' banks (fuse1d, counted apart) and the head's dense layers."""
    _, v2 = _net("mnv2_fuse_half")
    tot = counts.kernel_totals(v2, 1)
    stem = 112 * 112 * 32 * 27
    head = 1280 * 1000
    assert tot["fuseconv_fused"]["flops"] + tot["matmul"]["flops"] == \
        2 * (counts.network_counts(v2)["macs"] - stem - head)


def test_least_time_takes_the_slower_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert counts.least_seconds(197e12, 1, p) == pytest.approx(1.0)
    assert counts.least_seconds(1, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
