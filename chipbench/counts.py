"""Operations and bytes of the served networks, from a configuration's
own blocks: the MACs and parameters of paper Table 3, and the FLOPs and
least HBM bytes of each Pallas kernel call in one forward pass.

The MAC/parameter arithmetic is a copy of ``repro.vision.counting`` and
``repro.core.layerir`` (the program may change; the yardstick may not).

Kernel calls follow the served path of the ``pallas_tpu`` backend: an
MBConv block's expand 1x1 runs in the ``matmul`` kernel; a FuSe block
without SE runs its spatial banks, BatchNorm, activation and project 1x1
as one ``fuseconv_fused`` call; a FuSe block with SE runs its project in
``matmul``; a 1x1 ConvBN runs in ``matmul``.  FLOPs count 2 per MAC of
the banks and the 1x1 mixes (the affine and activation are left out), and
bytes count every input, weight and output read or written once at 4
bytes (fp32).  Both are what the call needs at least, so a roofline
share built on them can only fall short of 1.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from chipbench.netdef import se_channels, spatial_out_channels, walk

F32 = 4


def _spatial_macs(variant: str, k: int, c: int, oh: int, ow: int) -> int:
    if variant == "depthwise":
        return oh * ow * c * k * k
    if variant == "fuse_half":
        return oh * ow * c * k          # C/2 row + C/2 column filters
    if variant == "fuse_full":
        return 2 * oh * ow * c * k
    raise ValueError(variant)


def _spatial_params(variant: str, k: int, c: int) -> int:
    return {"depthwise": k * k * c, "fuse_half": k * c,
            "fuse_full": 2 * k * c}[variant]


def network_counts(net: dict) -> Dict[str, int]:
    """MACs and parameters (with 2 BatchNorm parameters per channel of
    every conv output, as Table 3 counts them) of one forward pass of one
    image."""
    v = net["variant"]
    macs = params = 0
    for st in walk(net):
        b, x, y = st.block, st.x, st.y
        t = b["type"]
        if t in ("stem", "conv"):
            k = b["kernel"]
            macs += y.h * y.w * y.c * k * k * x.c
            params += k * k * x.c * y.c + 2 * y.c
        elif t == "mbconv":
            e, k = b["exp"], b["kernel"]
            if e != x.c:
                macs += x.h * x.w * x.c * e
                params += x.c * e + 2 * e
            c_sp = spatial_out_channels(v, e)
            macs += _spatial_macs(v, k, e, y.h, y.w)
            params += _spatial_params(v, k, e) + 2 * c_sp
            if b["se"]:
                cr = se_channels(c_sp)
                macs += 2 * c_sp * cr
                params += 2 * c_sp * cr + cr + c_sp
            macs += y.h * y.w * c_sp * y.c
            params += c_sp * y.c + 2 * y.c
        elif t == "head":
            c = x.c
            if b.get("hidden"):
                macs += c * b["hidden"]
                params += c * b["hidden"] + b["hidden"]
                c = b["hidden"]
            macs += c * y.c
            params += c * y.c + y.c
    return {"macs": macs, "params": params}


def _matmul(m: int, k: int, n: int) -> Tuple[str, int, int]:
    return ("matmul", 2 * m * k * n, F32 * (m * k + k * n + m * n))


def kernel_calls(net: dict, batch: int) -> List[Tuple[str, int, int]]:
    """[(kernel, flops, least bytes)] of every Pallas kernel call of one
    forward pass at ``batch`` images, in the order the network runs them
    (the banks of SE blocks run in ``fuse1d``, listed for completeness)."""
    v = net["variant"]
    out: List[Tuple[str, int, int]] = []
    for st in walk(net):
        b, x, y = st.block, st.x, st.y
        t = b["type"]
        if t == "conv" and b["kernel"] == 1:
            out.append(_matmul(batch * x.h * x.w, x.c, y.c))
        if t != "mbconv":
            continue
        e, k = b["exp"], b["kernel"]
        if e != x.c:
            out.append(_matmul(batch * x.h * x.w, x.c, e))
        c_sp = spatial_out_channels(v, e)
        m_out = batch * y.h * y.w
        if v in ("fuse_half", "fuse_full") and not b["se"]:
            flops = (2 * batch * _spatial_macs(v, k, e, y.h, y.w)
                     + 2 * m_out * c_sp * y.c)
            nbytes = F32 * (batch * x.h * x.w * e + m_out * y.c
                            + _spatial_params(v, k, e) + 2 * c_sp
                            + c_sp * y.c)
            out.append(("fuseconv_fused", flops, nbytes))
            continue
        if v in ("fuse_half", "fuse_full"):
            # the row and the column bank, each over its own channels
            for c_b in ((e, e) if v == "fuse_full" else (e // 2, e - e // 2)):
                out.append(("fuse1d", 2 * m_out * c_b * k,
                            F32 * (batch * x.h * x.w * c_b + m_out * c_b
                                   + k * c_b)))
        out.append(_matmul(m_out, c_sp, y.c))
    return out


def kernel_totals(net: dict, batch: int) -> Dict[str, Dict[str, int]]:
    """Per kernel: calls, FLOPs and least bytes of one forward pass."""
    tot: Dict[str, Dict[str, int]] = {}
    for name, flops, nbytes in kernel_calls(net, batch):
        t = tot.setdefault(name, {"calls": 0, "flops": 0, "bytes": 0})
        t["calls"] += 1
        t["flops"] += flops
        t["bytes"] += nbytes
    return tot


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """The least time a call can take on the chip: bound by compute or by
    HBM bandwidth, whichever is slower."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
