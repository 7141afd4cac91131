"""A configuration file's networks, as the benchmark reads them.

A network is a list of blocks with their published sizes (see
``chipbench/configs/*.json``).  ``walk`` gives each block's input and
output shapes, which the FLOP/byte counts and the plain reference share.
SAME padding and stride follow the published MobileNet definitions: an
extent ``h`` at stride ``s`` becomes ``ceil(h / s)``.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional


@dataclasses.dataclass(frozen=True)
class Shape:
    h: int
    w: int
    c: int


def out_extent(extent: int, stride: int) -> int:
    return -(-extent // stride)


def se_channels(c: int, ratio: int = 4, divisor: int = 8) -> int:
    """Squeeze width of an SE block: c / ratio rounded to a multiple of
    ``divisor`` (MobileNetV3's ``_make_divisible``)."""
    return max(divisor, int(c / ratio + divisor / 2) // divisor * divisor)


def spatial_out_channels(variant: str, c: int) -> int:
    """Channels a block's spatial stage hands on: FuSe-full gives every
    channel a row and a column filter (2C), the others keep C."""
    return 2 * c if variant == "fuse_full" else c


@dataclasses.dataclass(frozen=True)
class Step:
    """One block with the shapes it sees."""
    block: dict
    index: int
    x: Shape                 # input
    y: Shape                 # output
    mid: Optional[Shape] = None   # expanded / spatial-stage input (MBConv)


def walk(net: dict) -> Iterator[Step]:
    """Blocks of ``net`` (a configuration's network entry) with shapes."""
    h = w = net["resolution"]
    c = net["in_channels"]
    for i, b in enumerate(net["blocks"]):
        x = Shape(h, w, c)
        kind = b["type"]
        if kind in ("stem", "conv"):
            h, w = out_extent(h, b["stride"]), out_extent(w, b["stride"])
            c = b["cout"]
            yield Step(b, i, x, Shape(h, w, c))
        elif kind == "mbconv":
            mid = Shape(h, w, b["exp"])
            h, w = out_extent(h, b["stride"]), out_extent(w, b["stride"])
            c = b["cout"]
            yield Step(b, i, x, Shape(h, w, c), mid)
        elif kind == "head":
            c = b["classes"]
            yield Step(b, i, x, Shape(1, 1, c))
        else:
            raise ValueError(f"unknown block type {kind!r}")

