"""Kernel ``fuseconv_fused`` (kernels/fused.py): least time of its calls
over its device time in the trace, in percent (chipbench/roofline.py)."""
from chipbench.roofline import share

# its events in a v5e trace are named by their HLO instruction:
# "%fuseconv_fused.<n> = <shape> custom-call(...)"
PATTERN = r"^%fuseconv_fused(\.\d+)? = "


def read(run):
    return share(run, "fuseconv_fused", PATTERN)
