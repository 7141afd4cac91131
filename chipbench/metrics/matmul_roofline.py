"""Kernel ``matmul`` (kernels/matmul.py), the 1x1 expand, project and
head mixes: least time of its calls over its device time in the trace,
in percent (chipbench/roofline.py)."""
from chipbench.roofline import share

# its events in a v5e trace are named by their HLO instruction:
# "%matmul.<n> = <shape> custom-call(...)"
PATTERN = r"^%matmul(\.\d+)? = "


def read(run):
    return share(run, "matmul", PATTERN)
