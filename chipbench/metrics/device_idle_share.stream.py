"""Device: share of the traced window in which no operation ran on the
device, mean over the cell's devices (chipbench/trace.py)."""


def read(run):
    return None if run.trace is None else run.trace["idle_share"]
