"""Load generator (chipbench/traffic): how late each submit ran against
its due time, 99th percentile over the window's requests, in ms by the
host clock.  A starved generator reads high here, not as a fast server."""
import numpy as np


def read(run):
    late = [(r["t_call"] - r["due"]) * 1e3 for r in run.in_window]
    if run.window["closed"] or not late:
        return None
    return float(np.percentile(late, 99))
