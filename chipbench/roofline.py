"""A kernel's share of its roofline in a traced run of a batch cell.

Least time per forward pass: the sum over the kernel's calls (from
``counts.kernel_calls`` at the cell's one bucket) of max(FLOPs / peak,
bytes / HBM bandwidth).  Forward passes in the trace: the kernel's events
over its calls per pass.  Share: least time of those passes over the
kernel's device time in the trace, in percent.  Nothing to read (None)
where the cell has more than one bucket or network, the trace holds no
event of the kernel, or the kernel's events do not come to the calls of
as many passes as ran in the window, to within a part-pass at each edge
and 2% (the path no longer runs the calls the counts describe).
"""
from __future__ import annotations

import re
from typing import Optional

from chipbench.counts import kernel_calls, least_seconds


def kernel_events(trace: dict, pattern: str):
    """(events, seconds) of the device ops whose name matches."""
    rx = re.compile(pattern)
    n = sum(c for k, c in trace["op_count"].items() if rx.search(k))
    s = sum(v for k, v in trace["op_s"].items() if rx.search(k))
    return n, s


def share(run, kernel: str, pattern: str) -> Optional[float]:
    nets = run.cell["config"]["networks"]
    buckets = run.cell["mix"]["buckets"]
    if run.trace is None or len(nets) != 1 or len(buckets) != 1:
        return None
    calls = [c for c in kernel_calls(nets[0], buckets[0]) if c[0] == kernel]
    n_ev, secs = kernel_events(run.trace, pattern)
    if not calls or n_ev == 0 or secs <= 0:
        return None
    passes = n_ev / len(calls)
    # the forward passes in the window: the trace's program executions
    # where it records them, else the batches the host saw answered in
    # it; a part-pass may lie at each edge, and answers lag the device
    n_dev = max(1, len(run.trace["modules"]))
    whole = sum(run.trace["modules"].values())
    if not run.trace["modules"]:
        t0, t1 = run.trace["host_t0"], run.trace["host_t1"]
        whole = sum(1 for r in run.rows if r["status"] == "ok"
                    and t0 <= r["done"] < t1) / buckets[0]
    if abs(passes - whole) > 2 * n_dev + 0.02 * whole:
        return None
    least = sum(least_seconds(f, b, run.peaks) for _, f, b in calls)
    return 100.0 * passes * least / secs
