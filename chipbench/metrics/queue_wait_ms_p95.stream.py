"""Engine admission and scheduler (engine.py, costmodel.py): 95th
percentile of ``VisionResult.queue_ms`` (submit to dispatch of its batch)
over the window's answered requests, in ms."""
import numpy as np


def read(run):
    q = [r["queue_ms"] for r in run.in_window if r["status"] == "ok"]
    return float(np.percentile(q, 95)) if q else None
