"""Engine host threads (engine.py, batcher.py): seconds the scheduler
spent forming batches (``ServeMetrics.host_busy_s``, its growth over the
window) over the window."""


def read(run):
    return run.window["host_busy_s"] / run.seconds
