"""Batcher (batcher.py): padded slots over bucket slots of the batches
that served the window's requests, from each answer's ``bucket`` and
``batch_fill`` (a batch of fill f answers f requests, so each answer
stands for 1/f of its batch)."""


def read(run):
    pad = slots = 0.0
    for r in run.in_window:
        if r["status"] == "ok" and r["fill"] > 0:
            pad += (r["bucket"] - r["fill"]) / r["fill"]
            slots += r["bucket"] / r["fill"]
    return pad / slots if slots else None
