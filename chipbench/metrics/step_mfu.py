"""Model step (registry.py, vision/zoo.py): 2 x MACs per image (the
benchmark's count, chipbench/counts.py) x images answered inside the
traced window, over the window x chips x the chip's bf16 peak, in
percent.  The served networks compute in float32, so this is measured
against a peak they cannot reach; it is the whole step's bound on every
kernel's gain."""
from chipbench.counts import network_counts


def read(run):
    tr = run.trace
    if tr is None:
        return None
    macs = [network_counts(n)["macs"] for n in run.cell["config"]["networks"]]
    t0, t1 = tr["host_t0"], tr["host_t1"]
    flops = sum(2 * macs[run.image_net[r["pool"]]] for r in run.rows
                if r["status"] == "ok" and t0 <= r["done"] < t1)
    if flops == 0:
        return None
    return 100.0 * flops / ((t1 - t0) * run.chips * run.peaks["bf16_flops"])
