"""Find an open-loop cell's knee once, on the chip: the highest offered
rate at which the backlog does not grow over the window.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --levels 20,40,60

One process sets the cell up once, then drives a window at each level in
turn (the mix's ``cameras`` for camera streams, its ``rate`` in
requests/s for Poisson), draining between levels.  The backlog grows
where the median latency of the requests due in the window's last
quarter is more than twice that of its first quarter plus 10 ms, or
where fewer requests were answered than offered.  Prints one JSON line
per level.
"""
from __future__ import annotations

import argparse
import copy
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--levels", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from chipbench import harness, weights
    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    config, mix = cell["config"], cell["mix"]
    params = weights.make(config, args.seed, jax.devices()[0])
    system = harness.build_system(config, mix, params, cell["chips"])
    system.engine.warmup()
    gen = importlib.import_module("chipbench.traffic." + mix["generator"])
    nets = config["networks"]
    for level in [float(v) for v in args.levels.split(",")]:
        m = copy.deepcopy(mix)
        arr = m["arrivals"]
        if arr["kind"] == "cameras":
            arr["cameras"] = int(level)
            offered = level * arr["fps"]
        else:
            arr["rate"] = level
            offered = level
        plan = gen.build(m, len(nets), nets[0]["in_channels"], args.seed,
                         harness.WARM_S + args.seconds)
        w = harness.drive(system, plan, plan.image_net, args.seconds)
        rows = harness.collect(system, w)
        inw = [r for r in rows if r["in_window"]]
        ok = [r for r in inw if r["status"] == "ok"]
        lat = np.array([(r["t_call"] - r["due"]) * 1e3 + r["e2e_ms"]
                        for r in ok])
        due = np.array([r["due"] - w["t0"] for r in ok])
        q = args.seconds / 4
        first = lat[due < q]
        last = lat[due >= 3 * q]
        p50_first = float(np.median(first)) if len(first) else float("nan")
        p50_last = float(np.median(last)) if len(last) else float("nan")
        late = np.array([(r["t_call"] - r["due"]) * 1e3 for r in inw])
        answered_in = sum(1 for r in ok if r["done"] < w["t_end"])
        grows = (len(ok) < len(inw) or not p50_last <= 2 * p50_first + 10)
        print(json.dumps({
            "level": level, "offered_rps": offered,
            "sent_rps": len(inw) / args.seconds,
            "answered_in_window_rps": answered_in / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "p50_first_quarter_ms": p50_first,
            "p50_last_quarter_ms": p50_last,
            "gen_late_p99_ms": float(np.percentile(late, 99)),
            "mean_fill": float(np.mean([r["fill"] for r in ok])),
            "failed": len(inw) - len(ok), "grows": bool(grows)}),
            flush=True)
        time.sleep(0.5)
    system.engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
