"""Read the numbers the correctness check compares, for the program and
for its control, on several seeds in one process:

    python chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed one run of the cell at its own size and load with a short
window, then the control: the plain reference with its contractions in
three bf16 passes (``Precision.HIGH``'s arithmetic) put in the program's
place, on the same images, judged by the same limits and rule as the
program (``control_correct``, which has to come out false).  One JSON
line per seed; the limits in the configuration are set from these
readings (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   t_start=time.perf_counter(), cell=cell,
                                   control=True)
        except harness.BenchError as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "program": {k: c["value"] for k, c in out["checks"].items()},
            "widest_gap": out["widest_gap"],
            "bf16x3_widest_gap": out["bf16x3_widest_gap"],
            "control": {k: c["value"]
                        for k, c in out["control_checks"].items()},
            "control_correct": out["control_correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
