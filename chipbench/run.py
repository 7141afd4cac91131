"""Run one benchmark cell once:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It refuses to run (exit code 3, no result) unless JAX's first device is
a TPU and there are as many as the cell asks for.  The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number the correctness check compared, with its limit.
The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from chipbench import harness
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START, cell=cell)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    w = out["window"]
    print(f"window compiles={w['compiles']} traces={w['traces']} "
          f"requests={out['attempted']} gc_full={w['gc_full']} "
          f"gc_full_s={w['gc_full_s']:.3f} " + " ".join(
              f"{k}={v!r}" for k, v in harness.host_clock_values(out).items()),
          flush=True)
    device = dict(out["device"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        tr = out["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["metrics"] = harness.per_layer_metrics(cell, out)
        result["device"] = device
        result["breakdown"] = tr["breakdown"]
    else:
        result["metrics"] = harness.e2e_metrics(cell, out)
        result["device"] = device
    checks = {k: {"value": c["value"], "limit": c["limit"]}
              for k, c in out["checks"].items()}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
