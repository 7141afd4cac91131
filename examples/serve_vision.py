"""Example: async pipelined FuSeConv vision serving with calibrated costs.

Registers two zoo networks (baseline depthwise + FuSe-Full) on the Pallas
backend (interpret mode on CPU) and submits bursts of mixed-size image
requests through the engine's pipelined executor: host-side letterboxing of
batch N+1 overlaps device execution of batch N, every request resolves a
``VisionFuture``, and each completed batch feeds the latency calibrator so
later scheduling/SLO decisions run in calibrated wall-ms instead of raw
ST-OS accelerator-ms.  Every returned logit vector is checked against the
XLA reference path, so this doubles as an end-to-end correctness demo of
the kernels-through-serving stack.

Run:  PYTHONPATH=src python examples/serve_vision.py [--backend xla]
"""
import argparse
import time

import numpy as np

from repro.serving.vision import (LatencyCalibrator, ModelRegistry,
                                  SystolicCostModel, VisionServeEngine,
                                  fit_image, submit_mixed_burst)
from repro.vision import zoo


def reference_logits(model, image: np.ndarray) -> np.ndarray:
    """The XLA reference path for one request (batch of 1, no engine)."""
    x = fit_image(np.asarray(image, np.float32), model.resolution)[None]
    logits, _ = zoo.apply_network(model.params, model.net, x, model.variant,
                                  backend="xla")
    return np.asarray(logits[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="pallas",
                    choices=["xla", "pallas", "pallas_tpu"])
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--bursts", type=int, default=2,
                    help="bursts served; the first also warms the calibrator")
    args = ap.parse_args()

    registry = ModelRegistry(backend=args.backend)
    net = zoo.tiny_net()
    registry.register(net, "depthwise")          # -> "tiny_net/depthwise"
    registry.register(net, "fuse_full")          # -> "tiny_net/fuse_full"

    calibrator = LatencyCalibrator(min_samples=2)
    engine = VisionServeEngine(
        registry, cost_model=SystolicCostModel(calibrator=calibrator),
        buckets=(1, 2, 4), max_in_flight=2)
    t0 = time.perf_counter()
    engine.warmup()
    print(f"warmup (compile {len(registry.compiled_buckets())} "
          f"model x bucket pairs): {time.perf_counter() - t0:.1f}s")

    worst = 0.0
    for burst in range(args.bursts):
        # Mixed-size burst, round-robin across the two models; per-request
        # futures resolve as the pipeline completes batches.
        submitted = submit_mixed_burst(engine, args.requests, seed=burst)
        futures = [(engine.future(rid), key, img)
                   for rid, key, img in submitted]
        print(f"\nburst {burst}: "
              f"{'rid':>3} {'model':28} {'bucket':>6} {'fill':>4} "
              f"{'predicted':>12} {'measured_ms':>11} {'e2e_ms':>8}  check")
        for fut, key, img in futures:
            r = fut.result(timeout=600)
            ref = reference_logits(registry.get(key), img)
            assert r.logits.shape == ref.shape, (r.logits.shape, ref.shape)
            err = float(np.max(np.abs(r.logits - ref)))
            worst = max(worst, err)
            ok = "OK" if np.allclose(r.logits, ref, rtol=1e-4, atol=1e-4) \
                else f"MISMATCH({err:.2e})"
            unit = "cal-ms" if r.calibrated else "acc-ms"
            print(f"{r.rid:>3} {r.model:28} {r.bucket:>6} {r.batch_fill:>4} "
                  f"{r.predicted_ms:>6.2f}{unit} {r.run_ms:>11.2f} "
                  f"{r.e2e_ms:>8.1f}  {ok}")
        engine.flush()

    m = engine.metrics.snapshot()
    print(f"\nthroughput: {m['throughput_ips']:.1f} images/s "
          f"({m['completed']} completed, {m['batches']} batches, "
          f"{m['padded_slots']} padded slots)")
    print(f"pipeline: max_in_flight={m['max_in_flight']} "
          f"(host {m['host_busy_s']:.2f}s busy forming batches)")
    print(f"calibration: {m['calibrated_batches']}/{m['batches']} batches "
          f"scheduled on calibrated wall-ms; |resid| p50="
          f"{m['calibration_abs_resid_ms']['p50_ms']:.2f}ms")
    print("'acc-ms' predictions are the ST-OS systolic cost model (paper "
          "accelerator); 'cal-ms' means the online least-squares fit had "
          "enough observations to quote this host's wall clock instead — "
          "that is what makes SLO admission meaningful off-paper.")
    print(f"max |engine - reference| over all logits: {worst:.2e}")
    for model_key, stats in m["e2e"].items():
        print(f"  {model_key}: e2e p50={stats['p50_ms']:.1f}ms "
              f"p99={stats['p99_ms']:.1f}ms (n={stats['count']})")
    engine.close()


if __name__ == "__main__":
    main()
