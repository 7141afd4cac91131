"""Subprocess child for tests/test_serve_sharded.py.

Virtual devices must exist before jax initializes its backend, and the
parent pytest process has long since initialized jax on the single real
CPU device (tests/conftest.py keeps it that way on purpose) — so the
sharded-serving checks run here, in a fresh process that forces 8 virtual
CPU devices FIRST.  Prints one JSON dict on the last stdout line; the
parent's tests assert on its fields, so one process launch (and one jax
warmup) serves every test.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the shared entry-point environment shim: merges the virtual-device flag
# into XLA_FLAGS (and quiets TF logging) BEFORE anything imports jax
from repro.launch.env import configure  # noqa: E402

configure(host_device_count=8)

import json  # noqa: E402

import numpy as np  # noqa: E402

# fp32 tolerance for one image's logits served in different batch shapes
# (observed differences are below 3e-7 on tiny_net logits of order 1)
FANBACK_TOL = dict(rtol=1e-5, atol=1e-5)


def main() -> None:
    import jax

    from repro.launch.mesh import make_data_mesh
    from repro.serving.vision import (LatencyCalibrator, ModelRegistry,
                                      SystolicCostModel, VisionServeEngine,
                                      fit_image, make_mixed_burst)
    from repro.vision import zoo

    out = {"devices": len(jax.devices())}
    net = zoo.tiny_net(resolution=16, width=8)
    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)

    def unsharded(reg_u, key, x, per_device):
        """The meshless path at the sharded run's per-device batch shape:
        XLA may pick different kernels for different batch sizes, so the
        bitwise comparison holds the batch shape each device sees fixed."""
        return np.concatenate([
            np.asarray(reg_u.apply(key, x[i:i + per_device]))
            for i in range(0, len(x), per_device)])

    # -- operator-level parity: sharded vs unsharded, per backend ----------
    for backend in ("xla", "pallas"):
        reg_s = ModelRegistry(backend=backend, mesh=mesh)
        reg_u = ModelRegistry(backend=backend)
        key = reg_s.register(net, "fuse_full").key
        reg_u.register(net, "fuse_full")
        # bucket 8 shards 1 image/device; bucket 4 does not divide 8 and
        # runs replicated (4 images/device) — both placements must be
        # bitwise-identical to the meshless path at that batch shape
        for bucket, per_device in ((8, 1), (4, 4)):
            x = rng.standard_normal((bucket, 16, 16, 3)).astype(np.float32)
            sharded = np.asarray(reg_s.apply(key, x))
            out[f"parity_{backend}_b{bucket}"] = bool(np.array_equal(
                sharded, unsharded(reg_u, key, x, per_device)))
        # half-mesh device group (the round scheduler's 2-group split):
        # bucket 4 over 4 devices, 1 image/device
        x = rng.standard_normal((4, 16, 16, 3)).astype(np.float32)
        grp = reg_s.devices[:4]
        out[f"parity_{backend}_group4"] = bool(np.array_equal(
            np.asarray(reg_s.apply(key, x, devices=grp)),
            unsharded(reg_u, key, x, 1)))

    # -- engine end-to-end: cross-model rounds, fan-back ordering ----------
    reg = ModelRegistry(backend="xla", mesh=mesh)
    reg.register(net, "depthwise")
    reg.register(net, "fuse_full")
    ref = ModelRegistry(backend="xla")
    ref.register(net, "depthwise")
    ref.register(net, "fuse_full")
    cal = LatencyCalibrator(min_samples=2)
    # "fifo" pins the structural round shape (even split, round-robin) the
    # assertions below rely on; the adaptive planner is exercised
    # separately at the end (its composition choice is measurement-driven
    # and deliberately not pinned)
    engine = VisionServeEngine(
        reg, cost_model=SystolicCostModel(calibrator=cal, n_devices=8,
                                          round_planner="fifo"),
        buckets=(1, 2, 4, 8), max_in_flight=2)
    engine.warmup()
    items = make_mixed_burst(reg, 16, seed=7)
    rids = [engine.submit(k, img) for k, img in items]
    results = engine.flush()
    out["e2e_statuses_ok"] = all(r.status == "ok" for r in results)
    out["e2e_rid_order"] = [r.rid for r in results] == sorted(rids)
    # fan-back: every request's future must carry the logits of ITS OWN
    # image.  The reference runs the image alone (batch 1) while the
    # engine batched it with others, so the comparison is at FANBACK_TOL:
    # XLA may reorder fp32 sums for another batch shape (a few ulps), and
    # another request's image differs by orders of magnitude more
    by_rid = {r.rid: r for r in results}
    fanback = True
    for rid, (k, img) in zip(rids, items):
        x = fit_image(np.asarray(img, np.float32), 16)[None]
        expect = np.asarray(ref.apply(k, x))[0]
        if not np.allclose(by_rid[rid].logits, expect, **FANBACK_TOL):
            fanback = False
    out["e2e_fanback"] = fanback
    snap = engine.metrics.snapshot()
    out["rounds"] = snap["rounds"]
    out["cross_model_rounds"] = snap["cross_model_rounds"]
    out["max_round_groups"] = snap["max_round_groups"]
    out["sharded_results"] = sorted({r.n_devices for r in results})
    # a second burst must reuse compiled entries (no unbounded cache
    # growth from round scheduling) and feed sharded calibration cells
    n_compiled = len(reg.compiled_buckets())
    engine.generate(make_mixed_burst(reg, 16, seed=8))
    out["jit_cache_stable"] = len(reg.compiled_buckets()) == n_compiled
    out["calibration_sharded_cells"] = sorted(
        {label for entry in cal.snapshot().values() if isinstance(entry, dict)
         for label in entry.get("buckets", {}) if "x" in str(label)})
    engine.close()

    # -- adaptive round planner end-to-end on the same mesh ----------------
    # composition choice is measurement-driven (calibrated wall-ms), so we
    # assert the machinery — every request served, strategies recorded,
    # per-request fan-back still right — not which composition won
    cal2 = LatencyCalibrator(min_samples=2)
    adaptive = VisionServeEngine(
        reg, cost_model=SystolicCostModel(calibrator=cal2, n_devices=8,
                                          round_planner="adaptive"),
        buckets=(1, 2, 4, 8), max_in_flight=2)
    adaptive.warmup()
    items2 = make_mixed_burst(reg, 16, seed=11)
    rids2 = [adaptive.submit(k, img) for k, img in items2]
    results2 = {r.rid: r for r in adaptive.flush()}
    ok2 = all(results2[rid].status == "ok" for rid in rids2)
    fanback2 = all(
        np.allclose(results2[rid].logits,
                    np.asarray(ref.apply(k, fit_image(
                        np.asarray(img, np.float32), 16)[None]))[0],
                    **FANBACK_TOL)
        for rid, (k, img) in zip(rids2, items2))
    snap2 = adaptive.metrics.snapshot()
    out["adaptive_ok"] = bool(ok2)
    out["adaptive_fanback"] = bool(fanback2)
    out["adaptive_rounds"] = snap2["rounds"]
    out["adaptive_strategies"] = snap2["round_strategies"]
    out["adaptive_strategy_rounds_match"] = (
        sum(snap2["round_strategies"].values()) == snap2["rounds"])
    adaptive.close()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
