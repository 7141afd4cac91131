"""Chip smoke: serve the zoo's MobileNets at 224px on a TPU through the
compiled Pallas kernels, and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-chip data mesh only

One chip (the default), through the path a user calls —
``ModelRegistry`` -> pipelined ``VisionServeEngine`` ->
``zoo.apply_network`` on the ``pallas_tpu`` backend -> ``kernels/``:

1. refuse to run unless JAX's first device is a TPU;
2. register three published configurations at full width (224px, width
   1.0, 1000 classes, weights seeded by ``--seed``):
   ``mobilenet_v2/depthwise`` (the depthwise KxK kernel),
   ``mobilenet_v2/fuse_half`` (the fused FuSeConv kernel) and
   ``mobilenet_v3_large/fuse_full`` (SE blocks: the fuse1d banks and the
   matmul kernel, k=3 and k=5);
3. warm every (model, bucket) entry, printing compile seconds and
   persistent-cache hits and misses per entry, and require a
   ``tpu_custom_call`` (a compiled Pallas kernel) in each compiled entry;
4. serve mixed-size requests — one per model alone (bucket 1), then
   eight per model together (bucket 8) — and require every status to be
   "ok";
5. compare every served logit vector with a float32 reference on the same
   chip — the same params on the ``xla`` backend under
   ``jax.default_matmul_precision("highest")`` — within ``REF_TOL``.

``--chips 4`` runs only the multi-device path: ``make_data_mesh(4)`` with
the adaptive cross-model round planner and the same models; it checks
that rounds spread over more than one device group, that bucket-8
batches shard over ``"data"``, and that every served logit vector agrees
with the same model run on one device.

The persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
where that is set, else in ``<checkout>/.jax_cache``.  The last line of
standard output is ``{"ok": true, "device": {...}}``; any failed phase
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

MODELS = ("mobilenet_v2/depthwise", "mobilenet_v2/fuse_half",
          "mobilenet_v3_large/fuse_full")
BUCKETS = (1, 8)
# The engine holds a batch smaller than the largest bucket this long for
# more requests: the chip drains a lone request before the next one is
# submitted, so without it a burst would be served one request at a time.
BATCH_WINDOW_MS = 250.0

# Largest allowed error of a served logit vector against the fp32
# reference, relative to the reference's largest |logit|.  The serving
# path computes in fp32 (kernels contract at fp32, the registry traces at
# "highest"), so the two differ by summation order and the TPU's multi-pass
# fp32 emulation: about 1e-6 relative per operation.  The seeded networks
# amplify a 1e-6 relative weight perturbation to at most 1.4e-5 (V2) and
# 5.3e-4 (V3-large) of their largest logit (XLA on CPU, 4 images each).
# 2e-3 sits above that, and below what one bf16 rounding of every weight
# does (1.2e-2 on V2, 7e-2 to 0.24 on V3-large), so a path that slipped to
# bf16 contraction fails.
REF_TOL = 2e-3


class SmokeFailure(RuntimeError):
    """A phase found something wrong; the script exits non-zero."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def build_registry(models, *, backend: str, resolution: int = 0, mesh=None,
                   seed: int = 0):
    from repro.launch.serve_vision import build_network
    from repro.serving.vision import ModelRegistry
    from repro.serving.vision.compilecache import DEFAULT_CACHE_DIR
    registry = ModelRegistry(backend=backend, mesh=mesh,
                             compilation_cache_dir=DEFAULT_CACHE_DIR)
    for entry in models:
        name, variant = entry.rsplit("/", 1)
        registry.register(build_network(name, resolution), variant,
                          key=entry, seed=seed)
    return registry


def warm(engine, registry) -> list:
    """Warm every entry; print per-entry compile seconds and persistent
    cache hits/misses."""
    t0 = time.perf_counter()
    entries = engine.warmup()
    stats = registry.compile_stats()
    for e in stats["compile_log"]:
        group = "" if e["devices"] is None else f" devices={e['devices']}"
        log(f"compile {e['key']} bucket={e['bucket']}{group} "
            f"seconds={e['build_ms'] / 1e3:.3f} "
            f"pcache_hits={e['pcache_hits']} "
            f"pcache_misses={e['pcache_misses']}")
    pc = stats["persistent"]
    log(f"warmup entries={len(entries)} "
        f"seconds={time.perf_counter() - t0:.3f} "
        f"cache_dir={stats['cache_dir']} pcache_hits={int(pc['hits'])} "
        f"pcache_misses={int(pc['misses'])}")
    return entries


def check_compiled_kernels(registry, buckets) -> None:
    """Every (model, bucket) entry's compiled program holds Pallas kernels
    compiled by Mosaic: ``tpu_custom_call``, never interpret mode."""
    import jax
    import jax.numpy as jnp
    for key in registry.keys():
        model = registry.get(key)
        for b in buckets:
            x = jax.ShapeDtypeStruct(
                (b, model.resolution, model.resolution,
                 model.net.in_channels), jnp.float32)
            text = registry.apply_fn(key, b).lower(
                model.params, x).compile().as_text()
            n = text.count('custom_call_target="tpu_custom_call"')
            log(f"kernels {key} bucket={b} tpu_custom_call={n}")
            require(n > 0, f"{key} bucket {b}: no compiled Pallas kernel")


def serve(engine, registry, buckets, seed: int):
    """Mixed-size requests: one per model alone (the smallest bucket), then
    a full largest bucket per model submitted together.  Requires every
    status "ok" and both buckets used.  Returns [(item, result)]."""
    from repro.serving.vision import make_mixed_burst
    n_models = len(registry.keys())
    small, big = min(buckets), max(buckets)
    items = make_mixed_burst(registry, n_models * (small + big), seed=seed)
    out = []
    for wave in (items[:n_models * small], items[n_models * small:]):
        rids = [engine.submit(k, img) for k, img in wave]
        by_rid = {r.rid: r for r in engine.flush()}
        out += [(item, by_rid[rid]) for item, rid in zip(wave, rids)]
    bad = [(r.rid, r.model, r.status, r.error) for _, r in out
           if r.status != "ok"]
    require(not bad, f"requests not served: {bad}")
    used = sorted({r.bucket for _, r in out})
    log(f"served {len(out)} requests, all ok; buckets used {used}")
    require({small, big} <= set(used), f"buckets {small} and {big} were "
            f"not both used: {used}")
    return out


def compare(served, registry, reference, tol: float, what: str) -> dict:
    """Per model: the largest error of a served logit vector against
    ``reference(key, fitted images)``, relative to the reference's
    largest |logit|, and the top-1 agreement."""
    import numpy as np
    from repro.serving.vision import fit_image
    by_model: dict = {}
    for (key, img), r in served:
        by_model.setdefault(key, []).append((img, r))
    report = {}
    for key, pairs in sorted(by_model.items()):
        res = registry.get(key).resolution
        x = np.stack([fit_image(np.asarray(img, np.float32), res)
                      for img, _ in pairs])
        ref = np.asarray(reference(key, x))
        got = np.stack([r.logits for _, r in pairs])
        require(got.shape == ref.shape, f"{key}: logits {got.shape} vs "
                f"reference {ref.shape}")
        require(bool(np.all(np.isfinite(got))), f"{key}: non-finite logits")
        err = float(np.max(np.abs(got - ref).max(axis=1)
                           / np.abs(ref).max(axis=1)))
        top1 = int(np.sum(got.argmax(1) == ref.argmax(1)))
        log(f"{what} {key} requests={len(pairs)} max_rel_err={err!r} "
            f"tol={tol} top1_agree={top1}/{len(pairs)}")
        report[key] = {"max_rel_err": err, "top1_agree": top1,
                       "n": len(pairs)}
        require(err <= tol, f"{key}: error {err!r} against the {what} "
                f"exceeds {tol}")
    return report


def xla_reference(registry):
    """fp32 reference: the registry's params on the ``xla`` backend under
    ``jax.default_matmul_precision("highest")``, one jit per model."""
    import jax
    from repro.vision import zoo
    fns = {}
    for key in registry.keys():
        m = registry.get(key)

        def f(params, x, net=m.net, variant=m.variant):
            with jax.default_matmul_precision("highest"):
                return zoo.apply_network(params, net, x, variant,
                                         train=False, backend="xla")[0]
        fns[key] = jax.jit(f)
    return lambda key, x: fns[key](registry.get(key).params, x)


def one_device_reference(mesh_registry, bucket: int):
    """The same models on one device: an unsharded registry of the same
    backend and params, fed in bucket-sized chunks."""
    import numpy as np
    from repro.serving.vision import ModelRegistry
    from repro.serving.vision.compilecache import DEFAULT_CACHE_DIR
    reg = ModelRegistry(backend=mesh_registry.backend,
                        compilation_cache_dir=DEFAULT_CACHE_DIR)
    for key in mesh_registry.keys():
        m = mesh_registry.get(key)
        reg.register(m.net, m.variant, key=key, params=m.params)

    def reference(key, x):
        xp = np.concatenate([x, np.zeros((-len(x) % bucket,) + x.shape[1:],
                                         x.dtype)])
        return np.concatenate([np.asarray(reg.apply(key, xp[i:i + bucket]))
                               for i in range(0, len(xp), bucket)])[:len(x)]
    return reference


def one_chip(models=MODELS, *, backend="pallas_tpu", resolution=0,
             buckets=BUCKETS, seed=0, tol=REF_TOL) -> dict:
    from repro.serving.vision import create_engine
    registry = build_registry(models, backend=backend,
                              resolution=resolution, seed=seed)
    engine = create_engine(registry, "pipelined", buckets=buckets,
                           batch_window_ms=BATCH_WINDOW_MS)
    try:
        warm(engine, registry)
        if backend == "pallas_tpu":
            check_compiled_kernels(registry, buckets)
        served = serve(engine, registry, buckets, seed)
    finally:
        engine.close()
    return compare(served, registry, xla_reference(registry), tol,
                   "fp32 xla reference")


def mesh_chips(n_chips: int, models=MODELS, *, backend="pallas_tpu",
               resolution=0, buckets=BUCKETS, seed=0, tol=REF_TOL) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_data_mesh
    from repro.serving.vision import (LatencyCalibrator, SystolicCostModel,
                                      create_engine)
    require(len(jax.devices()) >= n_chips,
            f"--chips {n_chips} needs {n_chips} devices, JAX sees "
            f"{len(jax.devices())}")
    registry = build_registry(models, backend=backend, resolution=resolution,
                              mesh=make_data_mesh(n_chips), seed=seed)
    engine = create_engine(
        registry, "pipelined", buckets=buckets,
        batch_window_ms=BATCH_WINDOW_MS,
        cost_model=SystolicCostModel(calibrator=LatencyCalibrator(),
                                     n_devices=n_chips,
                                     round_planner="adaptive"))
    try:
        warm(engine, registry)
        served = serve(engine, registry, buckets, seed)
        snap = engine.metrics.snapshot()
    finally:
        engine.close()
    log(f"rounds={snap['rounds']} cross_model_rounds="
        f"{snap['cross_model_rounds']} max_round_groups="
        f"{snap['max_round_groups']} strategies={snap['round_strategies']}")
    require(snap["max_round_groups"] > 1,
            "no round spread over more than one device group")
    widths = sorted({(r.bucket, r.n_devices) for _, r in served})
    log(f"(bucket, shard width) served: {widths}")
    require(any(b == max(buckets) and n > 1 for b, n in widths),
            f"no bucket-{max(buckets)} batch was sharded over devices")
    # a bucket-8 batch on the whole mesh comes back sharded over "data",
    # one row block per device
    key = registry.keys()[0]
    m = registry.get(key)
    b = max(buckets)
    out = registry.apply(key, np.zeros((b, m.resolution, m.resolution,
                                        m.net.in_channels), np.float32))
    spec = out.sharding.spec if isinstance(out.sharding,
                                           NamedSharding) else None
    rows = sorted({s.data.shape[0] for s in out.addressable_shards})
    log(f"bucket {b} on {n_chips} devices: output spec={spec} "
        f"rows per device={rows}")
    require(spec == P("data") and rows == [b // n_chips],
            f"bucket {b} did not shard over 'data': {spec}, {rows}")
    return compare(served, registry, one_device_reference(registry, b),
                   tol, "one-device reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve and check the three models on one chip;"
                         " 4: only the 4-chip data mesh and its one-device"
                         " comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(seed=args.seed)
        else:
            mesh_chips(args.chips, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak_bytes_in_use={stats['peak_bytes_in_use']}")
    log(f"total seconds={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
