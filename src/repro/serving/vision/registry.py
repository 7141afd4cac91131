"""Model registry: zoo networks x FuSe variants, jit-cached per batch bucket.

A ``RegisteredModel`` bundles everything the engine and cost model need for
one servable entry: the ``NetworkDef``, the spatial-operator variant, the
initialized (or loaded) params, the lowered operator IR (for the systolic
cost model), and the execution backend.  ``ModelRegistry.apply`` dispatches
through a jit cache keyed by ``(model key, batch bucket)`` so every bucket
compiles exactly once and mixed traffic never re-traces.

Sharding: constructed with a ``jax.sharding`` mesh carrying a ``"data"``
axis (see ``repro.launch.mesh.make_data_mesh``), the registry executes each
batch data-parallel over a device group — params replicated over the group
(``NamedSharding(mesh, P())``), the batch axis sharded over ``"data"`` when
the bucket divides the group size, replicated otherwise; the forward pass
runs under ``shard_map`` on each device's rows (or the whole replicated
batch), since the TPU compiler cannot partition a Pallas kernel.  Per
example the results are bitwise-identical to the unsharded path run at
the batch shape each device sees (XLA may choose other kernels, and so
round fp32 sums differently, for another batch size).  The jit cache key
grows to ``(model key, bucket, device-group ids)`` and per-group
parameter placements are cached, so the
round scheduler's handful of power-of-two contiguous groups each compile
exactly once.  Testable on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Restarts: with ``JAX_COMPILATION_CACHE_DIR`` exported (it wins) or
constructed with ``compilation_cache_dir``, the registry points jax's
persistent compilation cache at that directory (persistence floors
zeroed — see ``compilecache.py``) so every jit entry built here is
written to disk and a restarted process deserializes instead of
recompiling.  The registry also accounts for compilation: the first call
of each jit entry is timed into a compile log, persistent-cache hit/miss
deltas (exact, from jax's monitoring events) are attached per entry, and
``compile_stats()`` hands the whole ledger to ``engine.snapshot()`` and
the cold/warm restart CI gate.

All other latencies around this module are wall-clock; beyond the compile
log the registry does no timing.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.layerir import OpSpec
from repro.kernels import backend as kb
from repro.serving.vision.compilecache import (counters_delta,
                                               enable_compilation_cache,
                                               persistent_cache_counters)
from repro.serving.vision.metrics import span
from repro.vision import zoo


@dataclasses.dataclass
class RegisteredModel:
    key: str
    net: zoo.NetworkDef
    variant: Union[str, tuple]
    params: list
    ir: List[OpSpec]
    backend: kb.Backend

    @property
    def resolution(self) -> int:
        return self.net.resolution

    @property
    def num_classes(self) -> int:
        head = self.net.blocks[-1]
        assert isinstance(head, zoo.Head), head
        return head.classes


def default_model_key(net_name: str, variant: Union[str, tuple]) -> str:
    v = variant if isinstance(variant, str) else "hybrid"
    return f"{net_name}/{v}"


def device_groups(devices: Sequence, k: int) -> List[tuple]:
    """Split ``devices`` into ``k`` equal contiguous groups (the round
    scheduler's analogue of assigning independent convolutions to
    independent systolic-array rows)."""
    assert k >= 1 and len(devices) % k == 0, (len(devices), k)
    g = len(devices) // k
    return [tuple(devices[i * g:(i + 1) * g]) for i in range(k)]


def device_groups_sized(devices: Sequence,
                        sizes: Sequence[int]) -> List[tuple]:
    """Split ``devices`` into contiguous groups with explicit per-group
    sizes (the adaptive round planner's uneven splits); ``sizes`` must be
    positive and sum to the device count."""
    assert sum(sizes) == len(devices), (list(sizes), len(devices))
    out: List[tuple] = []
    i = 0
    for s in sizes:
        assert s >= 1, sizes
        out.append(tuple(devices[i:i + s]))
        i += s
    return out


class ModelRegistry:
    """Servable models + the (key, bucket[, device group]) -> jit cache."""

    def __init__(self, backend: Union[str, kb.Backend, None] = None,
                 mesh=None, compilation_cache_dir: Optional[str] = None):
        self.backend = kb.resolve_backend(backend)
        self.mesh = mesh
        if mesh is not None:
            assert "data" in mesh.axis_names, mesh.axis_names
            self.devices: Optional[tuple] = tuple(
                np.asarray(mesh.devices).flatten().tolist())
        else:
            self.devices = None
        # persistent compilation cache: the JAX_COMPILATION_CACHE_DIR
        # environment variable > the given dir > off.  Enabled
        # here, at construction, so every jit entry this registry ever
        # builds is persisted (and restart-replayable)
        self.compilation_cache_dir = enable_compilation_cache(
            compilation_cache_dir)
        self._models: Dict[str, RegisteredModel] = {}
        self._jit: Dict[tuple, Callable] = {}
        self._group_meshes: Dict[Tuple[int, ...], Mesh] = {}
        self._placed_params: Dict[Tuple[str, Tuple[int, ...]], list] = {}
        # per-entry compile log: one record per jit cache entry built by
        # THIS process, with the entry's build wall-ms and the persistent
        # cache hit/miss delta observed while it was built (warm restarts
        # should see hits, cold starts misses).  Written under a lock —
        # warmup, the scheduler, and replanning can all build entries.
        self._compile_lock = threading.Lock()
        self._compile_log: List[Dict] = []
        self._called: set = set()      # cache keys whose first call was logged

    @property
    def n_devices(self) -> int:
        return len(self.devices) if self.devices else 1

    # -- registration -------------------------------------------------------
    def register(self, net: zoo.NetworkDef, variant: Union[str, tuple]
                 = "depthwise", *, key: Optional[str] = None,
                 params: Optional[list] = None, seed: int = 0,
                 backend: Union[str, kb.Backend, None] = None
                 ) -> RegisteredModel:
        k = key or default_model_key(net.name, variant)
        assert k not in self._models, f"duplicate model key {k!r}"
        if params is None:
            params = zoo.init_network(jax.random.PRNGKey(seed), net, variant)
        bk = self.backend if backend is None else kb.resolve_backend(backend)
        model = RegisteredModel(k, net, variant, params,
                                zoo.lower_to_ir(net, variant), bk)
        self._models[k] = model
        return model

    def get(self, key: str) -> RegisteredModel:
        return self._models[key]

    def __contains__(self, key: str) -> bool:
        return key in self._models

    def keys(self) -> List[str]:
        return list(self._models)

    # -- execution ----------------------------------------------------------
    def _build_apply(self, model: RegisteredModel,
                     group: Optional[Mesh] = None,
                     shard: bool = False) -> Callable:
        """jit of the model's forward pass.  On a device group of more
        than one device the pass runs under ``shard_map``: with ``shard``
        each device computes its own rows of the batch, otherwise every
        device computes the whole (replicated) batch.  The TPU compiler
        cannot partition a Pallas kernel by itself ("Mosaic kernels cannot
        be automatically partitioned"), so the placement is spelled out."""
        net, variant, backend = model.net, model.variant, model.backend

        def apply(params, images):
            # serve the f32 network at f32: on TPU the default precision
            # of an f32 conv or dot rounds its operands to bf16, which
            # moves seeded zoo logits by 1-24% of the largest (PERF.md)
            with jax.default_matmul_precision("highest"):
                logits, _ = zoo.apply_network(params, net, images, variant,
                                              train=False, backend=backend)
            return logits

        if group is not None and group.size > 1:
            rows = P("data") if shard else P()
            apply = jax.shard_map(apply, mesh=group, in_specs=(P(), rows),
                                  out_specs=rows, check_vma=False)

        # Donate the batch input: it is dead after the call (the engine
        # pads into a fresh bucket array per round), so XLA may reuse its
        # buffer for the logits — one bucket-sized allocation less per
        # dispatch.  Params are NOT donated (they are the long-lived cached
        # placements).  When shapes prevent reuse XLA warns "Some donated
        # buffers were not usable"; that is expected for odd logit shapes,
        # so it is suppressed here and nowhere else.
        import warnings
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return jax.jit(apply, donate_argnums=(1,))

    def apply_fn(self, key: str, bucket: int) -> Callable:
        """The jitted apply for one (model, batch-bucket) shape class."""
        cache_key = (key, bucket)
        if cache_key not in self._jit:
            self._jit[cache_key] = self._build_apply(self._models[key])
        return self._jit[cache_key]

    def _call_entry(self, cache_key: tuple, fn: Callable, params,
                    x) -> jax.Array:
        """Invoke a jit entry; the FIRST call per cache key is timed and
        logged (tracing + XLA compile happen inside it — with a persistent
        cache hit the same call deserializes from disk instead, and the
        hit/miss delta captured around it records which one happened),
        inside a ``vision.compile`` span."""
        with self._compile_lock:
            fresh = cache_key not in self._called
            if fresh:
                self._called.add(cache_key)
        if not fresh:
            return fn(params, x)
        devices = list(cache_key[2]) if len(cache_key) > 2 else None
        with span("vision.compile", model=cache_key[0], bucket=cache_key[1],
                  devices=" ".join(map(str, devices or ()))) as sp:
            before = persistent_cache_counters()
            t0 = time.perf_counter()
            out = fn(params, x)
            build_ms = (time.perf_counter() - t0) * 1e3
            delta = counters_delta(before)
            sp.set_metadata(pcache_hit=int(delta["hits"] > 0))
        with self._compile_lock:
            self._compile_log.append({
                "entry": cache_key,
                "key": cache_key[0], "bucket": cache_key[1],
                "devices": devices,
                "build_ms": build_ms,
                "pcache_hits": int(delta["hits"]),
                "pcache_misses": int(delta["misses"]),
            })
        return out

    def _group_mesh(self, devices: tuple) -> Mesh:
        ids = tuple(d.id for d in devices)
        if ids not in self._group_meshes:
            self._group_meshes[ids] = Mesh(np.array(list(devices)),
                                           ("data",))
        return self._group_meshes[ids]

    def _params_for(self, key: str, devices: tuple) -> list:
        """Model params replicated over a device group (cached placement)."""
        ids = tuple(d.id for d in devices)
        cache_key = (key, ids)
        if cache_key not in self._placed_params:
            gmesh = self._group_mesh(devices)
            self._placed_params[cache_key] = jax.device_put(
                self._models[key].params, NamedSharding(gmesh, P()))
        return self._placed_params[cache_key]

    def apply(self, key: str, images,
              devices: Optional[Sequence] = None) -> jax.Array:
        """images: (bucket, res, res, C) — must already be bucket-padded.

        ``devices``: the device group to execute on (defaults to the whole
        mesh when one was given at construction, else the legacy
        single-device path).  The batch shards over the group when the
        bucket divides it; otherwise it is replicated (see the module
        docstring for what stays bitwise-identical)."""
        model = self._models[key]
        x = jnp.asarray(images)
        bucket = x.shape[0]
        if devices is None and self.devices is None:
            return self._call_entry((key, bucket),
                                    self.apply_fn(key, bucket),
                                    model.params, x)
        devs = tuple(devices) if devices is not None else self.devices
        gmesh = self._group_mesh(devs)
        ids = tuple(d.id for d in devs)
        shard = len(devs) > 1 and bucket % len(devs) == 0
        x = jax.device_put(x, NamedSharding(gmesh, P("data") if shard
                                            else P()))
        params = self._params_for(key, devs)
        cache_key = (key, bucket, ids)
        if cache_key not in self._jit:
            self._jit[cache_key] = self._build_apply(model, gmesh, shard)
        return self._call_entry(cache_key, self._jit[cache_key], params, x)

    def is_compiled(self, key: str, bucket: int,
                    devices: Optional[Sequence] = None) -> bool:
        """True when ``apply(key, <bucket-sized batch>, devices=...)``
        would hit an already-built jit entry — the executor's mid-flight
        replanner only backfills idle groups with warm entries, so a
        replan dispatch never compiles under traffic."""
        devs = tuple(devices) if devices is not None else self.devices
        if devs is None:
            return (key, bucket) in self._jit
        return (key, bucket, tuple(d.id for d in devs)) in self._jit

    def prewarm(self, key: str, buckets, *, host: bool = True,
                device: bool = True,
                groups: Optional[Sequence[Sequence]] = None) -> None:
        """Warm the serving pipeline's stages off the hot path.

        device: trace + compile one jitted apply per (model, bucket) and run
        it once, so the device stage never compiles under traffic.  Under a
        mesh this warms the full-mesh placement; pass ``groups`` (tuples of
        devices) to additionally warm the round scheduler's device groups.
        host: exercise the batch-formation path (letterbox + stack + bucket
        pad) per bucket, so first-request host latency doesn't pay numpy
        allocator / import warmup either.
        """
        model = self._models[key]
        res, cin = model.resolution, model.net.in_channels
        if host:
            from repro.serving.vision.batcher import (VisionRequest,
                                                      form_batch)
            img = np.zeros((res // 2 or 1, res + 1, cin), np.float32)
            for b in buckets:
                form_batch([VisionRequest(-1, key, img, 0.0)], b, res)
        if device:
            targets = [None] + [tuple(g) for g in (groups or [])]
            for devs in targets:
                for b in buckets:
                    self.warm_entry(key, b, devices=devs, host=False)

    def warm_entry(self, key: str, bucket: int,
                   devices: Optional[Sequence] = None, *,
                   host: bool = True) -> None:
        """Warm exactly ONE (model, bucket[, device group]) jit entry: run
        the bucket-shaped apply once and block.  With the persistent
        compilation cache enabled this either compiles-and-persists (cold)
        or deserializes from disk (warm) — either way the entry is hot for
        traffic afterwards.  ``host=True`` also exercises batch formation
        for the bucket (the manifest replay path warms per entry, so the
        host side must ride along)."""
        model = self._models[key]
        res, cin = model.resolution, model.net.in_channels
        if host:
            from repro.serving.vision.batcher import (VisionRequest,
                                                      form_batch)
            img = np.zeros((res // 2 or 1, res + 1, cin), np.float32)
            form_batch([VisionRequest(-1, key, img, 0.0)], bucket, res)
        out = self.apply(key, np.zeros((bucket, res, res, cin), np.float32),
                         devices=tuple(devices) if devices else None)
        jax.block_until_ready(out)

    def devices_by_id(self, ids: Sequence[int]) -> Optional[tuple]:
        """Map persisted device ids back to this process's device objects
        (manifest entries store ids — device objects don't survive a
        restart).  None when any id is not on the current mesh."""
        pool = {d.id: d for d in (self.devices or ())}
        try:
            return tuple(pool[i] for i in ids)
        except KeyError:
            return None

    def backend_fingerprint(self) -> str:
        """Stable hash of everything that invalidates persisted compile
        work: jax/jaxlib versions, platform, backend key, mesh shape, and
        the registered model set (key, variant, resolution, depth).  A
        warmup manifest recorded under a different fingerprint is stale —
        replaying it would warm the wrong entries (or hit nothing)."""
        import jaxlib
        ident = {
            "jax": jax.__version__,
            "jaxlib": getattr(jaxlib, "__version__", "?"),
            "platform": jax.default_backend(),
            "backend": getattr(self.backend, "key", str(self.backend)),
            "n_devices": self.n_devices,
            "models": sorted(
                (k, str(m.variant), m.resolution, len(m.net.blocks))
                for k, m in self._models.items()),
        }
        blob = json.dumps(ident, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def compile_stats(self) -> Dict:
        """Per-process compilation accounting: jit entries built, their
        per-entry build wall-ms (first-call trace+compile — or persistent-
        cache deserialize), and the process-wide persistent cache
        hit/miss counters.  The cold/warm restart gate diffs ``persistent
        ["misses"]`` across two processes sharing a cache dir."""
        with self._compile_lock:
            log = [dict(e) for e in self._compile_log]
        for e in log:
            e.pop("entry", None)       # tuple key, not JSON-serializable
        return {
            "cache_dir": self.compilation_cache_dir,
            "jit_entries": len(self._jit),
            "entries_built": len(log),
            "build_ms_total": sum(e["build_ms"] for e in log),
            "persistent": persistent_cache_counters(),
            "compile_log": log,
        }

    def compiled_buckets(self) -> List[tuple]:
        return sorted(self._jit, key=lambda k: (k[0], k[1], len(k)))
