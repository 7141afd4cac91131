"""Restart bench: cold-start-to-servable vs warm-restart-to-servable.

Every other serving suite measures steady-state latency; this one
measures the OTHER serving cost — how long a fresh process takes to
become servable (every reachable jit entry compiled) — and what the
persistent compilation cache + warmup manifest buy on restart.  The
measurement needs real process boundaries (the harness process has a
long-lived jax whose in-memory jit cache would mask everything), so it
launches ``repro.launch.serve_vision`` twice on the CPU (a rehearsal: the
harness process may hold the chip) against one cache dir of its own,
``.jax_cache/bench_serve_restart`` in the checkout, emptied first and
exported to both children as ``JAX_COMPILATION_CACHE_DIR``, and reads
``compilation.warmup_ms`` from each run's ``--json`` snapshot:

* ``serve_restart.cold_to_servable.xla`` — empty cache: warmup compiles
  every (model, bucket) entry and writes the manifest;
* ``serve_restart.warm_to_servable.xla`` — same dir: the manifest
  replays and every entry deserializes from disk.

Emitted in us like every other suite.  The cold/warm ratio is guarded
floor-only in scripts/bench_check.py: deserialization must not LOSE to
compilation, but the multiple depends on runner disk/CPU, so a baseline
ratchet would turn runner drift into flakes.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit
from repro.launch.env import configure

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench_serve_restart")
REQUESTS = 4


def _serve_once(cache_dir: str, manifest: str, json_path: str) -> dict:
    """One fresh launcher process; returns (snapshot, wall_s)."""
    env = configure(platform="cpu", compilation_cache_dir=cache_dir,
                    env=dict(os.environ))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_vision",
         "--requests", str(REQUESTS), "--engine", "sync",
         "--warmup-manifest", manifest, "--json", json_path],
        capture_output=True, text=True, timeout=1200, env=env, cwd=ROOT)
    wall_s = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(f"serve launcher failed (rc={proc.returncode}): "
                           f"{proc.stderr[-2000:]}")
    with open(json_path) as f:
        snap = json.load(f)
    snap["_wall_s"] = wall_s
    return snap


def run(backend: str = "xla"):
    shutil.rmtree(CACHE_DIR, ignore_errors=True)   # the cold run is cold
    with tempfile.TemporaryDirectory(prefix="bench_restart_") as tmp:
        manifest = os.path.join(tmp, "warmup_manifest.json")
        cold = _serve_once(CACHE_DIR, manifest, os.path.join(tmp, "c.json"))
        warm = _serve_once(CACHE_DIR, manifest, os.path.join(tmp, "w.json"))

    cold_ms = float(cold["compilation"]["warmup_ms"])
    warm_ms = float(warm["compilation"]["warmup_ms"])
    emit(f"serve_restart.cold_to_servable.{backend}", f"{cold_ms * 1e3:.0f}",
         f"warmup of {cold['compilation']['warmup_entries']} entries, "
         f"pcache_misses={cold['compilation']['warmup_pcache_misses']}, "
         f"process wall {cold['_wall_s']:.1f}s")
    emit(f"serve_restart.warm_to_servable.{backend}", f"{warm_ms * 1e3:.0f}",
         f"manifest_replayed={warm['compilation']['manifest_replayed']}, "
         f"pcache_hits={warm['compilation']['warmup_pcache_hits']}, "
         f"pcache_misses={warm['compilation']['warmup_pcache_misses']}, "
         f"process wall {warm['_wall_s']:.1f}s")
    emit(f"serve_restart.warm_speedup.{backend}", "-",
         f"{cold_ms / max(warm_ms, 1e-9):.2f}x faster to servable on "
         f"warm restart")
