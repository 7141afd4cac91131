"""Cold/warm restart gate: run the serve smoke twice against one
persistent compilation-cache directory and fail unless the warm restart
actually recompiled less.

    python scripts/restart_check.py [--report restart_check_report.json]

Two fresh launcher processes (``repro.launch.serve_vision``) share a
cache dir and a warmup manifest:

* cold — empty dir: every warmed jit entry is a persistent-cache MISS
  (a real XLA compile, then written to disk), manifest written;
* warm — same dir: the manifest replays the warmed entry set and every
  lookup should be a HIT (deserialize, no compile).

Gate (any failure exits 1):

* warm persistent-cache misses strictly lower than cold (the headline
  "compile count went down" check);
* warm misses == 0 — the cache is either fully effective or broken,
  there is no legitimate partial state for an unchanged binary;
* warm run replayed the manifest (``manifest_replayed``).

The JSON report (cold/warm counters, warmup wall-ms, verdicts) is
written even when the gate fails — CI uploads it as the artifact a
regression gets diagnosed from.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_serve(cache_dir: str, manifest: str, json_path: str,
              requests: int, engine: str) -> dict:
    """One CPU launcher process against ``cache_dir``, exported to it as
    ``JAX_COMPILATION_CACHE_DIR``; returns its metrics snapshot (read
    from ``--json-path``)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.env import configure
    env = configure(platform="cpu", compilation_cache_dir=cache_dir,
                    env=dict(os.environ))
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch.serve_vision",
           "--requests", str(requests), "--engine", engine,
           "--warmup-manifest", manifest,
           "--json", json_path]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=1200, env=env, cwd=ROOT)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-2000:] + "\n" + proc.stderr[-4000:])
        raise SystemExit(f"serve launcher failed (rc={proc.returncode})")
    with open(json_path) as f:
        return json.load(f)


def phase_record(snap: dict) -> dict:
    comp = snap.get("compilation", {})
    pc = comp.get("persistent", {})
    return {
        "pcache_hits": int(pc.get("hits", 0)),
        "pcache_misses": int(pc.get("misses", 0)),
        "entries_built": int(comp.get("entries_built", 0)),
        "build_ms_total": float(comp.get("build_ms_total", 0.0)),
        "warmup_ms": float(comp.get("warmup_ms", 0.0)),
        "warmup_entries": int(comp.get("warmup_entries", 0)),
        "manifest_replayed": bool(comp.get("manifest_replayed", False)),
    }


def main() -> int:
    ap = argparse.ArgumentParser(
        description="cold/warm restart compilation-cache gate")
    ap.add_argument("--report", default="restart_check_report.json",
                    help="write the cold/warm report here (always written,"
                         " pass/fail alike)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--engine", default="sync",
                    help="engine implementation to restart (default sync:"
                         " deterministic, and the restart property is"
                         " engine-independent)")
    ap.add_argument("--cache-dir", default=None,
                    help="reuse this cache dir instead of a fresh temp dir"
                         " (must be empty for the cold run to be cold)")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory(prefix="restart_check_") as tmp:
        cache_dir = args.cache_dir or os.path.join(tmp, "jax_cache")
        manifest = os.path.join(tmp, "warmup_manifest.json")
        cold = phase_record(run_serve(
            cache_dir, manifest, os.path.join(tmp, "cold.json"),
            args.requests, args.engine))
        warm = phase_record(run_serve(
            cache_dir, manifest, os.path.join(tmp, "warm.json"),
            args.requests, args.engine))

    checks = {
        "cold_compiled_something": cold["pcache_misses"] > 0,
        "warm_misses_strictly_lower":
            warm["pcache_misses"] < cold["pcache_misses"],
        "warm_misses_zero": warm["pcache_misses"] == 0,
        "warm_replayed_manifest": warm["manifest_replayed"],
        "warm_hits_cover_cold_compiles":
            warm["pcache_hits"] >= cold["pcache_misses"],
    }
    report = {"engine": args.engine, "requests": args.requests,
              "cold": cold, "warm": warm, "checks": checks,
              "ok": all(checks.values())}
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    print(f"restart-check: cold misses={cold['pcache_misses']} "
          f"build_ms={cold['build_ms_total']:.0f} warmup_ms="
          f"{cold['warmup_ms']:.0f} | warm misses={warm['pcache_misses']} "
          f"hits={warm['pcache_hits']} build_ms={warm['build_ms_total']:.0f}"
          f" warmup_ms={warm['warmup_ms']:.0f} "
          f"replayed={warm['manifest_replayed']}")
    for name, ok in sorted(checks.items()):
        print(f"  {'PASS' if ok else 'FAIL'}  {name}")
    print(f"report: {args.report}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
