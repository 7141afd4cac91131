"""Multi-process serving gate: a 2-process coordinator/worker pair must
agree on the mesh, produce round logits bitwise-identical to a
single-process engine, and warm the late-joining worker entirely from
the shared persistent compilation cache (zero recorded misses).

    python scripts/multiprocess_check.py \
        [--report multiprocess_check_report.json]

Three fresh launcher processes (``repro.launch.serve_vision``, the
production entry point — no test-only child):

* single — one process, one 4-device mesh, the reference burst; its
  logits digest is ground truth;
* coordinator — process 0 of a 2-process x 2-local-device topology on a
  free local port, fresh shared cache dir, runs the same burst through
  cross-process rounds;
* worker — process 1, started AFTER the coordinator (the rolling-join
  case), follower loop only.

Gate (any failure exits 1):

* both pair processes exit 0 and build the same mesh fingerprint;
* the pair's logits sha256 equals the single-process run's — rounds
  crossing the process boundary change placement, never values;
* rounds actually crossed processes (worker executed parts, coordinator
  gathered shards) — parity alone could pass with a degenerate plan;
* the worker recorded ZERO persistent-cache misses and its hits cover
  every broadcast entry it warmed: workers never write the cache, so a
  silent recompile shows up as hits falling short of the warmed count.

The JSON report (per-phase snapshots, verdicts) is written even when the
gate fails — CI uploads it as the artifact a regression gets diagnosed
from.
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMMON = ["--models", "tiny_net/fuse_full", "tiny_net/depthwise",
          "--resolution", "16", "--buckets", "1", "2", "4", "--seed", "3"]


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch(extra, n_devices: int, cache_dir: str) -> subprocess.Popen:
    """One launcher process with ``n_devices`` virtual CPU devices (the
    two-process mesh is a CPU rehearsal of the control plane), its
    compilation cache in ``cache_dir`` through the environment."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    logs = (tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve_vision",
         *COMMON, *extra],
        env=env, cwd=ROOT, stdout=logs[0], stderr=logs[1], text=True)
    proc.logs = logs
    return proc


def communicate(proc, timeout):
    """Wait for a launcher; return its (stdout, stderr).  Output goes to
    files, not pipes: a pipe drained only after the other process of the
    pair exits fills up (XLA logs a long line per cache load) and blocks
    its writer, and the pair deadlocks."""
    proc.wait(timeout=timeout)
    out, err = proc.logs
    out.seek(0)
    err.seek(0)
    return out.read(), err.read()


def finish(proc: subprocess.Popen, name: str, timeout: int = 1200) -> None:
    out, err = communicate(proc, timeout)
    if proc.returncode != 0:
        sys.stderr.write(f"--- {name} stdout ---\n{out[-2000:]}\n"
                         f"--- {name} stderr ---\n{err[-4000:]}\n")
        raise SystemExit(f"{name} launcher failed (rc={proc.returncode})")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="2-process serving mesh gate")
    ap.add_argument("--report", default="multiprocess_check_report.json",
                    help="write the report here (always written,"
                         " pass/fail alike)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--worker-delay", type=float, default=1.0,
                    help="seconds the worker joins after the coordinator"
                         " (the rolling-join case; broadcasts queue)")
    args = ap.parse_args()

    reqs = ["--requests", str(args.requests)]
    with tempfile.TemporaryDirectory(prefix="multiprocess_check_") as tmp:
        single_json = os.path.join(tmp, "single.json")
        finish(launch([*reqs, "--mesh", "4", "--json", single_json], 4,
                      os.path.join(tmp, "cache_single")), "single")

        port = free_port()
        pair = [*reqs, "--mesh", "2",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", "2",
                "--warmup-manifest", os.path.join(tmp, "manifest.json")]
        pair_cache = os.path.join(tmp, "cache_pair")
        coord_json = os.path.join(tmp, "coord.json")
        worker_json = os.path.join(tmp, "worker.json")
        coord = launch([*pair, "--process-id", "0", "--json", coord_json], 2,
                       pair_cache)
        time.sleep(args.worker_delay)
        worker = launch([*pair, "--process-id", "1",
                         "--json", worker_json], 2, pair_cache)
        finish(coord, "coordinator")
        finish(worker, "worker")

        with open(single_json) as f:
            single = json.load(f)
        with open(coord_json) as f:
            coordinator = json.load(f)
        with open(worker_json) as f:
            work = json.load(f)

    mp = coordinator.get("multiprocess", {})
    wstats = work.get("worker", {})
    wcache = work.get("compilation", {}).get("persistent", {})
    checks = {
        "single_served_everything":
            single.get("completed") == args.requests,
        "pair_served_everything":
            coordinator.get("completed") == args.requests,
        "mesh_fingerprints_agree":
            bool(mp.get("mesh_fingerprint"))
            and work.get("mesh_fingerprint") == mp.get("mesh_fingerprint"),
        "logits_bitwise_identical":
            bool(single.get("logits_sha256"))
            and coordinator.get("logits_sha256")
            == single.get("logits_sha256"),
        "rounds_crossed_processes":
            int(mp.get("shards_gathered", 0)) > 0
            and int(wstats.get("parts_executed", 0)) > 0,
        "worker_warmed_broadcast_entries":
            int(wstats.get("warmup_entries_warmed", 0)) > 0,
        "worker_zero_pcache_misses":
            int(wcache.get("misses", -1)) == 0,
        "worker_hits_cover_warmed_entries":
            int(wcache.get("hits", 0))
            >= int(wstats.get("warmup_entries_warmed", 0)) > 0,
    }
    report = {
        "requests": args.requests,
        "worker_delay_s": args.worker_delay,
        "single": {"completed": single.get("completed"),
                   "logits_sha256": single.get("logits_sha256"),
                   "mesh_devices": single.get("mesh_devices")},
        "coordinator": {"completed": coordinator.get("completed"),
                        "logits_sha256": coordinator.get("logits_sha256"),
                        "multiprocess": mp},
        "worker": {"stats": wstats, "persistent_cache": wcache,
                   "mesh_fingerprint": work.get("mesh_fingerprint")},
        "checks": checks,
        "ok": all(checks.values()),
    }
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)

    print(f"multiprocess-check: rounds={mp.get('rounds_broadcast', 0)} "
          f"gathered={mp.get('shards_gathered', 0)} "
          f"worker parts={wstats.get('parts_executed', 0)} "
          f"warmed={wstats.get('warmup_entries_warmed', 0)} "
          f"hits={wcache.get('hits', 0)} misses={wcache.get('misses', '?')}")
    for name, ok in sorted(checks.items()):
        print(f"  {'PASS' if ok else 'FAIL'}  {name}")
    print(f"report: {args.report}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
