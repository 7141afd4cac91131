"""Multi-process serving bench: served throughput of a 2-process
coordinator/worker mesh vs a single-process engine.

Same real-process-boundary requirement as ``serve_restart``: the pair is
two fresh ``repro.launch.serve_vision`` processes on the CPU (2 virtual
devices each, global universe of 4 — a rehearsal of the control plane,
never two processes on one chip) joined through the coordination service on a
free local port; the reference is one fresh single-process launcher on a
2-device mesh (same per-process device budget).  Both serve the same
deterministic burst and report engine-measured served throughput
(``throughput_ips`` from the metrics snapshot — warmup/compilation time
excluded), emitted as us/request like every other suite:

* ``serve_multiprocess.single_process.xla`` — 1 process x 2 devices;
* ``serve_multiprocess.two_process.xla``   — 2 processes x 2 devices.

On the CPU smoke rig the cross-process control plane (base64 round
broadcasts and logit-shard gathers through the KV store) is priced
against tiny tiny_net batches, so the two-process number is NOT expected
to win — the guard in scripts/bench_check.py is a floor-only sanity
bound (the mesh must not collapse), not a scaling claim.  Real scaling
needs real accelerators and real batch sizes.
"""
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from benchmarks.common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUESTS = 8

# one unmeasured warm burst first: the pair's first round otherwise
# absorbs the worker's whole warmup-broadcast chew (a one-time join
# cost), and the single-process engine gets the same calibration traffic
COMMON = ["--models", "tiny_net/fuse_full", "tiny_net/depthwise",
          "--resolution", "16", "--requests", str(REQUESTS),
          "--seed", "3", "--buckets", "1", "2", "4", "--warm-bursts", "1"]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch(extra, n_devices: int) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    logs = (tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve_vision",
         *COMMON, *extra],
        env=env, cwd=ROOT, stdout=logs[0], stderr=logs[1], text=True)
    proc.logs = logs
    return proc


def _communicate(proc, timeout):
    """Wait for a launcher; return its (stdout, stderr).  Output goes to
    files, not pipes: a pipe drained only after the other process of the
    pair exits fills up (XLA logs a long line per cache load) and blocks
    its writer, and the pair deadlocks."""
    proc.wait(timeout=timeout)
    out, err = proc.logs
    out.seek(0)
    err.seek(0)
    return out.read(), err.read()


def _finish(proc: subprocess.Popen, name: str) -> None:
    out, err = _communicate(proc, 1200)
    if proc.returncode != 0:
        raise RuntimeError(f"{name} launcher failed "
                           f"(rc={proc.returncode}): {err[-2000:]}")


def _us_per_request(snap: dict) -> float:
    ips = float(snap.get("throughput_ips") or 0.0)
    if ips <= 0:
        raise RuntimeError("snapshot reports no served throughput")
    return 1e6 / ips


def run(backend: str = "xla"):
    with tempfile.TemporaryDirectory(prefix="bench_mp_") as tmp:
        single_json = os.path.join(tmp, "single.json")
        single = _launch(["--mesh", "2", "--json", single_json], 2)
        _finish(single, "single")
        with open(single_json) as f:
            single_snap = json.load(f)

        port = _free_port()
        pair = ["--mesh", "2", "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", "2"]
        coord_json = os.path.join(tmp, "coord.json")
        coord = _launch([*pair, "--process-id", "0",
                         "--json", coord_json], 2)
        time.sleep(0.5)
        worker = _launch([*pair, "--process-id", "1"], 2)
        _finish(coord, "coordinator")
        _finish(worker, "worker")
        with open(coord_json) as f:
            coord_snap = json.load(f)

    single_us = _us_per_request(single_snap)
    two_us = _us_per_request(coord_snap)
    mp = coord_snap.get("multiprocess", {})
    emit(f"serve_multiprocess.single_process.{backend}", f"{single_us:.0f}",
         f"1 proc x 2 dev, {single_snap.get('completed')} served")
    emit(f"serve_multiprocess.two_process.{backend}", f"{two_us:.0f}",
         f"2 proc x 2 dev (global 4), {coord_snap.get('completed')} served,"
         f" rounds={mp.get('rounds_broadcast')},"
         f" shards_gathered={mp.get('shards_gathered')}")
    emit(f"serve_multiprocess.scale_ratio.{backend}", "-",
         f"{single_us / max(two_us, 1e-9):.2f}x single/two-process served"
         f" throughput ratio (control-plane overhead included)")
