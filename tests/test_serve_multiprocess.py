"""Two-process data-parallel serving acceptance.

The multi-process mesh only proves itself across real process boundaries:
a coordinator (process 0, runs the scheduler and traffic) and a worker
(process 1, follower loop) each with their own jax runtime and 2 virtual
CPU devices, joined through the coordination service on a free local
port.  The children are the production launcher itself
(``repro.launch.serve_vision``) — no test-only entry point.

Asserted here (and gated in CI by ``scripts/multiprocess_check.py``):

* both processes build the same mesh fingerprint;
* the 2-process round logits are bitwise-identical to a single-process
  engine serving the same burst on one 4-device mesh (per-row compute is
  placement-independent);
* the worker — started AFTER the coordinator, joining late — warms every
  broadcast entry as a pure persistent-cache hit: zero recorded misses,
  and hits covering the full warmed entry set (the coordinator populates
  the shared cache dir before broadcasting).
"""
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMMON = ["--models", "tiny_net/fuse_full", "tiny_net/depthwise",
          "--resolution", "16", "--requests", "6", "--seed", "3",
          "--buckets", "1", "2", "4"]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _child_env(n_devices, cache_dir):
    """A CPU launcher child: the two-process mesh is a rehearsal of the
    control plane on virtual CPU devices, never a second process on a
    chip.  The cache directory reaches it through the environment."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env.pop("REPRO_NUM_PROCESSES", None)
    env.pop("REPRO_PROCESS_ID", None)
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    return env


def _launcher(extra, n_devices, cache_dir):
    logs = (tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve_vision",
         *COMMON, *extra],
        env=_child_env(n_devices, cache_dir), cwd=ROOT,
        stdout=logs[0], stderr=logs[1], text=True)
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


@pytest.fixture(scope="module")
def mp_pair(tmp_path_factory):
    base = tmp_path_factory.mktemp("mp")
    cache = base / "jax_cache"
    port = _free_port()
    pair = ["--mesh", "2", "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", "2",
            "--warmup-manifest", str(base / "manifest.json")]
    coord = _launcher([*pair, "--process-id", "0",
                       "--json", str(base / "coord.json")], 2, cache)
    time.sleep(1.0)   # the worker joins late; broadcasts queue for it
    worker = _launcher([*pair, "--process-id", "1",
                        "--json", str(base / "worker.json")], 2, cache)
    cout, cerr = _communicate(coord, 900)
    wout, werr = _communicate(worker, 900)
    assert coord.returncode == 0, (cout[-2000:], cerr[-4000:])
    assert worker.returncode == 0, (wout[-2000:], werr[-4000:])

    single = _launcher(["--mesh", "4",
                        "--json", str(base / "single.json")], 4,
                       base / "jax_cache_single")
    sout, serr = _communicate(single, 900)
    assert single.returncode == 0, (sout[-2000:], serr[-4000:])
    return (json.loads((base / "coord.json").read_text()),
            json.loads((base / "worker.json").read_text()),
            json.loads((base / "single.json").read_text()))


def test_mesh_agreement(mp_pair):
    coord, worker, _ = mp_pair
    mp = coord["multiprocess"]
    assert mp["num_processes"] == 2 and mp["global_size"] == 4
    assert worker["mesh_fingerprint"] == mp["mesh_fingerprint"]
    assert worker["num_processes"] == 2
    assert worker["mesh_devices"] == 4 and worker["local_devices"] == 2


def test_cross_process_rounds_served_everything(mp_pair):
    coord, worker, _ = mp_pair
    assert coord["completed"] == 6 and coord["rejected"] == 0
    mp = coord["multiprocess"]
    # rounds actually crossed the process boundary, both directions
    assert mp["rounds_broadcast"] > 0
    assert mp["shards_gathered"] > 0
    assert mp["broadcast_bytes"] > 0 and mp["gather_bytes"] > 0
    assert worker["worker"]["rounds_seen"] == mp["rounds_broadcast"]
    assert worker["worker"]["parts_executed"] > 0


def test_logits_bitwise_identical_to_single_process(mp_pair):
    coord, _, single = mp_pair
    assert coord["logits_sha256"] == single["logits_sha256"]
    assert single["completed"] == coord["completed"]


def test_late_joining_worker_recompiles_nothing(mp_pair):
    """Acceptance: the worker joined after the coordinator and warmed
    from the shared cache dir + warmup broadcast — every warm compile
    deserialized (a recorded miss is an actual XLA compile-and-write)."""
    coord, worker, _ = mp_pair
    w = worker["worker"]
    pc = worker["compilation"]["persistent"]
    assert w["warmup_entries_warmed"] > 0
    assert pc["misses"] == 0
    # every broadcast entry this worker warmed was a persistent-cache
    # hit; a silent miss (workers never write the cache) would leave
    # hits short of the warmed count
    assert pc["hits"] >= w["warmup_entries_warmed"]
    # and the coordinator actually paid those compiles cold
    assert coord["compilation"]["persistent"]["misses"] > 0
    assert w["warmup_fingerprint"]


def test_worker_snapshot_shape(mp_pair):
    _, worker, _ = mp_pair
    assert worker["mode"] == "worker" and worker["process_id"] == 1
    for key in ("rounds_seen", "parts_executed", "parts_skipped",
                "warmup_entries_warmed", "warmup_entries_skipped",
                "shard_bytes_out", "warmup_fingerprint"):
        assert key in worker["worker"]
