"""The correctness check, driven on the CPU at a test size: the whole run
but the look for a chip, on the XLA backend at 32 px, a 2 s window.

* with the path intact the run is correct;
* the control (the reference with its contractions in three bf16 passes
  put in the program's place), judged by the same limits and rule, is
  not correct;
* with the timed path broken underneath, ``correct`` comes out false:
  an answer altered where it is produced, half of each batch left out,
  and (on four virtual devices) the exchange between chips left out.

Run this file in a process of its own (it gives the CPU four devices).
"""
import copy
import json
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

RES = 32


def mesh_cell():
    """The four-chip mesh of both networks (``configs/mesh4_mnv2_mnv3l``,
    not yet a cell of the benchmark; PERF.md) under a 3:1 Poisson mix."""
    with open(os.path.join(harness.HERE, "configs",
                           "mesh4_mnv2_mnv3l.json")) as f:
        config = json.load(f)
    mix = {"generator": "generate",
           "arrivals": {"kind": "poisson", "rate": 20, "arrival_seed": 0},
           "weights": [3, 1], "pool": 8, "buckets": [1, 8],
           "sides": {"kind": "uniform", "lo": 112, "hi": 448}}
    return {"name": "mesh", "chips": 4, "config": config, "mix": mix,
            "end_to_end": [], "per_layer": []}


def tiny(name, rate=20):
    cell = mesh_cell() if name == "mesh" else harness.load_cell(name)
    cfg = copy.deepcopy(cell["config"])
    for n in cfg["networks"]:
        n["resolution"] = RES
    mix = copy.deepcopy(cell["mix"])
    arr = mix["arrivals"]
    if arr["kind"] == "closed":
        arr["outstanding"], mix["buckets"], mix["pool"] = 8, [4], 16
        mix["sides"] = {"kind": "fixed", "h": RES, "w": RES}
    else:
        if arr["kind"] == "cameras":
            arr["cameras"] = 4
        else:
            arr["rate"], mix["pool"] = rate, 8
        mix["sides"] = {"kind": "uniform", "lo": RES // 2, "hi": RES * 2}
    cell.update(config=cfg, mix=mix)
    return cell


def run(name, wrap=None, control=False, seed=2**33 + 5, rate=20):
    cell = tiny(name, rate)
    return harness.run_cell(name, seed, 2.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            backend="xla", cell=cell, wrap_apply=wrap,
                            control=control)


def altered(apply):
    """Row 0 of every batch moved by 1% of its largest logit."""
    def f(key, images, devices=None):
        out = apply(key, images, devices=devices)
        y = np.asarray(out).copy()
        y[0] += 0.01 * np.abs(y[0]).max()
        return jnp.asarray(y)
    return f


def half_left_out(apply):
    """The second half of every batch of two or more never computed."""
    def f(key, images, devices=None):
        out = apply(key, images, devices=devices)
        y = np.asarray(out).copy()
        y[-(len(y) // 2):] = 0.0 if len(y) > 1 else y[-1:]
        return jnp.asarray(y)
    return f


def no_exchange(apply):
    """A batch sharded over chips comes back with every chip's rows
    replaced by the first chip's: the gather between chips left out."""
    def f(key, images, devices=None):
        out = apply(key, images, devices=devices)
        shards = sorted(out.addressable_shards, key=lambda s: s.index[0].start
                        or 0)
        if len(shards) < 2 or shards[0].data.shape[0] == out.shape[0]:
            return out
        first = np.asarray(shards[0].data)
        return jnp.asarray(np.concatenate([first] * len(shards)))
    return f


def test_intact_run_is_correct_and_control_is_far():
    out = run("mnv2_fuse_half.batch32", control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    prog = out["widest_gap"]["mobilenet_v2"]
    ctrl = out["bf16x3_widest_gap"]["mobilenet_v2"]
    assert ctrl > 10 * prog, (prog, ctrl)
    assert not out["control_correct"], out["control_checks"]


@pytest.mark.parametrize("name,fault", [
    ("mnv2_fuse_half.batch32", altered),
    ("mnv2_fuse_half.batch32", half_left_out),
    # at the test size the CPU drains each camera frame alone (bucket 1),
    # so half of a batch is no fault this cell can show here
    ("mnv2_fuse_half.cameras", altered),
])
def test_fault_is_caught(name, fault):
    out = run(name, wrap=fault)
    assert not out["correct"], out["checks"]


def test_mesh_intact_and_without_exchange():
    # a rate the CPU cannot keep up with, so that bucket-8 batches form
    # and shard over the four devices
    assert len(jax.devices()) >= 4
    ok = run("mesh", rate=400)
    assert ok["correct"], ok["checks"]
    bad = run("mesh", wrap=no_exchange, rate=400)
    assert not bad["correct"], bad["checks"]


def test_reference_matches_the_program_network():
    """The plain reference against the program's own XLA path on the same
    weights, at 32 px: they agree to float32 rounding."""
    from chipbench import weights
    from chipbench.reference import mobilenet
    from repro.vision import zoo
    cell = tiny("mesh")
    cfg = cell["config"]
    params = weights.make(cfg, 3)
    rng = np.random.default_rng(0)
    imgs = [rng.standard_normal((RES, RES, 3), dtype=np.float32)
            for _ in range(4)]
    for n, p in zip(cfg["networks"], params):
        net = zoo.ZOO[n["zoo"]](resolution=RES)
        with jax.default_matmul_precision("highest"):
            prog = np.asarray(zoo.apply_network(p, net, jnp.stack(imgs),
                                                n["variant"])[0])
        ref = mobilenet.logits(p, n, imgs)
        rel = np.abs(prog - ref).max() / np.abs(ref).max()
        assert rel < 1e-4, (n["zoo"], rel)
