"""Sharded cross-model serving tests.

Two layers:

* pure-logic tests of the round machinery (group partitioning, round
  planning, atomic round pops, round-drain admission estimates) that run
  in-process on the cost model and batcher alone;
* device tests on 8 virtual CPU devices — bitwise parity of sharded vs
  unsharded execution per backend (at the per-device batch shape), engine end-to-end round scheduling with
  result fan-back — which need ``--xla_force_host_platform_device_count``
  set before jax initializes, so they run once in a subprocess child
  (``tests/_serve_sharded_child.py``) whose JSON output the tests here
  assert on.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.serving.vision import (ModelRegistry, RequestQueue,
                                  SystolicCostModel, VisionRequest,
                                  device_groups, form_round, round_groups)
from repro.vision import zoo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Round-planner logic (no devices needed).
# ---------------------------------------------------------------------------

def test_round_groups_power_of_two_partitions():
    assert round_groups(1, 8) == 1
    assert round_groups(2, 8) == 2
    assert round_groups(3, 8) == 2          # 4 groups would exceed 3 models
    assert round_groups(4, 8) == 4
    assert round_groups(9, 8) == 8          # more models than devices: share
    assert round_groups(3, 2) == 2
    assert round_groups(5, 6) == 2          # 4 does not divide 6
    assert round_groups(4, 1) == 1


def test_device_groups_contiguous_equal():
    devs = list(range(8))
    assert device_groups(devs, 2) == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert device_groups(devs, 4) == [(0, 1), (2, 3), (4, 5), (6, 7)]
    assert device_groups(devs, 1) == [tuple(range(8))]


@pytest.fixture(scope="module")
def two_models():
    reg = ModelRegistry(backend="xla")
    net = zoo.tiny_net(resolution=16, width=8)
    a = reg.register(net, "depthwise")
    b = reg.register(net, "fuse_full")
    return a, b


def test_plan_round_composition(two_models):
    """The structural "fifo" planner: even split, FIFO round-robin deal
    (adaptive composition scoring is covered in test_round_planner.py)."""
    a, b = two_models
    cm = SystolicCostModel(n_devices=8, round_planner="fifo")
    plan = cm.plan_round([(a, 8), (b, 8)], (1, 2, 4, 8))
    assert plan.n_groups == 2 and plan.n_devices == 8
    assert plan.strategy == "even"
    assert [p.group for p in plan.parts] == [0, 1]       # FIFO round-robin
    # each part planned for its 4-device group: bucket 8 shards 4-wide
    for p in plan.parts:
        assert p.plan.bucket == 8 and p.plan.n_devices == 4
    # round latency = slowest group (groups run concurrently)
    per_part = [p.plan.predicted_ms for p in plan.parts]
    assert plan.predicted_ms == pytest.approx(max(per_part))
    assert plan.served == 16


def test_plan_round_single_model_full_mesh(two_models):
    a, _ = two_models
    cm = SystolicCostModel(n_devices=8)
    plan = cm.plan_round([(a, 8)], (1, 2, 4, 8))
    assert plan.n_groups == 1
    assert plan.parts[0].plan.n_devices == 8             # bucket 8 over 8
    # sharded accel-ms = per-device microbatch price
    assert plan.parts[0].plan.predicted_ms == pytest.approx(
        cm.predicted_ms(a, 1))


def test_indivisible_bucket_replicates(two_models):
    a, _ = two_models
    cm = SystolicCostModel(n_devices=8)
    assert cm.shard_width(8, 8) == 8
    assert cm.shard_width(4, 8) == 1        # 4 does not divide 8: replicate
    assert cm.shard_width(2, 1) == 1
    plan = cm.plan_bucket(a, 4, (4,), group_size=8)
    assert plan.n_devices == 1
    assert plan.predicted_ms == pytest.approx(cm.predicted_ms(a, 4))


def test_drain_rounds_prices_what_the_scheduler_does(two_models):
    """The admission backlog estimate must equal the round sequence the
    scheduler would actually form (plan_round applied until drained)."""
    a, b = two_models
    cm = SystolicCostModel(n_devices=8)
    buckets = (1, 2, 4, 8)
    # depth 8 each: one round serves everything (bucket 8 per model)
    one_round = cm.plan_round([(a, 8), (b, 8)], buckets)
    assert cm.drain_rounds_ms([(a, 8), (b, 8)], buckets) == pytest.approx(
        one_round.predicted_ms)
    # depth 10 each: the 8-bucket round plus a leftover round of 2s
    leftover = cm.plan_round([(a, 2), (b, 2)], buckets)
    assert cm.drain_rounds_ms([(a, 10), (b, 10)], buckets) == pytest.approx(
        one_round.predicted_ms + leftover.predicted_ms)
    assert cm.drain_rounds_ms([], buckets) == 0.0


def test_pop_many_is_atomic_fifo():
    q = RequestQueue()
    for i in range(6):
        q.push(VisionRequest(i, ("a", "b")[i % 2], None, float(i)))
    pops = q.pop_many([("a", 2), ("b", 1), ("missing", 3)])
    assert [[r.rid for r in reqs] for reqs in pops] == [[0, 2], [1], []]
    assert q.pending("a") == 1 and q.pending("b") == 2


def test_form_round_per_slot_results():
    """Aligned per-slot output: Batch / None (empty pop) / the exception a
    malformed part raised — one bad image never sinks the other models."""
    import numpy as np
    good = [VisionRequest(0, "a", np.zeros((4, 4, 3), np.float32), 0.0)]
    bad = [VisionRequest(1, "b", np.zeros((4, 4), np.float32), 0.0)]  # 2-D
    formed = form_round([(good, 2, 8), ([], 4, 8), (bad, 1, 8)])
    assert formed[0].model == "a" and formed[0].images.shape == (2, 8, 8, 3)
    assert formed[1] is None
    assert isinstance(formed[2], BaseException)


# ---------------------------------------------------------------------------
# Device tests: one subprocess on 8 virtual CPU devices.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded(request):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests",
                                      "_serve_sharded_child.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-4000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_child_saw_8_virtual_devices(sharded):
    assert sharded["devices"] == 8


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_outputs_bitwise_match_unsharded(sharded, backend):
    """Acceptance: same backend, sharded (data-parallel over the mesh,
    replicated when indivisible, half-mesh device group) vs unsharded at
    the batch shape each device sees — bitwise equal.  (XLA may pick other
    kernels for another batch size, so unsharded batch 8 vs eight
    1-image shards is not a bitwise comparison.)"""
    assert sharded[f"parity_{backend}_b8"] is True
    assert sharded[f"parity_{backend}_b4"] is True
    assert sharded[f"parity_{backend}_group4"] is True


def test_engine_forms_cross_model_rounds_on_mesh(sharded):
    assert sharded["rounds"] >= 1
    assert sharded["cross_model_rounds"] >= 1
    assert sharded["max_round_groups"] == 2         # 2 models -> 2 groups
    assert 4 in sharded["sharded_results"]          # some batches sharded


def test_engine_fans_results_back_in_order(sharded):
    assert sharded["e2e_statuses_ok"] is True
    assert sharded["e2e_rid_order"] is True
    assert sharded["e2e_fanback"] is True


def test_round_jit_cache_is_bounded_and_calibration_sharded(sharded):
    assert sharded["jit_cache_stable"] is True
    assert sharded["calibration_sharded_cells"]     # e.g. ["4x4"]


def test_adaptive_planner_serves_on_mesh(sharded):
    """Adaptive composition scoring end-to-end on 8 devices: every request
    ok, per-request fan-back to the right request, every dispatched round attributed to
    a scored strategy (which one wins is measurement-dependent)."""
    assert sharded["adaptive_ok"] is True
    assert sharded["adaptive_fanback"] is True
    assert sharded["adaptive_rounds"] >= 1
    assert sharded["adaptive_strategy_rounds_match"] is True
    assert set(sharded["adaptive_strategies"]) <= {"even", "uneven", "serial"}
