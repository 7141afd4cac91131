"""Vision serving subsystem: batcher, registry, cost model, engine e2e."""
import jax
import numpy as np
import pytest

from repro.serving.vision import (ModelRegistry, SystolicCostModel,
                                  VisionServeEngine, fit_image, form_batch,
                                  percentile)
from repro.serving.vision.batcher import VisionRequest
from repro.vision import zoo

NET = zoo.tiny_net()            # resolution 32, 10 classes


# ---------------------------------------------------------------------------
# Batcher.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,w", [(32, 32), (16, 20), (64, 48), (10, 70)])
def test_fit_image_shapes(h, w):
    img = np.random.default_rng(0).standard_normal((h, w, 3)).astype(
        np.float32)
    out = fit_image(img, 32)
    assert out.shape == (32, 32, 3)
    if h == 32 and w == 32:
        np.testing.assert_array_equal(out, img)


def test_fit_image_pad_is_centered_and_crop_is_center():
    img = np.ones((2, 2, 1), np.float32)
    out = fit_image(img, 4)
    assert out.sum() == 4 and out[1:3, 1:3, 0].sum() == 4
    big = np.zeros((6, 6, 1), np.float32)
    big[2:4, 2:4] = 1.0
    out = fit_image(big, 2)
    assert out.sum() == 4               # center crop keeps the hot square


def test_form_batch_pads_to_bucket():
    rng = np.random.default_rng(0)
    reqs = [VisionRequest(i, "m", rng.standard_normal((20, 40, 3)), float(i))
            for i in range(3)]
    batch = form_batch(reqs, 4, 32)
    assert batch.images.shape == (4, 32, 32, 3)
    assert batch.fill == 3
    assert np.all(batch.images[3] == 0)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

def test_registry_bucket_cache_keys():
    reg = ModelRegistry(backend="xla")
    reg.register(NET, "depthwise")
    reg.register(NET, "fuse_full")
    assert sorted(reg.keys()) == ["tiny_net/depthwise", "tiny_net/fuse_full"]
    x1 = np.zeros((1, 32, 32, 3), np.float32)
    x2 = np.zeros((2, 32, 32, 3), np.float32)
    reg.apply("tiny_net/depthwise", x1)
    reg.apply("tiny_net/depthwise", x2)
    reg.apply("tiny_net/depthwise", x2)     # cache hit, no new entry
    reg.apply("tiny_net/fuse_full", x1)
    assert reg.compiled_buckets() == [("tiny_net/depthwise", 1),
                                      ("tiny_net/depthwise", 2),
                                      ("tiny_net/fuse_full", 1)]


def test_registry_rejects_duplicate_key():
    reg = ModelRegistry()
    reg.register(NET, "depthwise")
    with pytest.raises(AssertionError):
        reg.register(NET, "depthwise")


def test_registry_donates_batch_input_not_params(monkeypatch):
    # The jit entry must donate exactly the batch argument (argnum 1):
    # donating params would invalidate the cached replicated placements.
    seen = []
    real_jit = jax.jit

    def spy_jit(fun, *a, **kw):
        seen.append(kw.get("donate_argnums"))
        return real_jit(fun, *a, **kw)

    monkeypatch.setattr(jax, "jit", spy_jit)
    reg = ModelRegistry(backend="xla")
    reg.register(NET, "depthwise")
    x = np.zeros((2, 32, 32, 3), np.float32)
    reg.apply("tiny_net/depthwise", x)
    assert seen == [(1,)]


def test_registry_donation_keeps_repeated_apply_bitwise():
    # Donation must not change results: repeated applies on the same host
    # batch (fresh device copy per call) stay bitwise equal to the direct
    # un-jitted zoo apply, and params survive across calls.
    reg = ModelRegistry(backend="xla")
    model = reg.register(NET, "fuse_full")
    x = np.random.default_rng(3).standard_normal(
        (2, 32, 32, 3)).astype(np.float32)
    ref, _ = zoo.apply_network(model.params, NET, x, "fuse_full",
                               train=False, backend=model.backend)
    first = np.asarray(reg.apply("tiny_net/fuse_full", x))
    np.testing.assert_allclose(first, np.asarray(ref), rtol=1e-5, atol=1e-5)
    for _ in range(3):
        # bitwise-stable across calls: a reused (donated) output buffer
        # must never leak a previous call's state into the next
        np.testing.assert_array_equal(
            np.asarray(reg.apply("tiny_net/fuse_full", x)), first)


# ---------------------------------------------------------------------------
# Cost model.
# ---------------------------------------------------------------------------

def test_costmodel_monotone_in_batch_and_cached():
    reg = ModelRegistry()
    model = reg.register(NET, "fuse_half")
    cm = SystolicCostModel()
    l1 = cm.predicted_ms(model, 1)
    l4 = cm.predicted_ms(model, 4)
    assert 0 < l1 < l4
    assert cm.predicted_ms(model, 1) == l1          # memoized
    assert ("tiny_net/fuse_half", 1) in cm._cache


def test_costmodel_fuse_beats_depthwise():
    """The co-design claim, surfaced at the serving layer: the scheduler's
    latency model ranks FuSe networks faster than the depthwise baseline."""
    reg = ModelRegistry()
    dw = reg.register(NET, "depthwise")
    fu = reg.register(NET, "fuse_half")
    cm = SystolicCostModel()
    assert cm.predicted_ms(fu, 4) < cm.predicted_ms(dw, 4)


def test_plan_bucket_and_drain():
    reg = ModelRegistry()
    model = reg.register(NET, "depthwise")
    cm = SystolicCostModel()
    buckets = (1, 2, 4, 8)
    plan = cm.plan_bucket(model, 3, buckets)
    assert plan.served == min(3, plan.bucket)
    assert plan.predicted_ms == cm.predicted_ms(model, plan.bucket)
    # draining more requests can never be predicted cheaper
    assert cm.drain_ms(model, 8, buckets) >= cm.drain_ms(model, 3, buckets)


def test_admission_slo():
    reg = ModelRegistry()
    model = reg.register(NET, "depthwise")
    cm = SystolicCostModel()
    ok, predicted = cm.admit(model, None, 0, (1, 2, 4))
    assert ok and predicted > 0
    ok, _ = cm.admit(model, 1e-6, 0, (1, 2, 4))     # impossible SLO
    assert not ok
    ok, _ = cm.admit(model, 1e6, 100, (1, 2, 4))    # generous SLO
    assert ok


# ---------------------------------------------------------------------------
# Engine end-to-end (XLA backend: fast on CPU).
# ---------------------------------------------------------------------------

def _mixed_engine(buckets=(1, 2, 4)):
    reg = ModelRegistry(backend="xla")
    reg.register(NET, "depthwise")
    reg.register(NET, "fuse_full")
    return VisionServeEngine(reg, cost_model=SystolicCostModel(),
                             buckets=buckets)


def test_engine_end_to_end_matches_reference():
    engine = _mixed_engine()
    rng = np.random.default_rng(1)
    submitted = []
    for i in range(9):
        key = engine.registry.keys()[i % 2]
        img = rng.standard_normal(
            (int(rng.integers(16, 64)), int(rng.integers(16, 64)), 3)
        ).astype(np.float32)
        rid = engine.submit(key, img)
        submitted.append((rid, key, img))
    results = engine.flush()
    assert [r.rid for r in results] == [rid for rid, _, _ in submitted]
    for (rid, key, img), r in zip(submitted, results):
        assert r.status == "ok"
        model = engine.registry.get(key)
        assert r.logits.shape == (model.num_classes,)
        x = fit_image(img, model.resolution)[None]
        ref, _ = zoo.apply_network(model.params, model.net, x, model.variant)
        np.testing.assert_allclose(r.logits, np.asarray(ref[0]),
                                   rtol=1e-4, atol=1e-4)
        assert r.predicted_ms > 0 and r.run_ms > 0 and r.e2e_ms >= r.run_ms


def test_engine_batching_independence():
    """A request's logits must not depend on its batchmates or bucket pad."""
    engine = _mixed_engine(buckets=(4,))
    img = np.random.default_rng(2).standard_normal((32, 32, 3)).astype(
        np.float32)
    key = "tiny_net/fuse_full"
    rid = engine.submit(key, img)
    for _ in range(3):
        engine.submit(key, np.zeros((32, 32, 3), np.float32))
    batched = {r.rid: r for r in engine.flush()}[rid]
    solo_engine = _mixed_engine(buckets=(1,))
    rid2 = solo_engine.submit(key, img)
    solo = {r.rid: r for r in solo_engine.flush()}[rid2]
    np.testing.assert_allclose(batched.logits, solo.logits,
                               rtol=1e-5, atol=1e-5)


def test_engine_admission_and_metrics():
    engine = _mixed_engine()
    img = np.zeros((32, 32, 3), np.float32)
    engine.submit("tiny_net/depthwise", img, slo_ms=1e-6)   # rejected
    engine.submit("tiny_net/depthwise", img)                # served
    results = engine.flush()
    assert [r.status for r in results] == ["rejected", "ok"]
    assert results[0].logits is None
    m = engine.metrics.snapshot()
    assert m["submitted"] == 2 and m["rejected"] == 1 and m["completed"] == 1
    assert m["batches"] == 1
    assert m["throughput_ips"] > 0


def test_engine_admission_counts_cross_model_backlog():
    """FIFO drains other models first, so their queued work must count
    against a new request's SLO."""
    engine = _mixed_engine(buckets=(1,))
    img = np.zeros((32, 32, 3), np.float32)
    cm = engine.cost_model
    fuse = engine.registry.get("tiny_net/fuse_full")
    # SLO that fits fuse_full alone but not behind 4 queued depthwise runs
    slo = cm.predicted_ms(fuse, 1) * 2
    for _ in range(4):
        engine.submit("tiny_net/depthwise", img)
    rid = engine.submit("tiny_net/fuse_full", img, slo_ms=slo)
    results = {r.rid: r for r in engine.flush()}
    assert results[rid].status == "rejected"
    # same request with an empty queue is admitted
    engine2 = _mixed_engine(buckets=(1,))
    rid2 = engine2.submit("tiny_net/fuse_full", img, slo_ms=slo)
    assert {r.rid: r for r in engine2.flush()}[rid2].status == "ok"


def test_engine_bucket_padding_counted():
    engine = _mixed_engine(buckets=(4,))    # forced padding: 1 req -> 4 slots
    engine.submit("tiny_net/depthwise", np.zeros((32, 32, 3), np.float32))
    engine.flush()
    assert engine.metrics.padded_slots == 3


def test_engine_unknown_model_raises():
    engine = _mixed_engine()
    with pytest.raises(KeyError):
        engine.submit("nope/depthwise", np.zeros((32, 32, 3), np.float32))


@pytest.mark.parametrize("poison", [False, True])
def test_launcher_exit_status_follows_request_statuses(monkeypatch, tmp_path,
                                                       capsys, poison):
    """The launcher returns normally when every request is served and
    exits non-zero when any request ends in status "error" (a batch whose
    apply raised) — a failed run never looks like a passing one."""
    from repro.launch import serve_vision as launcher
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    if poison:
        real = ModelRegistry.apply

        def apply(self, key, images, devices=None):
            if np.any(np.asarray(images)):     # warmup batches are zeros
                raise RuntimeError("poisoned batch")
            return real(self, key, images, devices=devices)
        monkeypatch.setattr(ModelRegistry, "apply", apply)
    argv = ["--models", "tiny_net/fuse_full", "--resolution", "16",
            "--requests", "2", "--buckets", "1", "2"]
    if poison:
        with pytest.raises(SystemExit) as exc:
            launcher.main(argv)
        assert exc.value.code not in (0, None)
        assert "status 'error'" in str(exc.value.code)
    else:
        launcher.main(argv)
    out = capsys.readouterr().out
    assert (" error " in out) == poison


def test_percentile_nearest_rank():
    assert percentile([], 50) == 0.0
    xs = [float(i) for i in range(1, 101)]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 100.0
    assert abs(percentile(xs, 50) - 50.0) <= 1.0


# ---------------------------------------------------------------------------
# Pallas backend parity through the engine (small net to keep compile cheap).
# ---------------------------------------------------------------------------

def test_engine_pallas_backend_matches_xla():
    small = zoo.tiny_net(num_classes=4, resolution=16, width=8)
    params = zoo.init_network(jax.random.PRNGKey(0), small, "fuse_full")
    reg_p = ModelRegistry(backend="pallas")
    reg_p.register(small, "fuse_full", params=params)
    reg_x = ModelRegistry(backend="xla")
    reg_x.register(small, "fuse_full", params=params)
    img = np.random.default_rng(3).standard_normal((20, 12, 3)).astype(
        np.float32)
    out = {}
    for name, reg in (("pallas", reg_p), ("xla", reg_x)):
        engine = VisionServeEngine(reg, buckets=(2,))
        rid = engine.submit("tiny_net/fuse_full", img)
        out[name] = {r.rid: r for r in engine.flush()}[rid].logits
    np.testing.assert_allclose(out["pallas"], out["xla"],
                               rtol=1e-4, atol=1e-4)
