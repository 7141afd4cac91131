"""Engine-interface conformance grid.

The tentpole claim of the JetStream-style refactor: every engine behind
:class:`ServingEngine` is interchangeable — driven through IDENTICAL
submit/poll/stream/flush/close sequences, the sync and pipelined engines
must produce identical per-request outcomes (same statuses, bitwise-
identical logits), differing only in when the work happens.  These tests
drive both engines through the same scripted sequences and diff the
outcomes, including the failure statuses ("rejected", "error") and the
closed-engine behavior; plus the factory/registration surface itself.
"""
import numpy as np
import pytest

from repro.serving.vision import (ENGINES, ModelRegistry,
                                  PipelinedVisionEngine, ServingEngine,
                                  SyncVisionEngine, VisionServeEngine,
                                  create_engine, make_mixed_burst,
                                  register_engine)
from repro.vision import zoo

BUCKETS = (1, 2, 4)


@pytest.fixture(scope="module")
def registry():
    reg = ModelRegistry(backend="xla")
    net = zoo.tiny_net(resolution=16, width=8)
    reg.register(net, "depthwise")
    reg.register(net, "fuse_full")
    return reg


def drive(engine, registry, n=10, seed=5):
    """One scripted conformance sequence: submit a burst, poll the first
    request to completion, stream the rest, flush, close.  Returns the
    per-request outcome list the engines are diffed on."""
    items = make_mixed_burst(registry, n, seed=seed)
    rids = [engine.submit(k, img) for k, img in items]

    first = engine.poll(rids[0], timeout_ms=60_000)
    assert first is not None and first.rid == rids[0]

    streamed = {r.rid: r for r in engine.stream_results(rids,
                                                        timeout_ms=60_000)}
    assert sorted(streamed) == sorted(rids)

    # poll is non-destructive: everything must still be flushable
    flushed = {r.rid: r for r in engine.flush()}
    assert sorted(flushed) == sorted(rids)
    engine.close()
    return [(flushed[rid].status, flushed[rid].bucket, flushed[rid].logits)
            for rid in rids]


@pytest.mark.parametrize("engine_name", sorted(["sync", "pipelined"]))
def test_engine_conforms_to_protocol(registry, engine_name):
    engine = create_engine(registry, engine_name, buckets=BUCKETS)
    try:
        assert isinstance(engine, ServingEngine)
        assert isinstance(engine, VisionServeEngine)
        for verb in ("submit", "poll", "stream_results", "warmup",
                     "snapshot", "close"):
            assert callable(getattr(engine, verb))
    finally:
        engine.close()


# fp32 tolerance for one image's logits computed in different batch
# shapes: the pipelined engine dispatches while requests still arrive, so
# it may batch a request into another bucket than the sync engine does,
# and XLA:CPU picks other kernels (another fp32 summation order) for batch
# 1 than for larger batches — observed differences are below 3e-7
BATCH_SHAPE_TOL = dict(rtol=1e-5, atol=1e-5)


def test_identical_sequences_identical_outcomes(registry):
    """Acceptance: same submit/poll/stream/flush/close script on both
    engines -> same statuses and, request by request, bitwise-identical
    logits where both engines served the request in the same bucket
    (within BATCH_SHAPE_TOL where they did not)."""
    sync_out = drive(create_engine(registry, "sync", buckets=BUCKETS),
                     registry)
    pipe_out = drive(create_engine(registry, "pipelined", buckets=BUCKETS),
                     registry)
    assert len(sync_out) == len(pipe_out)
    for (s_status, s_bucket, s_logits), (p_status, p_bucket, p_logits) in \
            zip(sync_out, pipe_out):
        assert s_status == p_status == "ok"
        if s_bucket == p_bucket:
            assert np.array_equal(s_logits, p_logits)
        else:
            np.testing.assert_allclose(s_logits, p_logits, **BATCH_SHAPE_TOL)


@pytest.mark.parametrize("engine_name", sorted(["sync", "pipelined"]))
def test_poll_unknown_rid_raises(registry, engine_name):
    engine = create_engine(registry, engine_name, buckets=BUCKETS)
    try:
        with pytest.raises(KeyError):
            engine.poll(10_000)
    finally:
        engine.close()


def test_rejected_status_parity(registry):
    """An SLO no engine can meet is rejected at submit time on both
    engines — admission is priced by the shared analytic cost model, so
    the decision must not depend on the execution path."""
    key = registry.keys()[0]
    img = np.zeros((16, 16, 3), np.float32)
    outcomes = {}
    for name in ("sync", "pipelined"):
        engine = create_engine(registry, name, buckets=BUCKETS)
        try:
            rid = engine.submit(key, img, slo_ms=1e-6)
            res = engine.poll(rid, timeout_ms=60_000)
            outcomes[name] = res.status
        finally:
            engine.close()
    assert outcomes == {"sync": "rejected", "pipelined": "rejected"}


class _PoisonRegistry:
    """Registry wrapper whose ``apply`` raises for one model key —
    exercises the engines' failed-batch path without a broken model."""

    def __init__(self, inner, poison_key):
        self._inner = inner
        self._poison = poison_key

    def apply(self, key, images, **kw):
        if key == self._poison:
            raise RuntimeError("poisoned model")
        return self._inner.apply(key, images, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_error_status_parity(registry):
    """A batch whose execution raises resolves its requests with status
    "error" (exception text attached) on BOTH engines; unaffected models
    still complete "ok"."""
    poison_key = registry.keys()[0]
    outcomes = {}
    for name in ("sync", "pipelined"):
        engine = create_engine(_PoisonRegistry(registry, poison_key), name,
                               buckets=BUCKETS)
        try:
            items = make_mixed_burst(registry, 8, seed=9)
            rids = [engine.submit(k, img) for k, img in items]
            done = {r.rid: r for r in engine.flush()}
        finally:
            engine.close()
        outcomes[name] = [
            (done[rid].status, (k == poison_key)) for rid, (k, _)
            in zip(rids, items)]
        for rid, (k, _) in zip(rids, items):
            if k == poison_key:
                assert done[rid].status == "error"
                assert "poisoned model" in done[rid].error
                assert done[rid].logits is None
            else:
                assert done[rid].status == "ok"
    assert outcomes["sync"] == outcomes["pipelined"]


@pytest.mark.parametrize("engine_name", sorted(["sync", "pipelined"]))
def test_closed_engine_rejects_submit(registry, engine_name):
    engine = create_engine(registry, engine_name, buckets=BUCKETS)
    engine.close()
    with pytest.raises(RuntimeError):
        engine.submit(registry.keys()[0], np.zeros((16, 16, 3), np.float32))
    engine.close()  # idempotent


# ---------------------------------------------------------------------------
# Factory / registration surface.
# ---------------------------------------------------------------------------

def test_factory_unknown_engine_raises(registry):
    with pytest.raises(ValueError, match="unknown engine"):
        create_engine(registry, "warp-drive")


def test_stock_engines_registered():
    assert ENGINES["sync"] is SyncVisionEngine
    assert ENGINES["pipelined"] is PipelinedVisionEngine


def test_register_engine_shadows_and_restores(registry):
    calls = []

    def fake(reg, **kw):
        calls.append(kw)
        return SyncVisionEngine(reg, **kw)

    original = ENGINES["sync"]
    register_engine("sync", fake)
    try:
        engine = create_engine(registry, "sync", buckets=BUCKETS)
        engine.close()
        assert calls == [{"buckets": BUCKETS}]
    finally:
        register_engine("sync", original)


def test_engine_flag_is_not_overridable(registry):
    """The named classes pin their execution path: a stray ``pipelined=``
    kwarg cannot flip a SyncVisionEngine into a threaded one."""
    engine = SyncVisionEngine(registry, pipelined=True, buckets=BUCKETS)
    try:
        assert engine.pipelined is False
    finally:
        engine.close()
    engine = PipelinedVisionEngine(registry, pipelined=False,
                                   buckets=BUCKETS)
    try:
        assert engine.pipelined is True
    finally:
        engine.close()
