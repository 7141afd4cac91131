"""The engine's profiler spans, read back from a real trace on the CPU.

Each test serves a tiny network under ``jax.profiler.start_trace`` with the
host tracer at level 1 (the level the spans are written at), reads the
``.xplane.pb`` with ``ProfileData`` and checks the spans of
docs/serving_vision.md ("Tracing a live server"): every span is recorded,
each batch carries one ``batch`` id from formation to fan-back in order
on the clock, one ``admit`` per request, full collections are spanned,
``host_busy_s`` is the sum of the ``form_batch`` spans, and with no
session (or the host tracer off) nothing is recorded and the answers do
not change.  Without a session: ``ServeMetrics.stage`` adds its duration
to ``host_busy_s`` on the metrics' clock, and the collection hook spans
full collections only.
"""
import contextlib
import gc
import glob
import os
import time
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.serving.vision import (ModelRegistry, SystolicCostModel,
                                  create_engine)
from repro.vision import zoo

NET = zoo.tiny_net()            # resolution 32, 10 classes
KEY = "tiny_net/fuse_full"
BATCH_SPANS = ("vision.form_batch", "vision.dispatch",
               "vision.await_device", "vision.fanback")


def _images(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((24 + 4 * (i % 3), 36, 3)).astype(np.float32)
            for i in range(n)]


def _registry(keys=(KEY,)):
    reg = ModelRegistry(backend="xla")
    for k in keys:
        reg.register(NET, k.split("/")[1], key=k)
    return reg


def _slow_apply(reg, seconds=0.05):
    """Hold each dispatch long enough that the one-slot pipeline is
    still full when the scheduler has formed the next batch, so it waits
    for a slot."""
    apply = reg.apply

    def slow(*a, **kw):
        time.sleep(seconds)
        return apply(*a, **kw)
    reg.apply = slow


def _spans(logdir):
    """Every ``vision.*`` / ``python.*`` event of the trace, as dicts."""
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("vision.", "python.")):
                    out.append({"name": e.name, "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "thread": (plane.name, i),
                                "stats": {k: v for k, v in e.stats}})
    return sorted(out, key=lambda s: s["start"])


def _traced(logdir, fn, level=1):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = level
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _spans(str(logdir))


def _burst(engine, images):
    """Submit, wait for every answer, return the logits in rid order."""
    rids = [engine.submit(KEY, img) for img in images]
    res = {r.rid: r for r in engine.flush()}
    return np.stack([res[r].logits for r in rids]), rids


def _by_batch(spans):
    got = defaultdict(list)
    for s in spans:
        if s["name"] in BATCH_SPANS:
            got[s["stats"]["batch"]].append(s)
    return got


def _check_batches(spans, n_batches):
    """One span of each batch stage per batch id, in pipeline order."""
    got = _by_batch(spans)
    assert len(got) == n_batches
    for bid, ss in got.items():
        assert [s["name"] for s in ss] == list(BATCH_SPANS), (bid, ss)
        starts = [s["start"] for s in ss]
        assert starts == sorted(starts)


@pytest.fixture(scope="module")
def reference():
    """The answers of the untraced sync engine, no session active."""
    assert not TraceAnnotation.is_enabled()
    eng = create_engine(_registry(), "sync", buckets=(1, 2, 4),
                        cost_model=SystolicCostModel())
    logits, _ = _burst(eng, _images(12))
    eng.close()
    return logits


def test_pipelined_engine_spans(tmp_path, reference):
    reg = _registry()
    _slow_apply(reg)
    eng = create_engine(reg, "pipelined", buckets=(1, 2, 4),
                        max_in_flight=1, cost_model=SystolicCostModel())
    busy0 = eng.metrics.host_busy_s

    def run():
        out = _burst(eng, _images(12))
        gc.collect()
        # the idle scheduler polls its queue every 50 ms: several of its
        # waits begin and end inside the session
        time.sleep(0.4)
        return out

    (logits, rids), spans = _traced(tmp_path, run)
    eng.close()
    names = {s["name"] for s in spans}
    assert names >= {"vision.admit", "vision.await_work",
                     "vision.await_slot", "vision.form_batch", "vision.dispatch",
                     "vision.await_device", "vision.fanback",
                     "vision.compile", "python.gc_full"}
    # nothing was warmed: the first call of each entry compiled, in a span
    comp = [s for s in spans if s["name"] == "vision.compile"]
    assert {s["stats"]["model"] for s in comp} == {KEY}
    assert all(s["stats"]["pcache_hit"] in (0, 1) for s in comp)
    _check_batches(spans, eng.metrics.batches)
    admits = [s for s in spans if s["name"] == "vision.admit"]
    assert sorted(s["stats"]["rid"] for s in admits) == sorted(rids)
    assert {s["stats"]["model"] for s in admits} == {KEY}
    fills = {s["stats"]["batch"]: s["stats"]["fill"] for s in spans
             if s["name"] == "vision.form_batch"}
    assert sum(fills.values()) == len(rids)
    assert all(s["stats"]["collected"] >= 0 for s in spans
               if s["name"] == "python.gc_full")
    # the one instrument feeds the counter: host_busy_s grew by the
    # form_batch spans' durations.  The counter's clock runs inside each
    # span, so it reads no more than the spans; it reads less by the
    # moments between a span's edges and the clock's reads, where a
    # thread switch or the metrics lock can hold the scheduler
    form_s = sum(s["end"] - s["start"] for s in spans
                 if s["name"] == "vision.form_batch") / 1e9
    grew = eng.metrics.host_busy_s - busy0
    assert 0.5 * form_s <= grew <= form_s + 1e-6
    # batch boundaries may differ from the sync engine's: f32 tolerance
    np.testing.assert_allclose(logits, reference, rtol=1e-5, atol=1e-6)


def test_sync_engine_spans(tmp_path, reference):
    eng = create_engine(_registry(), "sync", buckets=(1, 2, 4),
                        cost_model=SystolicCostModel())
    (logits, rids), spans = _traced(tmp_path,
                                    lambda: _burst(eng, _images(12)))
    eng.close()
    _check_batches(spans, eng.metrics.batches)
    assert len([s for s in spans if s["name"] == "vision.admit"]) == len(rids)
    # the sync path runs every stage on the caller's thread
    assert len({s["thread"] for s in spans
                if s["name"] in BATCH_SPANS + ("vision.admit",)}) == 1
    np.testing.assert_array_equal(logits, reference)


def test_round_scheduler_spans(tmp_path):
    keys = ("tiny_net/fuse_full", "tiny_net/depthwise")
    eng = create_engine(_registry(keys), "pipelined", buckets=(1, 2, 4),
                        cross_model=True, replan=True,
                        cost_model=SystolicCostModel())
    eng.warmup()

    def run():
        imgs = _images(8)
        rids = [eng.submit(keys[i % 2], img) for i, img in enumerate(imgs)]
        return rids, eng.flush()

    (rids, results), spans = _traced(tmp_path, run)
    eng.close()
    assert all(r.status == "ok" for r in results)
    rounds = [s for s in spans if s["name"] == "vision.form_round"]
    assert len(rounds) == eng.metrics.rounds
    assert sum(s["stats"]["fill"] for s in rounds) == len(rids)
    assert all(s["stats"]["parts"] >= 1 and s["stats"]["groups"] >= 1
               for s in rounds)
    replans = [s for s in spans if s["name"] == "vision.replan"]
    assert {s["stats"]["batch"] for s in replans} == \
        {s["stats"]["batch"] for s in rounds}
    # every part of a round carries the round's batch id, after its
    # formation; a backfill has an id of its own, inside a replan span
    disp = [s for s in spans if s["name"] == "vision.dispatch"]
    assert len(disp) == eng.metrics.batches
    formed = {s["stats"]["batch"]: s["start"] for s in rounds}
    for s in disp:
        if s["stats"]["batch"] in formed:
            assert s["start"] >= formed[s["stats"]["batch"]]
        else:
            assert any(r["start"] <= s["start"] <= s["end"] <= r["end"]
                       for r in replans)
    # warm-up compiled every entry before the trace began
    assert "vision.compile" not in {s["name"] for s in spans}


def test_no_spans_with_the_host_tracer_off(tmp_path, reference):
    eng = create_engine(_registry(), "pipelined", buckets=(1, 2, 4),
                        cost_model=SystolicCostModel())
    (logits, _), spans = _traced(tmp_path, lambda: _burst(eng, _images(12)),
                                 level=0)
    eng.close()
    assert spans == []
    # batch boundaries may differ from the sync engine's: f32 tolerance
    np.testing.assert_allclose(logits, reference, rtol=1e-5, atol=1e-6)


def test_gc_span_is_installed_once():
    from repro.serving.vision.metrics import _GcSpan
    for _ in range(3):
        create_engine(_registry(), "sync", cost_model=SystolicCostModel())
    assert sum(isinstance(cb, _GcSpan) for cb in gc.callbacks) == 1


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("raises", [False, True])
def test_stage_adds_its_duration_to_host_busy(raises):
    from repro.serving.vision.metrics import ServeMetrics
    clock = _Clock()
    m = ServeMetrics(clock=clock)
    with pytest.raises(RuntimeError) if raises else contextlib.nullcontext():
        with m.stage("vision.form_batch", batch=1) as sp:
            sp.set_metadata(fill=3)     # stats known only inside
            clock.t += 0.25
            if raises:
                raise RuntimeError("formation failed")
    assert m.host_busy_s == pytest.approx(0.25)
    assert m.snapshot()["host_busy_s"] == pytest.approx(0.25)


@pytest.mark.parametrize("generation", [0, 1])
def test_gc_span_ignores_young_collections(generation):
    from repro.serving.vision.metrics import _GcSpan
    cb = _GcSpan()
    cb("start", {"generation": generation, "collected": 0})
    assert cb._open is None
    cb("stop", {"generation": generation, "collected": 5})
    assert cb._open is None


def test_gc_span_opens_on_start_and_closes_on_stop():
    from repro.serving.vision.metrics import _GcSpan
    cb = _GcSpan()
    cb("stop", {"generation": 2, "collected": 1})     # no start: ignored
    assert cb._open is None
    cb("start", {"generation": 2, "collected": 0})
    assert cb._open is not None
    cb("stop", {"generation": 2, "collected": 7})
    assert cb._open is None
