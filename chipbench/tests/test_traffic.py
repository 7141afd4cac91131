"""The generator: one seed gives one schedule; seeds share their sizes."""
import json
import os

import numpy as np
import pytest

from chipbench.traffic import generate

MIXES = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


# a Poisson mix of two networks, 3:1 (no cell runs one yet; PERF.md)
POISSON = {"generator": "generate",
           "arrivals": {"kind": "poisson", "rate": 400, "arrival_seed": 0},
           "weights": [3, 1], "pool": 128,
           "sides": {"kind": "uniform", "lo": 112, "hi": 448},
           "buckets": [1, 8]}


def _mix(name):
    if name == "poisson":
        return POISSON
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix,nets", [("batch32", 1), ("cameras", 1),
                                      ("poisson", 2)])
def test_one_seed_one_schedule(mix, nets):
    m = _mix(mix)
    a = generate.build(m, nets, 3, 2**33 + 1, 4.0)
    b = generate.build(m, nets, 3, 2**33 + 1, 4.0)
    c = generate.build(m, nets, 3, 2**33 + 2, 4.0)
    assert np.array_equal(a.order, b.order)
    assert all(np.array_equal(x, y) for x, y in zip(a.images, b.images))
    if a.due_s is not None:
        assert np.array_equal(a.due_s, b.due_s)
        assert np.all(np.diff(a.due_s) >= 0)
        assert a.due_s[0] >= 0 and a.due_s[-1] < 4.0
    # another seed: the same arrival times (up to the cameras' jitter),
    # another order of the same sizes
    if m["arrivals"]["kind"] == "poisson":
        assert np.array_equal(a.due_s, c.due_s)
    if m["arrivals"]["kind"] == "cameras":
        jit = m["arrivals"]["jitter_ms"] / 1e3
        cs = np.sort(c.due_s)
        for t in np.sort(a.due_s)[100:-100]:
            i = np.searchsorted(cs, t)
            assert min(abs(cs[i] - t), abs(cs[i - 1] - t)) <= 2 * jit
    sa = sorted(im.shape for im in a.images)
    sc = sorted(im.shape for im in c.images)
    if m["sides"]["kind"] == "fixed":
        assert sa == sc
    else:
        assert sorted(s[0] for s in sa) == sorted(s[0] for s in sc)
        assert sorted(s[1] for s in sa) == sorted(s[1] for s in sc)
    assert not np.array_equal(a.images[0], c.images[0])


def test_cameras_rate_and_frames():
    m = _mix("cameras")
    n = m["arrivals"]["cameras"]
    p = generate.build(m, 1, 3, 7, 10.0)
    rate = len(p.due_s) / 10.0
    assert rate == pytest.approx(n * m["arrivals"]["fps"], rel=0.02)
    per = m["arrivals"]["frames_per_camera"]
    assert len(p.images) == n * per
    # a camera's frames share its size
    for cam in range(n):
        shapes = {p.images[cam * per + j].shape for j in range(per)}
        assert len(shapes) == 1


def test_poisson_mix_weights():
    m = _mix("poisson")
    p = generate.build(m, 2, 3, 11, 20.0)
    nets = p.image_net[p.order]
    share = np.mean(nets == 0)
    w = np.asarray(m["weights"], float)
    assert share == pytest.approx(w[0] / w.sum(), abs=0.03)
    assert len(p.due_s) / 20.0 == pytest.approx(m["arrivals"]["rate"],
                                                rel=0.05)
