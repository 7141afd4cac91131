"""The benchmark's one traffic generator.  A mix is a JSON file beside
this module; this reads its parameters and the seed and returns the
requests of a run: a pool of images, built before the window and cycled,
and either a closed loop (a fixed number of requests outstanding) or an
open-loop schedule of due times.

Every seed gets the same work in another order: the same image sizes
(sides on a grid over the mix's range, dealt out by a seeded
permutation), the same arrival times (camera phases and Poisson gaps are
drawn once from the mix's own ``arrival_seed``; the run's seed deals the
phases to the cameras, the jitter, and the images and networks to the
arrivals), and for a mix of networks their exact shares.

Arrival processes (``arrivals.kind``):

* ``closed``: ``outstanding`` requests in flight; a completion submits
  the next.  A batch job that keeps the server's queue full.
* ``cameras``: ``cameras`` streams at ``fps`` frames/s each, every
  camera with a uniform random phase and uniform jitter of
  ``jitter_ms``; each camera has one frame size and cycles through
  ``frames_per_camera`` frames.
* ``poisson``: exponential gaps at ``rate`` requests/s.

The inter-arrival arithmetic is a copy of the Poisson process in
``repro.serving.vision.traffic`` (the program may change; the yardstick
may not).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Plan:
    images: List[np.ndarray]        # the pool
    image_net: np.ndarray           # network index of each pool image
    closed: Optional[int]           # outstanding requests (closed loop)
    order: np.ndarray               # pool index of request i (cycled)
    due_s: Optional[np.ndarray]     # open loop: due time from warm start

    def request(self, i: int) -> int:
        return int(self.order[i % len(self.order)])


def _grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` sides spread evenly over [lo, hi)."""
    return (lo + (np.arange(n) + 0.5) * (hi - lo) / n).astype(np.int64)


def _sides(spec: dict, n: int, rng: np.random.Generator):
    """(heights, widths) of ``n`` images."""
    if spec["kind"] == "fixed":
        return np.full(n, spec["h"]), np.full(n, spec["w"])
    assert spec["kind"] == "uniform", spec
    g = _grid(spec["lo"], spec["hi"], n)
    return rng.permutation(g), rng.permutation(g)


def _fixed(mix: dict) -> np.random.Generator:
    """The stream that arrival times are drawn from: the mix's own, the
    same for every run's seed."""
    return np.random.default_rng([mix["arrivals"].get("arrival_seed", 0),
                                  0xA77])


def _pixels(rng, h: int, w: int, c: int) -> np.ndarray:
    return rng.standard_normal((h, w, c), dtype=np.float32)


def build(mix: dict, n_networks: int, in_channels: int, seed: int,
          span_s: float) -> Plan:
    """Requests of one run.  ``span_s`` is how long the schedule runs
    (warm-up and window); a closed loop ignores it."""
    rng = np.random.default_rng([seed, 0x5EED])
    arr = mix["arrivals"]
    weights = np.asarray(mix.get("weights", [1.0] * n_networks), np.float64)
    if len(weights) != n_networks:
        raise ValueError(f"mix weights {weights} for {n_networks} networks")
    kind = arr["kind"]
    if kind == "cameras":
        n_cam, per = arr["cameras"], arr["frames_per_camera"]
        hs, ws = _sides(mix["sides"], n_cam, rng)
        cam_net = rng.choice(n_networks, size=n_cam, p=weights / weights.sum())
        images, image_net = [], []
        for i in range(n_cam):
            for _ in range(per):
                images.append(_pixels(rng, int(hs[i]), int(ws[i]),
                                      in_channels))
                image_net.append(cam_net[i])
        period = 1.0 / arr["fps"]
        n_frames = int(np.ceil(span_s / period)) + 1
        phase = rng.permutation(_fixed(mix).uniform(0.0, period, n_cam))
        jitter = arr["jitter_ms"] / 1e3
        due = (phase[:, None] + np.arange(n_frames)[None, :] * period
               + rng.uniform(-jitter, jitter, (n_cam, n_frames)))
        pool = (np.arange(n_cam)[:, None] * per
                + np.arange(n_frames)[None, :] % per)
        due, pool = due.ravel(), pool.ravel()
        keep = (due >= 0.0) & (due < span_s)
        due, pool = due[keep], pool[keep]
        idx = np.argsort(due, kind="stable")
        return Plan(images, np.asarray(image_net), None, pool[idx],
                    due[idx])
    # ``pool`` images for each network the mix sends to
    n_pool = mix["pool"]
    nets = [m for m in range(n_networks) if weights[m] > 0]
    hs, ws = _sides(mix["sides"], n_pool * len(nets), rng)
    image_net = np.repeat(nets, n_pool)
    images = [_pixels(rng, int(h), int(w), in_channels)
              for h, w in zip(hs, ws)]
    if kind == "closed":
        return Plan(images, image_net, int(arr["outstanding"]),
                    rng.permutation(n_pool), None)
    if kind == "poisson":
        rate = float(arr["rate"])
        n = int(rate * span_s * 1.2) + 64
        due = np.cumsum(_fixed(mix).exponential(1.0 / rate, n))
        due = due[due < span_s]
        # networks in the mix's exact shares, in a seeded order; each
        # network cycles its own images
        counts = np.floor(weights / weights.sum() * len(due)).astype(int)
        counts[np.argmax(weights)] += len(due) - counts.sum()
        pick = rng.permutation(np.repeat(np.arange(n_networks), counts))
        seen = np.zeros(n_networks, np.int64)
        order = np.empty(len(due), np.int64)
        for i, m in enumerate(pick):
            order[i] = nets.index(m) * n_pool + seen[m] % n_pool
            seen[m] += 1
        return Plan(images, image_net, None, order, due)
    raise ValueError(f"unknown arrival kind {kind!r}")
