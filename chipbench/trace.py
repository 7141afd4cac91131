"""Device trace of a run: capture with JAX's profiler, and reduce the
events to the numbers the per-layer metrics read.

* busy time is the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  clipped to the traced window; idle share is 1 - busy / window,
  averaged over the devices the cell uses;
* a kernel's time is the sum of the durations of its events;
* the breakdown lists the device operations that took most time and the
  longest idle gaps, each named by the host event that covers most of
  it.

The profiler records device events only (no host tracer): on the chip
the host tracer's events inside the host-to-device copy of each batch
slowed it about tenfold while it was on, and left the device idle for
it (measured on a TPU v5e).  The window is therefore taken on the host
clock: event times count from the start of the profiling session, and
the window runs from when ``start_trace`` returned to when
``stop_trace`` was called.  With no host events, idle gaps are named
"no host event" until the program records spans of its own.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)


def union_ns(intervals: List[Interval], lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e, _ in intervals
                   if e > lo and s < hi)
    total, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: List[Interval], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """Idle gaps [(start, end)] between the busy intervals in [lo, hi)."""
    out, t = [], lo
    for s, e, _ in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _host_name(gap: Tuple[int, int], host: List[Interval]) -> str:
    """The host event that overlaps ``gap`` most (of equal overlaps, the
    shortest, which is the innermost), or "no host event"."""
    best, best_key = "no host event", (0, 0)
    for s, e, name in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, s - e) > best_key:
            best, best_key = name, (ov, s - e)
    return best


def reduce(devices: Dict[str, List[Interval]], modules: Dict[str, int],
           host: List[Interval], lo: int, hi: int, top: int = 10) -> dict:
    """Reduce device op events to busy/idle, per-op time and a breakdown.
    ``devices`` maps each device plane to its op events, ``modules`` to
    its count of program (module) executions in the window, ``host`` is
    every host event."""
    window = hi - lo
    if window <= 0 or not devices:
        raise ValueError("empty trace window")
    busy = {d: union_ns(ev, lo, hi) for d, ev in devices.items()}
    op_ns: Dict[str, int] = {}
    op_n: Dict[str, int] = {}
    gaps: List[Tuple[int, int, str]] = []
    for d, ev in devices.items():
        for s, e, name in ev:
            if e <= lo or s >= hi:
                continue
            op_ns[name] = op_ns.get(name, 0) + min(e, hi) - max(s, lo)
            op_n[name] = op_n.get(name, 0) + 1
        gaps += [(g0, g1, d) for g0, g1 in gaps_ns(ev, lo, hi)]
    gaps.sort(key=lambda g: g[0] - g[1])
    busy_mean = sum(busy.values()) / len(busy) / 1e9
    return {
        "window_s": window / 1e9,
        "busy_s": busy_mean,
        "idle_share": 1.0 - busy_mean / (window / 1e9),
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "op_count": op_n,
        "modules": dict(modules),
        "breakdown": {
            "device_ops": [[k, v / 1e9] for k, v in sorted(
                op_ns.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_host_name((g0, g1), host), (g1 - g0) / 1e9]
                          for g0, g1, _ in gaps[:top]],
        },
    }


def read(path: str, lo: int, hi: int,
         device_ids: Optional[List[int]] = None) -> dict:
    """Load an ``.xplane.pb`` and reduce it over [lo, hi) ns of the
    session."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    modules: Dict[str, int] = {}
    host: List[Interval] = []
    mods: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        if is_dev and device_ids is not None:
            try:
                if int(plane.name.rsplit(":", 1)[1]) not in device_ids:
                    continue
            except ValueError:
                continue
        for line in plane.lines:
            evs = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                   for e in line.events]
            if is_dev and line.name == OPS_LINE:
                devices[plane.name] = evs
            elif is_dev and line.name == MODULES_LINE:
                mods[plane.name] = evs
            elif not is_dev and plane.name.startswith("/host"):
                host += evs
    for d, evs in mods.items():
        modules[d] = sum(1 for s, e, _ in evs if s >= lo and e <= hi)
    host = [h for h in host if h[1] > lo and h[0] < hi]
    return reduce(devices, modules, host, lo, hi)


class Tracer:
    """Profiles ``seconds`` of the window starting ``delay`` seconds after
    ``start()``, on a thread of its own; ``result()`` waits for it and
    returns the reduced trace and the host-clock bounds of the window."""

    def __init__(self, delay: float, seconds: float, device_ids=None):
        self.delay, self.seconds = delay, seconds
        self.device_ids = device_ids
        self.t0 = self.t1 = None
        self._lo = self._hi = 0
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-trace")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            time.sleep(self.delay)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            t_call = time.perf_counter()
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            try:
                self.t0 = time.perf_counter()
                time.sleep(self.seconds)
                self.t1 = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            self._lo = int((self.t0 - t_call) * 1e9)
            self._hi = int((self.t1 - t_call) * 1e9)
        except BaseException as e:        # reported by result()
            self._err = e

    def result(self) -> dict:
        self._thread.join()
        try:
            if self._err is not None:
                raise self._err
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError("the profiler wrote no trace")
            out = read(paths[0], self._lo, self._hi, self.device_ids)
            out["host_t0"], out["host_t1"] = self.t0, self.t1
            out["trace_bytes"] = os.path.getsize(paths[0])
            return out
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
