"""One run of one benchmark cell: set up the served system from the
cell's configuration, drive it with the cell's traffic for the window,
check every answer, and read the metrics.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in the file that entry names, its mix in
``chipbench/traffic/<mix>.json``, each per-layer metric in
``chipbench/metrics/<metric>.py`` and the plain reference in
``chipbench/reference/<reference>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# warm traffic before the window: fills the pipeline and feeds the cost
# model's calibrator, so the window sees the steady state
WARM_S = 2.0
# how long a traced run profiles, in the middle of its window
TRACE_S = 3.0
# how long past the window's close an answer is waited for
LATE_S = 60.0


class BenchError(RuntimeError):
    """The cell cannot be run as described; nothing is measured."""


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(ROOT, cfg["file"]))
    mix = _load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in names]
    return {"name": name, "chips": w["chips"], "config": config,
            "mix": mix, "end_to_end": e2e, "per_layer": per_layer}


def load_metric(name: str) -> Callable:
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str):
    return importlib.import_module("chipbench.reference." + name)


def cache_dir() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def enable_cache() -> None:
    """Persist every compiled program, the benchmark's own (weights,
    reference) too, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the system under test ----------------------------------------------------

def _program_blocks(net) -> List[dict]:
    """The program's network definition in the configuration's terms."""
    from repro.vision import zoo
    out = []
    for b in net.blocks:
        d = dataclasses.asdict(b)
        if isinstance(b, zoo.Stem):
            d["type"] = "stem"
        elif isinstance(b, zoo.MBConv):
            d["type"] = "mbconv"
        elif isinstance(b, zoo.ConvBN):
            d["type"] = "conv"
        elif isinstance(b, zoo.Head):
            d["type"] = "head"
        else:
            d["type"] = type(b).__name__
        out.append(d)
    return out


@dataclasses.dataclass
class System:
    registry: object
    engine: object
    keys: List[str]              # registry key of each network
    devices: list                # devices the cell uses


def build_system(config: dict, mix: dict, params: list, chips: int,
                 backend: Optional[str] = None) -> System:
    """Registry -> pipelined engine, as a user builds them, with the
    benchmark's weights."""
    import jax
    from repro.serving.vision import (LatencyCalibrator, ModelRegistry,
                                      SystolicCostModel, create_engine)
    from repro.vision import zoo
    mesh = None
    if config.get("mesh") == "data":
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(chips)
    elif chips != 1:
        raise BenchError(f"{chips} chips need mesh 'data'")
    registry = ModelRegistry(backend=backend or config["backend"],
                             mesh=mesh, compilation_cache_dir=cache_dir())
    keys = []
    for n, p in zip(config["networks"], params):
        net = zoo.ZOO[n["zoo"]](num_classes=n["classes"],
                                width_mult=n["width"],
                                resolution=n["resolution"])
        if net.in_channels != n["in_channels"] \
                or _program_blocks(net) != n["blocks"]:
            raise BenchError(f"the program's {n['zoo']} differs from "
                             f"the configuration's blocks")
        registry.register(net, n["variant"], key=n["key"], params=p)
        keys.append(n["key"])
    engine = create_engine(
        registry, "pipelined", buckets=tuple(mix["buckets"]),
        cost_model=SystolicCostModel(calibrator=LatencyCalibrator(),
                                     n_devices=chips,
                                     round_planner=config["planner"]))
    devices = list(jax.devices()[:chips])
    return System(registry, engine, keys, devices)


# -- driving the window -------------------------------------------------------

class _GcPauses:
    """Sums the host-clock time the process spent in full (generation 2)
    garbage collections while ``on``: every thread of the process stalls
    for as long as one runs."""

    def __init__(self):
        self.on = False
        self.count = 0
        self.seconds = 0.0
        self._t = None
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            if self.on:
                self.count += 1
                self.seconds += time.perf_counter() - self._t
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._event)


class _CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        from jax import monitoring
        self.on = False
        self.traces = self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def drive(system: System, plan, image_net, seconds: float,
          tracer=None) -> dict:
    """Warm traffic for ``WARM_S``, then the window.  Returns what was
    submitted (request id, pool image, due time and call time of each, in
    flat lists, so that the harness adds no objects for the garbage
    collector to walk) and the host-clock bounds of the window."""
    engine = system.engine
    rids: List[int] = []
    pools: List[int] = []
    dues: List[float] = []
    calls: List[float] = []
    counter = _CompileCounter()
    pauses = _GcPauses()
    host_busy = {}

    def submit(i: int, due: float) -> int:
        p = plan.request(i)
        t = time.perf_counter()
        rid = engine.submit(system.keys[image_net[p]], plan.images[p])
        rids.append(rid)
        pools.append(p)
        dues.append(due)
        calls.append(t)
        return rid

    t_begin = time.perf_counter()
    t0 = t_begin + WARM_S
    t_end = t0 + seconds
    opened = False

    def open_window():
        nonlocal opened
        host_busy["t0"] = engine.metrics.host_busy_s
        counter.on = pauses.on = True
        opened = True
        if tracer is not None:
            tracer.start()

    if plan.closed is not None:
        out = deque(submit(i, t_begin) for i in range(plan.closed))
        i = plan.closed
        while True:
            fut = engine.future(out.popleft())
            try:
                fut.result(timeout=max(1e-3, t_end + LATE_S
                                       - time.perf_counter()))
            except TimeoutError:
                break
            now = time.perf_counter()
            if not opened and now >= t0:
                open_window()
            if now >= t_end:
                break
            out.append(submit(i, now))
            i += 1
    else:
        for i, d in enumerate(plan.due_s):
            due = t_begin + float(d)
            if due >= t_end:
                break
            if not opened and due >= t0:
                while time.perf_counter() < t0:
                    time.sleep(t0 - time.perf_counter())
                open_window()
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            submit(i, due)
    while time.perf_counter() < t_end:
        time.sleep(t_end - time.perf_counter())
    counter.on = pauses.on = False
    pauses.close()
    host_busy["t1"] = engine.metrics.host_busy_s
    return {"rid": rids, "pool": pools, "due": dues, "t_call": calls,
            "t0": t0, "t_end": t_end,
            "traces": counter.traces, "compiles": counter.compiles,
            "gc_full": pauses.count, "gc_full_s": pauses.seconds,
            "host_busy_s": host_busy.get("t1", 0.0) - host_busy.get("t0", 0.0),
            "closed": plan.closed is not None}


def collect(system: System, window: dict) -> List[dict]:
    """Wait for every answer (``LATE_S`` past the close at most) and join
    it to its record.  A request with no answer has status "unanswered"."""
    deadline = window["t_end"] + LATE_S
    rows = []
    for rid, pool, due, t_call in zip(window["rid"], window["pool"],
                                      window["due"], window["t_call"]):
        fut = system.engine.future(rid)
        try:
            res = fut.result(timeout=max(1e-3, deadline - time.perf_counter()))
        except TimeoutError:
            res = None
        row = {"rid": rid, "pool": pool, "due": due, "t_call": t_call,
               "in_window": window["t0"] <= due < window["t_end"]}
        if res is None:
            row["status"] = "unanswered"
        else:
            row.update(status=res.status, e2e_ms=res.e2e_ms,
                       queue_ms=res.queue_ms, bucket=res.bucket,
                       fill=res.batch_fill, logits=res.logits,
                       done=t_call + res.e2e_ms / 1e3)
        rows.append(row)
    return rows


def memory_peak(devices) -> int:
    """Peak bytes on the fullest device: the buffers' peak and the peak of
    the region the TPU runtime reserves for programs' temporaries, which
    ``peak_bytes_in_use`` leaves out (a bucket-32 pass reserves some
    600 MB there beside 50 MB of buffers, measured on a TPU v5e)."""
    def peak(d) -> int:
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) \
            + int(st.get("peak_bytes_reserved", 0))
    return max(peak(d) for d in devices)


# -- correctness --------------------------------------------------------------

def served_pools(rows: List[dict]) -> List[int]:
    """The pool images that some answer of the window was served for."""
    return sorted({r["pool"] for r in rows
                   if r["in_window"] and r["status"] == "ok"})


def reference_logits(config: dict, params: list, plan, image_net,
                     pools: List[int], precision: str) -> Dict[int, np.ndarray]:
    """The plain reference's logits of each pool image in ``pools``."""
    ref = load_reference(config["reference"])
    out: Dict[int, np.ndarray] = {}
    for m, net in enumerate(config["networks"]):
        mine = [p for p in pools if image_net[p] == m]
        if not mine:
            continue
        got = ref.logits(params[m], net, [plan.images[p] for p in mine],
                         precision)
        out.update(zip(mine, got))
    return out


def widest_gaps(config: dict, image_net, rows: List[dict], ref,
                answer: Callable[[dict], np.ndarray]) -> Dict[str, float]:
    """Per network, over every answer of the window: the largest distance
    of its logits (``answer(row)``) from the reference's for its image,
    relative to the reference's largest |logit|."""
    out: Dict[str, float] = {}
    for r in rows:
        if not r["in_window"] or r["status"] != "ok":
            continue
        want = ref[r["pool"]]
        got = np.asarray(answer(r), np.float32)
        g = float(np.abs(got - want).max() / np.abs(want).max())
        net = config["networks"][image_net[r["pool"]]]["zoo"]
        out[net] = max(out.get(net, 0.0), g if np.isfinite(g) else np.inf)
    return out


def gap_shares(served: Dict[str, float], control: Dict[str, float]
               ) -> Dict[str, float]:
    """``logit_gap_share.<net>``: the served answers' widest gap over the
    widest gap of the same answers computed in three bf16 passes.  How far
    float32 rounding carries depends on the seed's weights (a widest gap
    swings fiftyfold between seeds, the control's with it); the share
    does not."""
    out = {}
    for net, g in served.items():
        c = control[net]
        out["logit_gap_share." + net] = (g / c if 0 < c < float("inf")
                                         else float("inf"))
    return out


def judge(config: dict, mix: dict, gap: Dict[str, float], failed: int):
    """Each number compared beside its limit, and whether the run is
    correct: every network the mix sends to was compared, and every
    number is within its limit."""
    nets = config["networks"]
    limits = {"logit_gap_share." + n["zoo"]: n["gap_share_limit"]
              for n in nets}
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in sorted(gap.items())}
    checks["failed"] = {"value": failed, "limit": 0}
    sent = {n["zoo"] for n, w in zip(nets, mix.get("weights", [1] * len(nets)))
            if w > 0}
    correct = ({k.split(".", 1)[1] for k in gap} == sent
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return checks, bool(correct)


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the per-layer metric readers see."""
    cell: dict
    rows: List[dict]
    window: dict
    seconds: float
    chips: int
    peaks: dict
    trace: Optional[dict]
    image_net: np.ndarray        # network index of each pool image

    @property
    def in_window(self) -> List[dict]:
        return [r for r in self.rows if r["in_window"]]


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             backend: Optional[str] = None, cell: Optional[dict] = None,
             wrap_apply: Optional[Callable] = None,
             control: bool = False) -> dict:
    """One run.  ``cell``/``backend`` and ``wrap_apply`` (which wraps the
    registry's apply, to plant a fault) are for the benchmark's own tests
    on the CPU; ``control`` also reads the lower-precision control."""
    import jax
    from chipbench import peaks as peaks_mod
    from chipbench import weights
    cell = cell or load_cell(name)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(devices) < cell["chips"]:
        raise BenchError(f"{name} needs {cell['chips']} chips, JAX sees "
                         f"{len(devices)}")
    peaks = peaks_mod.peaks_for(dev.device_kind) if require_tpu else {}
    config, mix = cell["config"], cell["mix"]
    sys.path.insert(0, os.path.join(ROOT, "src"))

    enable_cache()
    marks = [("start", t_start), ("jax", time.perf_counter())]
    params = weights.make(config, seed, dev)
    marks.append(("weights", time.perf_counter()))
    system = build_system(config, mix, params, cell["chips"], backend)
    if wrap_apply is not None:
        system.registry.apply = wrap_apply(system.registry.apply)
    marks.append(("registry", time.perf_counter()))
    system.engine.warmup()
    marks.append(("warmup", time.perf_counter()))
    nets = config["networks"]
    generate = importlib.import_module("chipbench.traffic."
                                       + mix["generator"])
    plan = generate.build(mix, len(nets), nets[0]["in_channels"], seed,
                          WARM_S + seconds)
    image_net = plan.image_net
    tracer = None
    if trace:
        from chipbench.trace import Tracer
        # the trace ends shortly before the window closes, so that most
        # of the profiler's own work to stop falls after the window
        span = min(TRACE_S, seconds / 2)
        tracer = Tracer(max(0.0, seconds - span - 1.0), span,
                        [d.id for d in system.devices])
    window = drive(system, plan, image_net, seconds, tracer)
    setup_s = window["t0"] - t_start
    marks.append(("warm_traffic", window["t0"]))
    print("setup " + " ".join(f"{b[0]}={b[1] - a[1]:.3f}s"
                              for a, b in zip(marks, marks[1:])),
          file=sys.stderr, flush=True)
    rows = collect(system, window)
    mem = memory_peak(system.devices)
    trace_out = tracer.result() if tracer is not None else None
    system.engine.close()
    snap_host_busy = window["host_busy_s"]
    del system
    gc.collect()

    pools = served_pools(rows)
    t_ref = time.perf_counter()
    ref = reference_logits(config, params, plan, image_net, pools,
                           "highest")
    low = reference_logits(config, params, plan, image_net, pools, "high")
    served = widest_gaps(config, image_net, rows, ref, lambda r: r["logits"])
    lowest = widest_gaps(config, image_net, rows, ref,
                         lambda r: low[r["pool"]])
    print(f"reference images={len(ref)} "
          f"seconds={time.perf_counter() - t_ref:.3f} " + " ".join(
              f"widest_gap.{k}={served[k]!r} bf16x3_widest_gap.{k}="
              f"{lowest[k]!r}" for k in sorted(served)),
          file=sys.stderr, flush=True)
    inw = [r for r in rows if r["in_window"]]
    failed = sum(1 for r in inw if r["status"] != "ok")
    checks, correct = judge(config, mix, gap_shares(served, lowest), failed)
    out = {
        "correct": correct, "attempted": len(inw), "failed": failed,
        "checks": checks, "setup_s": setup_s, "memory_peak_bytes": mem,
        "window": window, "rows": rows, "trace": trace_out,
        "host_busy_s": snap_host_busy, "widest_gap": served,
        "bf16x3_widest_gap": lowest,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": cell["chips"], "memory_peak_bytes": mem},
    }
    if control:
        # the control in the program's place: its answers are ``low``,
        # one for every request the program answered, judged by the same
        # limits and the same rule
        out["control_checks"], out["control_correct"] = judge(
            config, mix, gap_shares(lowest, lowest), 0)
    run = Run(cell, rows, window, seconds, cell["chips"], peaks, trace_out,
              image_net)
    out["run"] = run
    return out


def e2e_metrics(cell: dict, out: dict) -> Dict[str, dict]:
    """The cell's end-to-end metrics, by the host clock."""
    vals = host_clock_values(out)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if m["name"] in vals}


def host_clock_values(out: dict) -> Dict[str, float]:
    """Every end-to-end number a run of this kind gives, by the host
    clock (those its cell does not hold to a bound too)."""
    run: Run = out["run"]
    inw = [r for r in run.in_window if r["status"] == "ok"]
    vals = {"setup_s": out["setup_s"]}
    w = out["window"]
    if w["closed"]:
        done = sum(1 for r in run.rows if r["status"] == "ok"
                   and w["t0"] <= r["done"] < w["t_end"])
        vals["images_per_s"] = done / run.seconds
    else:
        lat = np.array([(r["t_call"] - r["due"]) * 1e3 + r["e2e_ms"]
                        for r in inw])
        if len(lat):
            vals["latency_p50_ms"] = float(np.percentile(lat, 50))
            vals["latency_p95_ms"] = float(np.percentile(lat, 95))
    return vals


def per_layer_metrics(cell: dict, out: dict) -> Dict[str, dict]:
    res = {}
    for m in cell["per_layer"]:
        v = load_metric(m["name"])(out["run"])
        if v is not None:
            res[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return res
