"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

Functions only — importing this module never touches jax device state.

Multi-process serving adds :func:`make_multiprocess_data_mesh`: a global
1-D ``"data"`` universe over every process's devices with a process-local
addressable shard.  Compute in the serving mesh stays process-local (see
``launch/distributed.py`` coordination mode), so the global universe is a
*logical* construct: :class:`LogicalDevice` entries carry a stable global
id plus their owning process and local device index, and the universe is
ordered round-robin across processes — position ``j`` belongs to process
``j % P``.  With every device-group size a multiple of P (the cost
model's ``group_granularity``), any contiguous aligned slice of the
universe gives each process an equal stripe of *identical local device
ids* — which is what makes coordinator-warmed persistent-cache entries
hit bitwise on every worker.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import List, NamedTuple, Sequence, Tuple

import jax


class LogicalDevice(NamedTuple):
    """One slot in the global serving universe.  ``id`` is the stable
    global id (``process * n_local + local``) used in warmup manifests
    and round specs; ``process``/``local`` locate the physical device."""

    id: int
    process: int
    local: int


@dataclass(frozen=True)
class MultiprocessDataMesh:
    """Global 1-D data universe + this process's addressable shard."""

    local_mesh: object  # jax Mesh over this process's devices
    num_processes: int
    process_id: int
    n_local: int
    universe: Tuple[LogicalDevice, ...] = field(default=())

    @property
    def global_size(self) -> int:
        return self.num_processes * self.n_local

    @property
    def universe_ids(self) -> Tuple[int, ...]:
        return tuple(d.id for d in self.universe)

    def local_devices(self) -> Tuple:
        """This process's physical jax devices, local-index order."""
        return tuple(self.local_mesh.devices.flat)

    def by_id(self, ids: Sequence[int]) -> Tuple[LogicalDevice, ...]:
        table = {d.id: d for d in self.universe}
        return tuple(table[i] for i in ids)

    def stripe(self, group: Sequence[LogicalDevice],
               process_id: int = -1) -> Tuple[Tuple, List[int]]:
        """The addressable shard of ``group`` for one process: its
        physical devices (local-index order) and the positions inside the
        group they own.  For aligned groups the local indices — and hence
        the compiled programs' device assignments — are identical on
        every process."""
        pid = self.process_id if process_id < 0 else process_id
        positions = [j for j, d in enumerate(group) if d.process == pid]
        locals_ = self.local_devices()
        devs = tuple(locals_[group[j].local] for j in positions)
        return devs, positions

    def fingerprint(self) -> str:
        """Topology digest every process must agree on before serving."""
        locals_ = self.local_devices()
        blob = "|".join([
            str(self.num_processes), str(self.n_local),
            locals_[0].platform if locals_ else "none",
            ",".join(str(d.id) for d in locals_),
            ",".join(f"{d.id}:{d.process}:{d.local}"
                     for d in self.universe),
        ])
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def describe(self) -> dict:
        return {
            "num_processes": self.num_processes,
            "process_id": self.process_id,
            "n_local": self.n_local,
            "global_size": self.global_size,
            "mesh_fingerprint": self.fingerprint(),
        }


def logical_universe(num_processes: int,
                     n_local: int) -> Tuple[LogicalDevice, ...]:
    """The global device universe in round-robin (process-interleaved)
    order: position ``j`` -> (process ``j % P``, local ``j // P``).  Any
    contiguous slice whose offset and length are multiples of P then
    spans all processes with equal, identically-numbered local stripes."""
    out = []
    for j in range(num_processes * n_local):
        p, l = j % num_processes, j // num_processes
        out.append(LogicalDevice(id=p * n_local + l, process=p, local=l))
    return tuple(out)


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes.  jax 0.9 defaults new meshes to
    Explicit axis types, under which a gather over a sharded operand
    (the embedding lookup in ``models/model.py``) must name its output
    sharding; this repo relies on the compiler propagating it."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the real local device (CPU smoke paths)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_devices: int = 0):
    """1-D data-parallel mesh over the first ``n_devices`` local devices
    (0 = all).  This is the vision-serving mesh: batches shard over
    ``"data"``, params replicate.  On CPU, virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = n_devices or len(jax.devices())
    assert n <= len(jax.devices()), (n, len(jax.devices()))
    return _make_mesh((n,), ("data",))


def make_multiprocess_data_mesh(num_processes: int, process_id: int,
                                n_local_devices: int = 0
                                ) -> MultiprocessDataMesh:
    """Global 1-D ``"data"`` mesh over all processes' devices, with this
    process's addressable shard as a local jax mesh.

    Every process calls this with the same ``num_processes`` and its own
    ``process_id``; ``n_local_devices`` counts *per-process* devices
    (0 = all local).  On CPU, virtual local devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — 2 processes
    x N virtual devices runs on one CI box.  All processes must bring the
    same per-process device count; agreement is checked by exchanging
    :meth:`MultiprocessDataMesh.fingerprint` at startup."""
    assert 0 <= process_id < num_processes, (process_id, num_processes)
    n = n_local_devices or len(jax.devices())
    local = make_data_mesh(n)
    return MultiprocessDataMesh(
        local_mesh=local, num_processes=num_processes,
        process_id=process_id, n_local=n,
        universe=logical_universe(num_processes, n))


def data_axes(mesh) -> tuple:
    """The axes a global batch is sharded over (pod acts as outer data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
