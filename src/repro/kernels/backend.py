"""Execution-backend selection for the vision/serving stack.

Three ways to run the paper's operators:

  * ``xla``            — the pure-XLA reference path (``repro.core.fuseconv``
                         lax convolutions).  Always available; the
                         correctness oracle for the others.
  * ``pallas``         — the Pallas kernels executed in ``interpret=True``
                         mode (Python semantics; CPU only — selecting it on
                         an accelerator fails at the first kernel call).
  * ``pallas_tpu``     — the same kernels with ``interpret=False``: compiled
                         by Mosaic for the TPU.

A ``Backend`` is a frozen value object threaded through
``repro.vision.zoo.apply_network`` (and anything else that executes
operators) so a single flag flips the whole network between paths without
re-tracing logic scattered across call sites.  ``Backend.interpret`` is the
ONLY source of truth for interpret-vs-compiled: kernel wrappers take
``interpret=None`` and resolve it via :func:`resolve_interpret`, so a call
site that forgets to thread the flag gets the process default instead of a
silently hardcoded ``True`` (which would make ``pallas_tpu`` interpret),
and the resolution refuses interpret mode off CPU.

``Backend.fused`` gates the fused FuSeConv megakernel
(``repro.kernels.fused.fuseconv_fused``): on by default for the pallas
backends (inference only — training needs the decomposed path's separate
BatchNorm), ``*_nofused`` keys pin the decomposed pipeline for
differential testing and bisection.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str                 # "xla" | "pallas"
    interpret: bool = True    # only meaningful for the pallas kernels
    fused: bool = True        # pallas only: use the fused FuSeConv megakernel

    def __post_init__(self):
        assert self.name in ("xla", "pallas"), self.name

    @property
    def use_pallas(self) -> bool:
        return self.name == "pallas"

    @property
    def key(self) -> str:
        """Stable string form (cache keys, CLI round-trips)."""
        if self.name == "pallas":
            base = "pallas" if self.interpret else "pallas_tpu"
            return base if self.fused else base + "_nofused"
        return "xla"


XLA = Backend("xla")
PALLAS = Backend("pallas", interpret=True)
PALLAS_TPU = Backend("pallas", interpret=False)
PALLAS_NOFUSED = Backend("pallas", interpret=True, fused=False)
PALLAS_TPU_NOFUSED = Backend("pallas", interpret=False, fused=False)

_BY_KEY = {"xla": XLA, "pallas": PALLAS, "pallas_interpret": PALLAS,
           "pallas_tpu": PALLAS_TPU, "pallas_nofused": PALLAS_NOFUSED,
           "pallas_tpu_nofused": PALLAS_TPU_NOFUSED}

BACKEND_KEYS = ("xla", "pallas", "pallas_tpu")


def resolve_backend(spec: Union[str, Backend, None]) -> Backend:
    """Accepts a Backend, one of BACKEND_KEYS (plus the ``*_nofused``
    debugging keys), or None (-> XLA reference)."""
    if spec is None:
        return XLA
    if isinstance(spec, Backend):
        return spec
    try:
        return _BY_KEY[spec]
    except KeyError:
        raise ValueError(
            f"unknown backend {spec!r}; expected one of {BACKEND_KEYS}")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret`` argument where the kernel is called.

    Interpret mode runs only where JAX's platform is CPU: ``None`` (nobody
    threaded a Backend here) resolves to ``True`` on CPU and to ``False``
    (compiled) anywhere else, and an explicit ``True`` on an accelerator
    is an error — a kernel that silently ran in the Python interpreter on
    the chip would look like a working, very slow device path.  An
    explicit ``False`` on CPU is allowed: it is how a kernel is compiled
    for a described, unattached TPU (tests/test_tpu_compile.py).
    """
    import jax
    platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform != "cpu":
        raise ValueError(
            f"Pallas interpret mode was requested on platform {platform!r}; "
            f"it runs only on CPU.  Select the 'pallas_tpu' backend (or "
            f"pass interpret=False) to run the compiled kernels.")
    return bool(interpret)
