"""Pallas TPU kernel: MXU-tiled matmul (the 1x1 pointwise stage of FuSe blocks).

Output-stationary accumulation — the grid's innermost axis walks the K
(reduction) dimension and an fp32 accumulator stays resident in VMEM scratch
(the "output stationary in the PEs" of the paper's §3.3, at MXU-tile
granularity).  128-aligned blocks map onto the 128x128 MXU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb


def _matmul_kernel(a_ref, b_ref, y_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fp32 contraction on the MXU, as in interpret mode (the TPU default
    # for f32 operands may round them to bf16)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                            b_ref[...].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: Optional[bool] = None) -> jax.Array:
    """y = a @ b with fp32 VMEM-scratch accumulation.  a: (M,K), b: (K,N).

    ``interpret`` resolves through ``backend.resolve_interpret``: the
    Python interpreter on CPU, the compiled kernel on TPU.
    """
    interpret = kb.resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = -m % bm, -n % bn, -k % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gn, gk = (m + pm) // bm, (n + pn) // bn, (k + pk) // bk
    y = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return y[:m, :n]
