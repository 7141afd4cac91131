"""Pallas TPU kernel for FuSeConv's primitive: a bank of independent 1-D convs.

This is the ST-OS dataflow adapted to the TPU memory hierarchy (DESIGN.md §3):

  * the paper maps each independent 1-D convolution to one systolic-array
    ROW and broadcasts the K taps to all PEs in the row;
  * here, each independent problem occupies one SUBLANE row of a VMEM tile
    ((T, C) layout: sublanes = time, lanes = channels), and each tap
    ``w[k, c]`` is broadcast across the whole T axis by the VPU — the
    broadcast register plays the role of the paper's per-row weight link;
  * the input tile is DMA'd HBM->VMEM once and reused for all K taps
    (K shifted fused multiply-adds), so the op runs at the HBM roofline
    instead of paying im2col's K x replication.

Layout: x_pad (N, T + K - 1, C)  — already padded by the wrapper (ops.py),
        w     (K, C)            — per-channel taps,
        y     (N, T, C).
Grid: (N, C / block_c); each program owns the full (padded) T extent of one
problem batch and a 128-aligned channel slab.  K is static (3/5/7 in the
paper's networks, 4 in RG-LRU / xLSTM front-ends).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend as kb

DEFAULT_BLOCK_C = 128


def _fuse1d_kernel(x_ref, w_ref, y_ref, *, k: int, t: int):
    # x_ref: (1, T+K-1, Cb); w_ref: (K, Cb); y_ref: (1, T, Cb)
    acc = jnp.zeros(y_ref.shape[1:], dtype=jnp.float32)
    for tap in range(k):  # static unroll: K shifted broadcast-FMAs
        acc += x_ref[0, tap:tap + t, :].astype(jnp.float32) * \
            w_ref[tap, :].astype(jnp.float32)[None, :]
    y_ref[0] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fuse1d(x_pad: jax.Array, w: jax.Array, *, block_c: int = DEFAULT_BLOCK_C,
           interpret: Optional[bool] = None) -> jax.Array:
    """Bank of independent 1-D convolutions.

    x_pad: (N, T + K - 1, C) pre-padded inputs; w: (K, C).
    Returns y: (N, T, C) with y[n, t, c] = sum_k x_pad[n, t + k, c] * w[k, c].

    ``interpret`` resolves through ``backend.resolve_interpret``: the
    Python interpreter on CPU, the compiled kernel on TPU.
    """
    interpret = kb.resolve_interpret(interpret)
    n, tp, c = x_pad.shape
    k, cw = w.shape
    assert cw == c, (w.shape, x_pad.shape)
    t = tp - k + 1
    assert t >= 1
    bc = min(block_c, c)
    # pad channels up to a lane multiple
    c_pad = -c % bc
    if c_pad:
        x_pad = jnp.pad(x_pad, ((0, 0), (0, 0), (0, c_pad)))
        w = jnp.pad(w, ((0, 0), (0, c_pad)))
    grid = (n, (c + c_pad) // bc)
    y = pl.pallas_call(
        functools.partial(_fuse1d_kernel, k=k, t=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((k, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, t, bc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, t, c + c_pad), x_pad.dtype),
        interpret=interpret,
    )(x_pad, w)
    return y[..., :c] if c_pad else y
