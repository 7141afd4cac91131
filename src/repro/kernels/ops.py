"""jit'd model-facing wrappers around the Pallas kernels.

On CPU the kernels run in ``interpret=True`` mode (Python semantics,
bit-equivalent block schedule); on TPU the resolved ``Backend.interpret``
(False for ``pallas_tpu``) must be threaded through — every wrapper takes
``interpret=None`` meaning "resolve by platform"
(``backend.resolve_interpret``: interpret on CPU only), never a hardcoded
mode.  The wrappers own
layout plumbing: padding, chunking long sequences into VMEM-sized tiles, and
the 2-D row/column transposes that reduce FuSe-2D to the fuse1d primitive.

The fused FuSeConv megakernel and the depthwise KxK kernel live in
``repro.kernels.fused`` and are re-exported here (``fuseconv_fused``,
``depthwise_kxk``) so ``zoo.apply_network`` has a single kernel namespace.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import backend as kb
from repro.kernels import fuse1d as _fuse1d
from repro.kernels import fused as _fused
from repro.kernels import matmul as _matmul

# Re-exported fused kernels (zoo dispatches through this module so the
# dispatch-spy test can pin what actually runs).
fuseconv_fused = _fused.fuseconv_fused
depthwise_kxk = _fused.depthwise_kxk

# Canonical SAME-padding split (XLA-compatible) shared with fused.py.
_same_pad = _fused.same_pad

# Chunk length for the fuse1d T axis: keeps (Tc+K-1, 128) fp32 tiles ~4 MB.
MAX_T_CHUNK = 8192


def fuse_conv1d_temporal(x: jax.Array, w: jax.Array, *, causal: bool = True,
                         interpret: Optional[bool] = None,
                         block_c: int = _fuse1d.DEFAULT_BLOCK_C) -> jax.Array:
    """Depthwise temporal conv via the fuse1d kernel.  x: (B,T,C), w: (K,C)."""
    interpret = kb.resolve_interpret(interpret)
    b, t, c = x.shape
    k = w.shape[0]
    pad = (k - 1, 0) if causal else ((k - 1) // 2, k - (k - 1) // 2 - 1)
    x_pad = jnp.pad(x, ((0, 0), pad, (0, 0)))
    if t <= MAX_T_CHUNK:
        return _fuse1d.fuse1d(x_pad, w, block_c=block_c, interpret=interpret)
    # Split long sequences into overlapping chunks folded into the N axis.
    n_chunks = -(-t // MAX_T_CHUNK)
    t_pad = n_chunks * MAX_T_CHUNK - t
    x_pad = jnp.pad(x_pad, ((0, 0), (0, t_pad), (0, 0)))
    starts = jnp.arange(n_chunks) * MAX_T_CHUNK
    chunks = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(x_pad, s, MAX_T_CHUNK + k - 1,
                                               axis=1),
        out_axes=1)(starts)                      # (B, n_chunks, Tc+K-1, C)
    chunks = chunks.reshape(b * n_chunks, MAX_T_CHUNK + k - 1, c)
    y = _fuse1d.fuse1d(chunks, w, block_c=block_c, interpret=interpret)
    y = y.reshape(b, n_chunks * MAX_T_CHUNK, c)
    return y[:, :t, :]


def fuse_conv2d_rows(x: jax.Array, w_row: jax.Array, *, stride: int = 1,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Kx1 (vertical) bank via fuse1d.  x: (B,H,W,C), w_row: (K,C)."""
    interpret = kb.resolve_interpret(interpret)
    b, h, wdim, c = x.shape
    # conv along H: fold W into the problem axis -> (B*W, H, C)
    xt = x.transpose(0, 2, 1, 3).reshape(b * wdim, h, c)
    k = w_row.shape[0]
    out_h, lo, hi = _same_pad(h, k, stride)
    x_pad = jnp.pad(xt, ((0, 0), (lo, hi), (0, 0)))
    y = _fuse1d.fuse1d(x_pad, w_row, interpret=interpret)  # (B*W, T, C)
    t = y.shape[1]
    y = y.reshape(b, wdim, t, c).transpose(0, 2, 1, 3)
    if stride > 1:
        y = y[:, ::stride, ::stride, :]
    return y[:, :out_h]


def fuse_conv2d_cols(x: jax.Array, w_col: jax.Array, *, stride: int = 1,
                     interpret: Optional[bool] = None) -> jax.Array:
    """1xK (horizontal) bank via fuse1d.  x: (B,H,W,C), w_col: (K,C)."""
    interpret = kb.resolve_interpret(interpret)
    b, h, wdim, c = x.shape
    xt = x.reshape(b * h, wdim, c)
    k = w_col.shape[0]
    out_w, lo, hi = _same_pad(wdim, k, stride)
    x_pad = jnp.pad(xt, ((0, 0), (lo, hi), (0, 0)))
    y = _fuse1d.fuse1d(x_pad, w_col, interpret=interpret)
    y = y.reshape(b, h, y.shape[1], c)
    if stride > 1:
        y = y[:, ::stride, ::stride, :]
    return y[:, :, :out_w]


def fuse_conv2d_half(x: jax.Array, w_row: jax.Array, w_col: jax.Array, *,
                     stride: int = 1,
                     interpret: Optional[bool] = None) -> jax.Array:
    interpret = kb.resolve_interpret(interpret)
    c_r = w_row.shape[-1]
    y_r = fuse_conv2d_rows(x[..., :c_r], w_row, stride=stride,
                           interpret=interpret)
    y_c = fuse_conv2d_cols(x[..., c_r:], w_col, stride=stride,
                           interpret=interpret)
    return jnp.concatenate([y_r, y_c], axis=-1)


def fuse_conv2d_full(x: jax.Array, w_row: jax.Array, w_col: jax.Array, *,
                     stride: int = 1,
                     interpret: Optional[bool] = None) -> jax.Array:
    """FuSe-Full: every channel gets a row AND a column filter -> 2C out."""
    interpret = kb.resolve_interpret(interpret)
    y_r = fuse_conv2d_rows(x, w_row, stride=stride, interpret=interpret)
    y_c = fuse_conv2d_cols(x, w_col, stride=stride, interpret=interpret)
    return jnp.concatenate([y_r, y_c], axis=-1)


def pointwise(x: jax.Array, w: jax.Array, *,
              interpret: Optional[bool] = None) -> jax.Array:
    """1x1 conv via the MXU matmul kernel.  x: (..., Cin), w: (Cin, Cout)."""
    interpret = kb.resolve_interpret(interpret)
    lead = x.shape[:-1]
    y = _matmul.matmul(x.reshape(-1, x.shape[-1]), w, interpret=interpret)
    return y.reshape(*lead, w.shape[-1])
