"""Fused FuSeConv Pallas megakernel + the depthwise KxK baseline kernel.

Two kernels the serving hot path was missing:

``fuseconv_fused``
    One ``pallas_call`` computing a whole FuSeConv spatial stage AND its
    pointwise mix: the Kx1 row bank, the 1xK column bank, the (inference)
    BatchNorm affine, the activation, and the 1x1 channel-mixing matmul.
    The decomposed path (``ops.fuse_conv2d_full``/``_half`` followed by
    ``ops.pointwise``) materializes the ``c_sp``-channel spatial output in
    HBM and reads it back for the matmul — three kernel dispatches and an
    HBM round-trip for the widest tensor in the block.  Here the spatial
    output lives only in VMEM/registers: per block the input tile is read
    once, the mixed output is written once.  This is the ST-OS insight at
    the memory level — the paper's dataflow keeps the 1-D banks' outputs
    stationary in the PEs; the megakernel keeps them stationary in VMEM
    through the pointwise mix as well.

``depthwise_kxk``
    The baseline depthwise KxK operator.  Without it, "depthwise" stages
    silently fell back to XLA even on the ``pallas`` backend, so baseline
    depthwise-separable nets were never actually servable on the Pallas
    path.  K*K shifted broadcast-FMAs per channel slab, same schedule
    family as ``fuse1d``.

Tiling (both kernels): grid over (problem row-tile, channel block).  The
padded input is first split by stride phase (``_phase_split``), so every
tap — strided or not — is a contiguous window of one phase.  The
row-tile axis folds overlapping input row windows into the batch axis on
the host (the same trick ``ops.fuse_conv1d_temporal`` uses for long
sequences) so VMEM holds a bounded ``(row window, W, C)`` slab regardless
of image height, sized by ``_row_plan`` to ``VMEM_BUDGET``; the channel
axis blocks the pointwise *output* channels
for ``fuseconv_fused`` (the spatial intermediate must see all of its
``c_sp`` inputs to mix them) and the depthwise channels for
``depthwise_kxk`` (no cross-channel mixing, so input channels tile
freely, tail blocks zero-padded and sliced away — the same contract
``fuse1d`` pins in tests/test_fuse1d_padding.py).

SAME padding for stride 1/2 follows the XLA split (``same_pad``: low side
gets ``pad_total // 2``) so both kernels stay bit-compatible with the lax
reference path at every extent parity.

``interpret=None`` resolves through ``backend.resolve_interpret`` — the
Backend object threaded by ``zoo.apply_network`` is the only place that
decides interpret vs compiled, so ``pallas_tpu`` actually runs compiled.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend as kb

DEFAULT_BLOCK_C = 128       # depthwise channel block (lane width)
DEFAULT_BLOCK_COUT = 128    # fused-kernel pointwise output-channel block
# VMEM one program may fill with its double-buffered blocks and fp32
# temporaries (as counted by _row_plan).  Half of the TPU compiler's 16 MiB
# default scoped-VMEM limit: XLA may also place a kernel's operands or
# results in VMEM around the call (a 17.3 MiB stride-2 depthwise program
# at 56x56x96 was refused inside MobileNetV2 at batch 8), so the row tile
# leaves it room.  tests/test_tpu_compile.py compiles the zoo's widest
# stages against a described v5e to hold this.
VMEM_BUDGET = 8 * 1024 * 1024

# In-kernel activations (fp32): must mirror repro.vision.layers.ACTS.
ACTS = {
    "linear": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "hswish": lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0,
}


def same_pad(extent: int, k: int, stride: int):
    """XLA 'SAME' padding for a strided conv: (out_len, pad_lo, pad_hi).

    XLA puts ``pad_total // 2`` on the low side; for stride > 1 over an
    even extent that differs from stride-1 centering, so every kernel that
    pads-then-subsamples must use THIS split to match the lax reference.
    """
    out_len = -(-extent // stride)
    pad_total = max(0, (out_len - 1) * stride + k - extent)
    lo = pad_total // 2
    return out_len, lo, pad_total - lo


def _tile_bytes(rows: int, cols: int) -> int:
    """fp32 bytes of a (rows, cols) slab in (8, 128) VMEM tiles."""
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4


def _row_plan(out_h: int, stride: int, k: int, block_h: Optional[int], *,
              in_row: int, out_row: int, fixed: int = 0):
    """(rows per tile, n_tiles, window, window step) for row tiling.

    The window and its step count rows of the phase-split input
    (:func:`_phase_split`): output row ``r`` with tap ``t`` reads phase
    row ``r + t // stride``, so a tile of ``th`` output rows needs
    ``th + (k - 1) // stride`` phase rows, and tiles step by ``th``.

    Without ``block_h`` the tile is the fewest row tiles whose program
    fits :data:`VMEM_BUDGET`: ``2 * in_row`` bytes per window row (the
    double-buffered input block), ``out_row`` bytes per output row (output
    block and temporaries), plus ``fixed`` (weight blocks).  Rows are
    then spread evenly over the tiles so little padding is computed.
    """
    halo = (k - 1) // stride
    if block_h is None:
        room = VMEM_BUDGET - fixed - 2 * in_row * halo
        fit = max(1, room // (2 * in_row + out_row))
        n_tiles = -(-out_h // min(fit, out_h))
        th = -(-out_h // n_tiles)
    else:
        th = max(1, min(block_h, out_h))
    n_tiles = -(-out_h // th)
    return th, n_tiles, th + halo, th


def _phase_split(x_pad: jax.Array, stride: int) -> jax.Array:
    """Space-to-batch by stride phase: (B, Hp, Wp, C) -> (B, s*s, Hq, Wq, C).

    Phase ``p * s + q`` holds ``x_pad[:, p::s, q::s]``, so a strided tap
    at padded offset ``(dy, dx)`` is the CONTIGUOUS window starting at
    ``(dy // s, dx // s)`` of phase ``(dy % s) * s + dx % s``.  Kernel
    bodies then slice only contiguously: Mosaic rejects strided value
    slices ("Only 2D gather is supported").  Rows/columns added to make
    Hp, Wp multiples of s are zero and feed no valid output.
    """
    b, hp, wp, c = x_pad.shape
    s = stride
    if s == 1:
        return x_pad[:, None]
    eh, ew = -hp % s, -wp % s
    if eh or ew:
        x_pad = jnp.pad(x_pad, ((0, 0), (0, eh), (0, ew), (0, 0)))
    hq, wq = (hp + eh) // s, (wp + ew) // s
    x = x_pad.reshape(b, hq, s, wq, s, c).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(b, s * s, hq, wq, c)


def _row_windows(xq: jax.Array, n_tiles: int, win: int, step: int
                 ) -> jax.Array:
    """Fold overlapping phase-row windows into the batch axis.

    xq: (B, P, Hq, Wq, C) -> (B * n_tiles, P, win, Wq, C); window i covers
    phase rows [i*step, i*step + win).  Rows past Hq are zero (they only
    feed output rows that get sliced away).
    """
    b = xq.shape[0]
    need = (n_tiles - 1) * step + win
    extra = need - xq.shape[2]
    if extra > 0:
        xq = jnp.pad(xq, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
    starts = jnp.arange(n_tiles) * step
    wins = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(xq, s, win, axis=2),
        out_axes=1)(starts)                     # (B, n_tiles, P, win, Wq, C)
    return wins.reshape(b * n_tiles, *xq.shape[1:2], win, *xq.shape[3:])


def _tiled_input(x_pad: jax.Array, stride: int, k: int,
                 block_h: Optional[int], out_h: int, *, out_row: int,
                 fixed: int = 0):
    """Phase-split and row-tile a padded input for the kernels below:
    returns (blocks (N, s*s, win, Wq, C), th, n_tiles)."""
    xq = _phase_split(x_pad, stride)
    in_row = stride * stride * _tile_bytes(xq.shape[3], xq.shape[4])
    th, n_tiles, win, step = _row_plan(out_h, stride, k, block_h,
                                       in_row=in_row, out_row=out_row,
                                       fixed=fixed)
    if n_tiles > 1:
        xq = _row_windows(xq, n_tiles, win, step)
    else:
        xq = xq[:, :, :win]
    return xq, th, n_tiles


def _tap(x, stride: int, dy: int, dx: int, th: int, ow: int):
    """(th, ow, C) window of the phase-split block ``x`` (P, win, Wq, C)
    read by the tap at padded offset (dy, dx) — a contiguous slice."""
    ph = (dy % stride) * stride + dx % stride
    y0, x0 = dy // stride, dx // stride
    return x[ph, y0:y0 + th, x0:x0 + ow, :]


# ---------------------------------------------------------------------------
# Fused FuSeConv megakernel: 1-D banks + affine + act + pointwise mix.
# ---------------------------------------------------------------------------

def _fuseconv_fused_kernel(x_ref, wr_ref, wc_ref, g_ref, b_ref, wp_ref,
                           y_ref, *, k: int, stride: int, th: int,
                           out_w: int, lo_h: int, lo_w: int, c_r: int,
                           variant: str, act: str):
    # x_ref: (1, s*s, win, Wq, C); wr/wc: (K, C_row)/(K, C_col);
    # g/b: (1, c_sp); wp_ref: (c_sp, bcout); y_ref: (1, th, out_w, bcout).
    x = x_ref[0].astype(jnp.float32)
    if variant == "fuse_full":
        xr = xc = x
    else:  # fuse_half: row filters on [:c_r], column filters on [c_r:]
        xr, xc = x[..., :c_r], x[..., c_r:]
    wr = wr_ref[...].astype(jnp.float32)
    wc = wc_ref[...].astype(jnp.float32)
    # Kx1 row bank: conv along H, W subsampled at the row-conv column
    # origin lo_w (the decomposed path never pads W for the row bank).
    acc_r = jnp.zeros((th, out_w, xr.shape[-1]), jnp.float32)
    for tap in range(k):  # static unroll: K shifted broadcast-FMAs
        acc_r += _tap(xr, stride, tap, lo_w, th, out_w) * \
            wr[tap][None, None, :]
    # 1xK column bank: conv along W, H subsampled at origin lo_h.
    acc_c = jnp.zeros((th, out_w, xc.shape[-1]), jnp.float32)
    for tap in range(k):
        acc_c += _tap(xc, stride, lo_h, tap, th, out_w) * \
            wc[tap][None, None, :]
    # Spatial output exists only here (VMEM) — never written to HBM.
    y_sp = jnp.concatenate([acc_r, acc_c], axis=-1)        # (th, out_w, c_sp)
    y_sp = y_sp * g_ref[0][None, None, :] + b_ref[0][None, None, :]
    y_sp = ACTS[act](y_sp)
    wp = wp_ref[...].astype(jnp.float32)
    # fp32 contraction on the MXU, as in interpret mode (the TPU default
    # for f32 operands may round them to bf16)
    y = jnp.dot(y_sp.reshape(th * out_w, -1), wp,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    y_ref[0] = y.reshape(th, out_w, -1).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "variant", "stride", "act", "block_cout", "block_h", "interpret"))
def fuseconv_fused(x: jax.Array, w_row: jax.Array, w_col: jax.Array,
                   w_pw: jax.Array, *, variant: str = "fuse_full",
                   stride: int = 1, scale: Optional[jax.Array] = None,
                   bias: Optional[jax.Array] = None, act: str = "linear",
                   block_cout: int = DEFAULT_BLOCK_COUT,
                   block_h: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """FuSeConv block in one kernel: 1-D banks -> affine -> act -> 1x1 mix.

    x: (B, H, W, C) NHWC.  w_row: (K, C_row), w_col: (K, C_col) with
    C_row = C_col = C for ``fuse_full`` (c_sp = 2C) and C_row + C_col = C
    for ``fuse_half`` (c_sp = C).  w_pw: (c_sp, Cout).  ``scale``/``bias``
    (each (c_sp,), optional) fold an inference-mode BatchNorm between the
    spatial banks and the mix; ``act`` applies after the affine.  Output:
    (B, H', W', Cout), SAME padding, stride 1 or 2.

    Semantics contract (pinned by tests/test_backend_conformance.py):
      act(affine(concat([row_bank, col_bank]))) @ w_pw
    == the decomposed ``fuse_conv2d_{full,half}`` + BN + act + ``pointwise``
    pipeline, within fp32 tolerance.
    """
    assert variant in ("fuse_half", "fuse_full"), variant
    interpret = kb.resolve_interpret(interpret)
    b, h, w, c = x.shape
    k = w_row.shape[0]
    assert w_col.shape[0] == k, (w_row.shape, w_col.shape)
    c_r = w_row.shape[1]
    if variant == "fuse_full":
        assert c_r == c and w_col.shape[1] == c, (w_row.shape, x.shape)
        c_sp = 2 * c
    else:
        assert c_r + w_col.shape[1] == c, (w_row.shape, w_col.shape, c)
        c_sp = c
    assert w_pw.shape[0] == c_sp, (w_pw.shape, c_sp)
    cout = w_pw.shape[1]
    g = jnp.ones((c_sp,), x.dtype) if scale is None else scale
    bb = jnp.zeros((c_sp,), x.dtype) if bias is None else bias
    g = g.reshape(1, c_sp).astype(jnp.float32)
    bb = bb.reshape(1, c_sp).astype(jnp.float32)

    out_h, lo_h, hi_h = same_pad(h, k, stride)
    out_w, lo_w, hi_w = same_pad(w, k, stride)
    x_pad = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    bcout = max(1, min(block_cout, cout))
    cout_pad = -cout % bcout
    # per output row: output block (x2) + the two bank accumulators, the
    # concatenated spatial row and the mixed row (fp32 temporaries)
    out_row = (3 * _tile_bytes(out_w, bcout) + 2 * _tile_bytes(out_w, c)
               + _tile_bytes(out_w, c_sp))
    fixed = 2 * (_tile_bytes(c_sp, bcout) + 2 * _tile_bytes(k, c)
                 + 2 * _tile_bytes(1, c_sp))
    xq, th, n_tiles = _tiled_input(x_pad, stride, k, block_h, out_h,
                                   out_row=out_row, fixed=fixed)
    n, n_ph, win, wq = xq.shape[:4]

    w_pw_p = jnp.pad(w_pw, ((0, 0), (0, cout_pad))) if cout_pad else w_pw

    grid = (n, (cout + cout_pad) // bcout)
    y = pl.pallas_call(
        functools.partial(_fuseconv_fused_kernel, k=k, stride=stride, th=th,
                          out_w=out_w, lo_h=lo_h, lo_w=lo_w, c_r=c_r,
                          variant=variant, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_ph, win, wq, c),
                         lambda i, j: (i, 0, 0, 0, 0)),
            pl.BlockSpec(w_row.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(w_col.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((1, c_sp), lambda i, j: (0, 0)),
            pl.BlockSpec((1, c_sp), lambda i, j: (0, 0)),
            pl.BlockSpec((c_sp, bcout), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, th, out_w, bcout),
                               lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, th, out_w, cout + cout_pad),
                                       x.dtype),
        interpret=interpret,
    )(xq, w_row, w_col, g, bb, w_pw_p)
    if n_tiles > 1:
        y = y.reshape(b, n_tiles * th, out_w, cout + cout_pad)
    y = y[:, :out_h]
    return y[..., :cout] if cout_pad else y


# ---------------------------------------------------------------------------
# Depthwise KxK kernel: the baseline operator, finally servable on Pallas.
# ---------------------------------------------------------------------------

def _depthwise_kxk_kernel(x_ref, w_ref, y_ref, *, k: int, stride: int,
                          th: int, out_w: int):
    # x_ref: (1, s*s, win, Wq, bc); w_ref: (K, K, bc);
    # y_ref: (1, th, out_w, bc)
    x = x_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.zeros((th, out_w, x.shape[-1]), jnp.float32)
    for ty in range(k):      # static unroll: K*K shifted broadcast-FMAs
        for tx in range(k):
            acc += _tap(x, stride, ty, tx, th, out_w) * \
                w[ty, tx][None, None, :]
    y_ref[0] = acc.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "stride", "block_c", "block_h", "interpret"))
def depthwise_kxk(x: jax.Array, w: jax.Array, *, stride: int = 1,
                  block_c: int = DEFAULT_BLOCK_C,
                  block_h: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Depthwise KxK conv.  x: (B, H, W, C), w: (K, K, C); SAME padding,
    stride 1 or 2.  Matches ``repro.core.fuseconv.depthwise_conv2d``."""
    interpret = kb.resolve_interpret(interpret)
    b, h, wdim, c = x.shape
    kh, kw, cw = w.shape
    assert kh == kw and cw == c, (w.shape, x.shape)
    out_h, lo_h, hi_h = same_pad(h, kh, stride)
    out_w, lo_w, hi_w = same_pad(wdim, kw, stride)
    x_pad = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))

    bc = max(1, min(block_c, c))
    c_pad = -c % bc
    if c_pad:  # tail block: zero-pad channels up to a lane multiple
        x_pad = jnp.pad(x_pad, ((0, 0), (0, 0), (0, 0), (0, c_pad)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, c_pad)))
    # per output row: output block (x2) + the accumulator
    out_row = 3 * _tile_bytes(out_w, bc)
    xq, th, n_tiles = _tiled_input(
        x_pad, stride, kh, block_h, out_h, out_row=out_row,
        fixed=2 * kh * _tile_bytes(kw, bc))
    n, n_ph, win, wq = xq.shape[:4]

    grid = (n, (c + c_pad) // bc)
    y = pl.pallas_call(
        functools.partial(_depthwise_kxk_kernel, k=kh, stride=stride, th=th,
                          out_w=out_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_ph, win, wq, bc),
                         lambda i, j: (i, 0, 0, 0, j)),
            pl.BlockSpec((kh, kw, bc), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, th, out_w, bc),
                               lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, th, out_w, c + c_pad), x.dtype),
        interpret=interpret,
    )(xq, w)
    if n_tiles > 1:
        y = y.reshape(b, n_tiles * th, out_w, c + c_pad)
    y = y[:, :out_h]
    return y[..., :c] if c_pad else y
