"""FuSeConv: Fully-Separable Convolutions (Ganesan & Kumar, 2021).

The paper factorizes the depthwise K x K convolution of a depthwise-separable
block fully into independent 1-D convolutions:

  * FuSe-Full (D=1): every input channel is convolved with BOTH a Kx1 row
    filter and a 1xK column filter -> 2C output channels.
  * FuSe-Half (D=2, the default drop-in): the first C/2 channels get Kx1 row
    filters, the remaining C/2 get 1xK column filters -> C output channels.

Everything here is NHWC.  ``w_row`` has shape (K, C_r) — a Kx1 filter per
channel (convolves along H); ``w_col`` has shape (K, C_c) — a 1xK filter per
channel (convolves along W).  All functions are pure and jit-friendly.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Primitive convolutions (NHWC).
# ---------------------------------------------------------------------------

def _pad_for(extent: int, k: int, stride: int, padding: str):
    """(out_len, pad_lo, pad_hi) of lax's 'SAME' (low side gets
    ``pad_total // 2``) or 'VALID' padding."""
    if padding == "VALID":
        return (extent - k) // stride + 1, 0, 0
    assert padding == "SAME", padding
    out_len = -(-extent // stride)
    pad_total = max(0, (out_len - 1) * stride + k - extent)
    return out_len, pad_total // 2, pad_total - pad_total // 2


def conv2d(x: Array, w: Array, *, stride: int = 1, padding: str = "SAME") -> Array:
    """Standard convolution.  x: (B,H,W,Cin), w: (Kh,Kw,Cin,Cout).

    Computed as shifted-window patches @ weights (im2col): the same sum
    as ``lax.conv_general_dilated``.  For a network's stem (batch 1, fp32
    precision, a Pallas kernel reading the output) the TPU compiler took
    over a minute on the direct convolution and seconds on this form.
    """
    kh, kw, cin, cout = w.shape
    _, h, wd, _ = x.shape
    oh, lo_h, hi_h = _pad_for(h, kh, stride, padding)
    ow, lo_w, hi_w = _pad_for(wd, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    taps = [xp[:, i:i + (oh - 1) * stride + 1:stride,
               j:j + (ow - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(taps, axis=-1)      # features (kh, kw, cin)
    return jnp.einsum("bhwp,po->bhwo", patches,
                      w.reshape(kh * kw * cin, cout))


def depthwise_conv2d(x: Array, w: Array, *, stride: int = 1,
                     padding: str = "SAME") -> Array:
    """Depthwise convolution.  x: (B,H,W,C), w: (K,K,C)."""
    k0, k1, c = w.shape
    w4 = w.reshape(k0, k1, 1, c)
    return jax.lax.conv_general_dilated(
        x, w4, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
    )


def pointwise_conv2d(x: Array, w: Array) -> Array:
    """1x1 convolution == per-pixel matmul.  x: (B,H,W,Cin), w: (Cin,Cout)."""
    return jnp.einsum("bhwi,io->bhwo", x, w)


def fuse_conv1d_rows(x: Array, w_row: Array, *, stride: int = 1,
                     padding: str = "SAME") -> Array:
    """Bank of independent Kx1 (vertical) 1-D convolutions.

    x: (B,H,W,C), w_row: (K, C).  Output: (B,H',W',C) where the W axis is
    subsampled by ``stride`` as well so the op stays a drop-in for a strided
    depthwise conv.
    """
    k, c = w_row.shape
    w4 = w_row.reshape(k, 1, 1, c)
    return jax.lax.conv_general_dilated(
        x, w4, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
    )


def fuse_conv1d_cols(x: Array, w_col: Array, *, stride: int = 1,
                     padding: str = "SAME") -> Array:
    """Bank of independent 1xK (horizontal) 1-D convolutions."""
    k, c = w_col.shape
    w4 = w_col.reshape(1, k, 1, c)
    return jax.lax.conv_general_dilated(
        x, w4, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
    )


def fuse_conv2d_half(x: Array, w_row: Array, w_col: Array, *, stride: int = 1,
                     padding: str = "SAME") -> Array:
    """FuSe-Half: row filters on channels [:C/2], column filters on [C/2:].

    x: (B,H,W,C); w_row: (K, C//2); w_col: (K, C - C//2).
    Output: (B,H',W',C) — same channel count, a drop-in for depthwise KxK.
    """
    c = x.shape[-1]
    c_r = w_row.shape[-1]
    assert c_r + w_col.shape[-1] == c, (w_row.shape, w_col.shape, c)
    y_r = fuse_conv1d_rows(x[..., :c_r], w_row, stride=stride, padding=padding)
    y_c = fuse_conv1d_cols(x[..., c_r:], w_col, stride=stride, padding=padding)
    return jnp.concatenate([y_r, y_c], axis=-1)


def fuse_conv2d_full(x: Array, w_row: Array, w_col: Array, *, stride: int = 1,
                     padding: str = "SAME") -> Array:
    """FuSe-Full: every channel gets both a row and a column filter -> 2C.

    x: (B,H,W,C); w_row: (K, C); w_col: (K, C).  Output: (B,H',W',2C).
    """
    c = x.shape[-1]
    assert w_row.shape[-1] == c and w_col.shape[-1] == c
    y_r = fuse_conv1d_rows(x, w_row, stride=stride, padding=padding)
    y_c = fuse_conv1d_cols(x, w_col, stride=stride, padding=padding)
    return jnp.concatenate([y_r, y_c], axis=-1)


# ---------------------------------------------------------------------------
# Temporal (sequence) form — the operator's natural primitive.  Used by the
# LM-side hybrid blocks (RG-LRU / xLSTM conv front-ends), see DESIGN.md §4.
# ---------------------------------------------------------------------------

def fuse_conv1d_temporal(x: Array, w: Array, *, causal: bool = True) -> Array:
    """Bank of independent temporal 1-D convolutions (depthwise over time).

    x: (B, T, C), w: (K, C).  Causal 'SAME' padding by default (pad left
    K-1) so position t sees x[t-K+1 .. t] — the standard conv front-end of
    RG-LRU / Mamba / xLSTM blocks.  This is exactly the FuSeConv primitive:
    B*C independent length-T 1-D convolutions.
    """
    k, c = w.shape
    pad = (k - 1, 0) if causal else ((k - 1) // 2, k // 2)
    w4 = w.reshape(k, 1, c)  # (T-window, 1, C)
    return jax.lax.conv_general_dilated(
        x, w4, window_strides=(1,), padding=[pad],
        dimension_numbers=("NTC", "TIO", "NTC"), feature_group_count=c,
    )


def fuse_conv1d_temporal_step(state: Array, x_t: Array, w: Array
                              ) -> Tuple[Array, Array]:
    """Single decode step of the causal temporal conv.

    state: (B, K-1, C) last K-1 inputs; x_t: (B, C).  Returns (new_state, y_t).
    """
    k, _ = w.shape
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B,K,C)
    y_t = jnp.einsum("bkc,kc->bc", window, w)
    return window[:, 1:, :], y_t


# ---------------------------------------------------------------------------
# Parameter containers + init.
# ---------------------------------------------------------------------------

VARIANTS = ("depthwise", "fuse_half", "fuse_full", "scaffold")


# ---------------------------------------------------------------------------
# NOS weight derivation (paper §4.1): FuSe filters are linear projections of
# the depthwise teacher kernel through a shared KxK adapter:
#   row filter (Kx1, channel c) = A @ T_w[:, mid, c]   (middle column)
#   col filter (1xK, channel c) = A @ T_w[mid, :, c]   (middle row)
# One adapter per layer, shared across row/col and across all channels
# (only K^2 extra trainable params per scaffolded layer).
# ---------------------------------------------------------------------------

def derive_fuse_from_teacher(dw: Array, adapter: Array,
                             variant: str = "fuse_half") -> dict:
    """dw: (K,K,C) teacher depthwise kernel; adapter: (K,K)."""
    k = dw.shape[0]
    mid = k // 2
    rows_src = dw[:, mid, :]            # (K, C): middle column per channel
    cols_src = dw[mid, :, :]            # (K, C): middle row per channel
    r_full = adapter @ rows_src         # (K, C)
    c_full = adapter @ cols_src
    c = dw.shape[-1]
    if variant == "fuse_half":
        c_r = c // 2
        return {"row": r_full[:, :c_r], "col": c_full[:, c_r:]}
    return {"row": r_full, "col": c_full}


@dataclasses.dataclass(frozen=True)
class SpatialOpSpec:
    """Which operator realizes the KxK spatial stage of a separable block."""
    variant: str           # one of VARIANTS
    kernel: int            # K
    channels: int          # C (input channels of the spatial stage)
    stride: int = 1

    def __post_init__(self):
        assert self.variant in VARIANTS, self.variant

    @property
    def out_channels(self) -> int:
        return 2 * self.channels if self.variant == "fuse_full" else self.channels

    def param_count(self) -> int:
        k, c = self.kernel, self.channels
        if self.variant == "depthwise":
            return k * k * c
        if self.variant == "fuse_half":
            return k * c           # K per channel (C/2 rows + C/2 cols)
        if self.variant == "scaffold":
            return k * k * c + k * k   # teacher kernel + shared adapter
        return 2 * k * c           # fuse_full

    def macs(self, out_h: int, out_w: int) -> int:
        k, c = self.kernel, self.channels
        if self.variant == "depthwise":
            return out_h * out_w * c * k * k
        if self.variant == "fuse_half":
            return out_h * out_w * c * k
        return out_h * out_w * 2 * c * k


def init_spatial_op(key: Array, spec: SpatialOpSpec, dtype=jnp.float32) -> dict:
    k, c = spec.kernel, spec.channels
    fan_in = k * k if spec.variant == "depthwise" else k
    scale = float(np.sqrt(2.0 / fan_in))
    if spec.variant == "depthwise":
        return {"dw": jax.random.normal(key, (k, k, c), dtype) * scale}
    if spec.variant == "fuse_half":
        kr, kc = jax.random.split(key)
        c_r = c // 2
        return {"row": jax.random.normal(kr, (k, c_r), dtype) * scale,
                "col": jax.random.normal(kc, (k, c - c_r), dtype) * scale}
    if spec.variant == "scaffold":
        scale_dw = float(np.sqrt(2.0 / (k * k)))
        return {"dw": jax.random.normal(key, (k, k, c), dtype) * scale_dw,
                "adapter": jnp.eye(k, dtype=dtype),
                "choice": jnp.zeros((), dtype)}
    kr, kc = jax.random.split(key)
    return {"row": jax.random.normal(kr, (k, c), dtype) * scale,
            "col": jax.random.normal(kc, (k, c), dtype) * scale}


def apply_spatial_op(params: dict, spec: SpatialOpSpec, x: Array,
                     padding: str = "SAME") -> Array:
    if spec.variant == "depthwise":
        return depthwise_conv2d(x, params["dw"], stride=spec.stride,
                                padding=padding)
    if spec.variant == "scaffold":
        # NOS scaffolded stage: compute both the teacher (depthwise) and the
        # adapter-derived FuSe-Half paths, select at runtime.  Both paths in
        # the graph keeps jit stable across per-step operator sampling.
        y_dw = depthwise_conv2d(x, params["dw"], stride=spec.stride,
                                padding=padding)
        derived = derive_fuse_from_teacher(params["dw"], params["adapter"],
                                           "fuse_half")
        y_fuse = fuse_conv2d_half(x, derived["row"], derived["col"],
                                  stride=spec.stride, padding=padding)
        choice = params["choice"].astype(y_dw.dtype)
        return choice * y_fuse + (1.0 - choice) * y_dw
    if spec.variant == "fuse_half":
        return fuse_conv2d_half(x, params["row"], params["col"],
                                stride=spec.stride, padding=padding)
    return fuse_conv2d_full(x, params["row"], params["col"],
                            stride=spec.stride, padding=padding)
