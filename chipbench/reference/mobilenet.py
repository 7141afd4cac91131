"""Plain float32 reference of the MobileNet family with FuSe spatial
stages, in straightforward ``jax.numpy``: no kernels, no batching logic,
no code of the program.  It follows the published descriptions
(MobileNetV2, arXiv:1801.04381; MobileNetV3, arXiv:1905.02244; FuSeConv,
arXiv:2108.11441):

* stem / ConvBN: KxK convolution, SAME padding, BatchNorm, activation;
* inverted residual: 1x1 expand + BN + activation (when the expansion
  differs from the input width), the spatial stage (FuSe-half: Kx1 row
  filters on the first C/2 channels and 1xK column filters on the rest;
  FuSe-full: both on every channel, concatenated; depthwise: KxK per
  channel), BN + activation, squeeze-and-excite (mean over the image,
  dense + ReLU, dense + hard sigmoid, scale) where the block has it, 1x1
  project + BN, and the residual when stride is 1 and widths match;
* head: mean over the image, an optional dense + activation, the
  classifier.

SAME padding puts ``pad_total // 2`` on the low side, as XLA does, and a
strided Kx1 (1xK) filter subsamples the other axis at the same stride.

``precision`` is "highest" (float32 products, the configuration's
stated precision) or "high": every contraction in three bf16 passes
(the high and the low bf16 halves of each operand, the low-times-low
term dropped), which is what ``Precision.HIGH`` does on a TPU, written
out so that it computes the same on any backend.  Elementwise products
(the spatial banks) stay float32 in both: a lower matmul precision does
not touch them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def letterbox(img: np.ndarray, res: int) -> np.ndarray:
    """Centre-crop each side longer than ``res``, then zero-pad each side
    shorter than ``res`` equally (the odd pixel below/right)."""
    h, w, _ = img.shape
    if h > res:
        t = (h - res) // 2
        img = img[t:t + res]
    if w > res:
        t = (w - res) // 2
        img = img[:, t:t + res]
    ph, pw = res - img.shape[0], res - img.shape[1]
    return np.pad(img, ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2),
                        (0, 0)))


def _split(a):
    # ``reduce_precision`` and not a round trip through bfloat16: XLA may
    # drop a float32 -> bfloat16 -> float32 pair as excess precision (the
    # TPU compiler does), which leaves ``lo`` zero and the contraction
    # one bf16 pass
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    assert precision == "high", precision
    # each half is exact in bfloat16, so each pass multiplies exactly and
    # sums in float32, as the matrix unit does
    ah, al = (h.astype(jnp.bfloat16) for h in _split(a))
    bh, bl = (h.astype(jnp.bfloat16) for h in _split(b))
    return sum(jnp.einsum(eq, x, y, preferred_element_type=jnp.float32)
               for x, y in ((ah, bh), (ah, bl), (al, bh)))


ACTS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "hswish": lambda x: x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0,
}


def _hsigmoid(x):
    return jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def _pads(extent: int, k: int, stride: int):
    out = -(-extent // stride)
    total = max(0, (out - 1) * stride + k - extent)
    return out, total // 2, total - total // 2


def _windows(x, kh: int, kw: int, stride: int):
    """SAME-padded (B, oh, ow, C) windows of ``x`` for each tap (i, j) of
    a kh x kw filter, in row-major tap order."""
    _, h, w, _ = x.shape
    oh, lh, hh = _pads(h, kh, stride)
    ow, lw, hw = _pads(w, kw, stride)
    xp = jnp.pad(x, ((0, 0), (lh, hh), (lw, hw), (0, 0)))
    return [xp[:, i:i + (oh - 1) * stride + 1:stride,
               j:j + (ow - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]


def _conv(x, w, stride: int, precision: str):
    """KxK convolution, w: (K, K, Cin, Cout)."""
    k = w.shape[0]
    taps = jnp.concatenate(_windows(x, k, k, stride), axis=-1)
    return _dot("bhwp,po->bhwo", taps, w.reshape(-1, w.shape[-1]), precision)


def _bank(x, w, kh: int, kw: int, stride: int):
    """Per-channel kh x kw filter (a 1-D bank when one side is 1);
    w: (kh * kw, C)."""
    acc = 0.0
    for t, win in enumerate(_windows(x, kh, kw, stride)):
        acc = acc + win * w[t]
    return acc


def _bn(p, x):
    return (x - p["mean"]) * jax.lax.rsqrt(p["var"] + EPS) * p["scale"] \
        + p["bias"]


def _spatial(p, x, variant: str, k: int, stride: int):
    if variant == "depthwise":
        return _bank(x, p["dw"].reshape(k * k, -1), k, k, stride)
    c_r = p["row"].shape[1]
    if variant == "fuse_full":
        return jnp.concatenate([_bank(x, p["row"], k, 1, stride),
                                _bank(x, p["col"], 1, k, stride)], axis=-1)
    assert variant == "fuse_half", variant
    return jnp.concatenate([_bank(x[..., :c_r], p["row"], k, 1, stride),
                            _bank(x[..., c_r:], p["col"], 1, k, stride)],
                           axis=-1)


def forward(params: list, net: dict, x, precision: str = "highest"):
    """Logits (B, classes) of letterboxed images x: (B, res, res, C)."""
    v = net["variant"]
    for b, p in zip(net["blocks"], params):
        t = b["type"]
        if t in ("stem", "conv"):
            if p["w"].ndim == 2:
                x = _dot("bhwi,io->bhwo", x, p["w"], precision)
            else:
                x = _conv(x, p["w"], b["stride"], precision)
            x = ACTS[b["act"]](_bn(p["bn"], x))
        elif t == "mbconv":
            act = ACTS[b["act"]]
            short = x
            if "expand" in p:
                x = act(_bn(p["bn0"], _dot("bhwi,io->bhwo", x, p["expand"],
                                           precision)))
            x = _spatial(p["sp"], x, v, b["kernel"], b["stride"])
            x = act(_bn(p["bn1"], x))
            if b["se"]:
                s = jnp.mean(x, axis=(1, 2))
                s = ACTS["relu"](_dot("bc,cr->br", s, p["se"]["reduce"]["w"],
                                      precision) + p["se"]["reduce"]["b"])
                s = _hsigmoid(_dot("br,rc->bc", s, p["se"]["expand"]["w"],
                                   precision) + p["se"]["expand"]["b"])
                x = x * s[:, None, None, :]
            x = _bn(p["bn2"], _dot("bhwi,io->bhwo", x, p["project"],
                                   precision))
            if b["stride"] == 1 and short.shape[-1] == x.shape[-1]:
                x = x + short
        elif t == "head":
            x = jnp.mean(x, axis=(1, 2))
            if b.get("hidden"):
                x = ACTS[b["act"]](_dot("bc,ch->bh", x, p["hidden"]["w"],
                                        precision) + p["hidden"]["b"])
            x = _dot("bc,ck->bk", x, p["fc"]["w"], precision) + p["fc"]["b"]
        else:
            raise ValueError(t)
    return x


@functools.lru_cache(maxsize=None)
def _jitted(net_json: str, precision: str):
    import json
    net = json.loads(net_json)
    return jax.jit(lambda params, x: forward(params, net, x, precision))


def logits(params: list, net: dict, images, precision: str = "highest",
           block: int = 16) -> np.ndarray:
    """Reference logits of raw images (any sizes), letterboxed here and
    run ``block`` at a time so that the reference fits beside nothing."""
    import json
    f = _jitted(json.dumps(net, sort_keys=True), precision)
    res = net["resolution"]
    x = np.stack([letterbox(np.asarray(im, np.float32), res)
                  for im in images])
    n = len(x)
    pad = -n % block
    if pad:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    out = [np.asarray(f(params, x[i:i + block]))
           for i in range(0, len(x), block)]
    return np.concatenate(out)[:n]
