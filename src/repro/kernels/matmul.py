"""Pallas TPU kernel: MXU matmul (the 1x1 pointwise stage of FuSe blocks).

Blocks follow the shapes (:func:`matmul_plan`).  Every grid step pays a
fixed pipeline cost on the chip (about 0.37 us on a v5e), which at 1x1
widths (K and N of 16-1,920) outweighs the data a 128x128 block moves, so
the kernel takes as few steps as VMEM allows:

- N and K whole in one block where they fit; a block dimension equal to
  the array's needs no 128-alignment, so N = 144 or K = 960 is neither
  padded nor sliced.  With one K step the kernel writes ``dot(a, b)``
  straight to the output block.
- M in the largest row tile that fits :data:`fused.VMEM_BUDGET` with the
  double-buffered a, b and output blocks and the fp32 product, counted in
  (8, 128) VMEM tiles: M itself, else a divisor of M that is a multiple
  of 8.  M is padded only when no such divisor reaches an eighth of the
  tile that fits.
- Where whole K and N leave no room for 8 rows (MobileNetV1's 1024x1024
  1x1), K is split into 128-multiples, then N too if need be, padded where
  they do not divide; the K axis then accumulates in an fp32 VMEM scratch,
  output stationary across the innermost grid axis (the paper's §3.3, at
  block granularity).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb
from repro.kernels.fused import VMEM_BUDGET, _tile_bytes

# split widths tried, widest first, when a whole K or N does not fit
_SPLITS = (2048, 1024, 512, 256, 128)


class MatmulPlan(NamedTuple):
    bm: int
    bn: int
    bk: int
    grid: Tuple[int, int, int]     # (M tiles, N tiles, K steps)
    padded: bool                   # some operand is padded (or sliced back)

    @property
    def steps(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]


def _vmem_bytes(bm: int, bk: int, bn: int, split_k: bool) -> int:
    """The program's VMEM as the plan counts it: double-buffered a, b and
    output blocks, the fp32 product, and the accumulator on a K split, all
    as fp32 (an upper bound for narrower operands)."""
    blocks = _tile_bytes(bm, bk) + _tile_bytes(bk, bn) + _tile_bytes(bm, bn)
    return 2 * blocks + _tile_bytes(bm, bn) * (2 if split_k else 1)


def _rows(m: int, fit: int) -> int:
    """Row tile for ``fit`` rows of room (a multiple of 8): M, else the
    largest divisor of M that is a multiple of 8, else an even spread of M
    over tiles of at most ``fit`` rows, M then padded.  A divisor down to
    an eighth of ``fit`` is taken before padding: its extra steps cost
    about 0.37 us each, a pad adds XLA copies of ``a`` and ``y`` around the
    kernel (M = 392 takes 7 tiles of 56 rows, not 2 padded ones)."""
    if m <= fit:
        return m
    for d in range(fit, fit // 8 - 1, -8):
        if m % d == 0:
            return d
    rows = -(-m // -(-m // fit))
    return -(-rows // 8) * 8


def matmul_plan(m: int, k: int, n: int) -> MatmulPlan:
    """Blocks and grid of ``matmul`` for (m, k) @ (k, n)."""
    for bn in (n,) + tuple(s for s in _SPLITS if s < n):
        for bk in (k,) + tuple(s for s in _SPLITS if s < k):
            split_k = bk < k
            fixed = 2 * _tile_bytes(bk, bn)
            per8 = _vmem_bytes(8, bk, bn, split_k) - fixed
            room = VMEM_BUDGET - fixed
            if room < per8:
                continue
            bm = _rows(m, room // per8 * 8)
            grid = (-(-m // bm), -(-n // bn), -(-k // bk))
            padded = bool(m % bm or n % bn or k % bk)
            return MatmulPlan(bm, bn, bk, grid, padded)
    raise ValueError(f"no matmul blocks fit VMEM for {(m, k, n)}")


def _matmul_kernel(a_ref, b_ref, y_ref, *acc_ref, n_k: int):
    # fp32 contraction on the MXU, as in interpret mode (the TPU default
    # for f32 operands may round them to bf16)
    prod = jnp.dot(a_ref[...].astype(jnp.float32),
                   b_ref[...].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    if n_k == 1:
        y_ref[...] = prod.astype(y_ref.dtype)
        return
    acc_ref, = acc_ref

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += prod

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matmul(a: jax.Array, b: jax.Array, *,
           interpret: Optional[bool] = None) -> jax.Array:
    """y = a @ b in fp32, blocks from :func:`matmul_plan`.  a: (M,K), b: (K,N).

    ``interpret`` resolves through ``backend.resolve_interpret``: the
    Python interpreter on CPU, the compiled kernel on TPU.
    """
    interpret = kb.resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk, (gm, gn, gk), _ = matmul_plan(m, k, n)
    pm, pn, pk = gm * bm - m, gn * bn - n, gk * bk - k
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    y = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] if gk > 1 else [],
        interpret=interpret,
    )(a, b)
    return y[:m, :n] if pm or pn else y
