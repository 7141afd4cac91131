"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import fuseconv as fc
from repro.kernels import ops, ref
from repro.kernels.fuse1d import fuse1d
from repro.kernels.matmul import matmul

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("n,t,c,k", [
    (1, 8, 8, 3), (2, 17, 33, 5), (4, 64, 128, 3), (1, 16, 7, 4),
    (3, 33, 257, 7), (2, 128, 96, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fuse1d_sweep(n, t, c, k, dtype):
    x = jax.random.normal(KEY, (n, t + k - 1, c)).astype(dtype)
    w = jax.random.normal(KEY, (k, c)).astype(dtype)
    y = fuse1d(x, w)
    yr = ref.fuse1d_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,k,n", [
    (16, 16, 16), (128, 128, 128), (130, 257, 65), (7, 300, 5),
    (256, 64, 512),
    # blocks from the shapes (matmul_plan): whole N that is no multiple of
    # 128, M with an odd factor in one block or in row tiles that divide
    # it, K too wide for one block (padded, accumulated), N split in two
    (49, 160, 960), (1568, 24, 144), (6272, 96, 576), (1568, 1920, 160),
    (49, 1000, 1280), (8, 256, 8320),
])
def test_matmul_sweep(m, k, n):
    a = jax.random.normal(KEY, (m, k))
    b = jax.random.normal(KEY, (k, n))
    np.testing.assert_allclose(matmul(a, b), ref.matmul_ref(a, b),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 3), t=st.integers(1, 40), c=st.integers(1, 40),
       k=st.integers(1, 7))
def test_fuse1d_property(n, t, c, k):
    x = jax.random.normal(KEY, (n, t + k - 1, c))
    w = jax.random.normal(KEY, (k, c))
    np.testing.assert_allclose(fuse1d(x, w), ref.fuse1d_ref(x, w),
                               rtol=1e-4, atol=1e-4)


def test_ops_fuse2d_matches_core():
    x = jax.random.normal(KEY, (2, 13, 11, 8))
    wr = jax.random.normal(KEY, (5, 4))
    wc = jax.random.normal(KEY, (5, 4))
    for s in (1, 2):
        y1 = ops.fuse_conv2d_half(x, wr, wc, stride=s)
        y2 = fc.fuse_conv2d_half(x, wr, wc, stride=s)
        np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)


def test_ops_temporal_long_chunked(monkeypatch):
    monkeypatch.setattr(ops, "MAX_T_CHUNK", 16)
    x = jax.random.normal(KEY, (2, 75, 12))
    w = jax.random.normal(KEY, (4, 12))
    y1 = ops.fuse_conv1d_temporal(x, w)
    y2 = fc.fuse_conv1d_temporal(x, w)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)


def test_pointwise_kernel():
    x = jax.random.normal(KEY, (2, 7, 9, 32))
    w = jax.random.normal(KEY, (32, 24))
    y = ops.pointwise(x, w)
    np.testing.assert_allclose(y, jnp.einsum("bhwi,io->bhwo", x, w),
                               rtol=1e-4, atol=1e-4)
