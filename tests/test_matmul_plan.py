"""``matmul_plan``: the matmul kernel's blocks, sized from its shapes.

Every grid step costs a fixed pipeline overhead on the chip, so the plan
takes whole K and N blocks and the largest row tiles that fit
``VMEM_BUDGET``.  These tests hold the plan to that for every 1x1 the
served networks run (recorded from the zoo's own forward pass at serving
buckets), and pin its grid-step tally: with fixed 128x128x128 blocks a
bucket-32 pass took 9,785 steps (MobileNetV2) and 10,363 (V3-large).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import matmul as kmatmul
from repro.kernels.fused import VMEM_BUDGET, _tile_bytes
from repro.vision import zoo

NETS = {"mobilenet_v2": "fuse_half", "mobilenet_v3_large": "fuse_full"}

# grid steps of one forward pass, by network and bucket (fixed 128-blocks:
# 372, 2,525, 9,785 and 430, 2,719, 10,363)
STEPS = {
    ("mobilenet_v2", 1): 22, ("mobilenet_v2", 8): 96,
    ("mobilenet_v2", 32): 350,
    ("mobilenet_v3_large", 1): 26, ("mobilenet_v3_large", 8): 107,
    ("mobilenet_v3_large", 32): 369,
}


def _served_matmuls(monkeypatch, name, batch):
    """(M, K, N) of every ``matmul`` call in one 224px forward pass on the
    Pallas backend, in order (traced abstractly, nothing computed)."""
    seen = []

    def record(a, b, **kw):
        seen.append((a.shape[0], a.shape[1], b.shape[1]))
        return jnp.zeros((a.shape[0], b.shape[1]), a.dtype)

    monkeypatch.setattr(kmatmul, "matmul", record)
    net, variant = zoo.ZOO[name](), NETS[name]
    params = jax.eval_shape(
        lambda: zoo.init_network(jax.random.PRNGKey(0), net, variant))
    x = jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32)
    jax.eval_shape(lambda p, x: zoo.apply_network(
        p, net, x, variant, backend="pallas")[0], params, x)
    return seen


def _check_blocks(m, k, n, plan):
    bm, bn, bk, (gm, gn, gk), padded = plan
    # a block dimension is the array's, or (8, 128)-aligned
    assert bm == m or bm % 8 == 0, plan
    assert bn == n or bn % 128 == 0, plan
    assert bk == k or bk % 128 == 0, plan
    # the grid covers the operands; blocks divide them or the call pads
    assert (gm - 1) * bm < m <= gm * bm, plan
    assert (gn - 1) * bn < n <= gn * bn, plan
    assert (gk - 1) * bk < k <= gk * bk, plan
    assert padded == bool(m % bm or n % bn or k % bk), plan
    # double-buffered a, b and y blocks, the fp32 product and (on a K
    # split) the accumulator, in (8, 128) tiles
    vmem = (2 * (_tile_bytes(bm, bk) + _tile_bytes(bk, bn)
                 + _tile_bytes(bm, bn))
            + _tile_bytes(bm, bn) * (2 if gk > 1 else 1))
    assert vmem <= VMEM_BUDGET, (plan, vmem)


@pytest.mark.parametrize("bucket", [1, 8, 32])
@pytest.mark.parametrize("name", sorted(NETS))
def test_served_1x1s_whole_blocks_in_vmem(monkeypatch, name, bucket):
    shapes = _served_matmuls(monkeypatch, name, bucket)
    assert shapes, "no matmul call recorded"
    steps = 0
    for m, k, n in shapes:
        plan = kmatmul.matmul_plan(m, k, n)
        _check_blocks(m, k, n, plan)
        # none of the networks' 1x1s pads or splits K or N
        assert not plan.padded and plan.grid[1:] == (1, 1), (m, k, n, plan)
        steps += plan.steps
    assert steps == STEPS[name, bucket]


@pytest.mark.parametrize("m,k,n,grid,padded", [
    (401408, 16, 96, (128, 1, 1), False),   # row tiles dividing M
    (49, 160, 960, (1, 1, 1), False),       # one block
    (392, 1920, 160, (7, 1, 1), False),     # 56-row tiles, not 2 padded
    (8 * 10007, 64, 64, (25, 1, 1), True),  # M = 8 x a prime: padded
    (1568, 1024, 1024, (14, 1, 2), False),  # MobileNetV1's last 1x1
    (49, 1000, 1280, (1, 1, 2), True),      # K split, padded to 1,024
    (8, 4096, 2304, (1, 1, 16), False),     # K split into 256s
    (8, 256, 8320, (1, 5, 1), True),        # N split into 2,048s
    (7, 300, 5, (1, 1, 1), False),
])
def test_plan_paths(m, k, n, grid, padded):
    plan = kmatmul.matmul_plan(m, k, n)
    _check_blocks(m, k, n, plan)
    assert (plan.grid, plan.padded) == (grid, padded)

