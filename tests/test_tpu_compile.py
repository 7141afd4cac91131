"""Compile rehearsal: the Pallas kernels at real widths, compiled for a
described (unattached) TPU v5e.

Interpret mode on CPU checks what a kernel computes; only the TPU
compiler (Mosaic) says whether it accepts the kernel: strided value
slices, unaligned slices, VMEM per program.  Each test here compiles one
kernel at the widths the zoo's networks give it — MobileNetV2/V3 at 224px
and batch 8 — and requires a compiled ``tpu_custom_call`` in the result;
one compiles a whole network, where XLA's own use of VMEM around the
kernels counts against the same limit.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports every test file.  The fixture skips where no v5e can be
described.  The persistent compilation cache is off around these compiles
(an executable compiled for a described device cannot be read back).
"""
import functools

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import fuse1d as kfuse1d
from repro.kernels import fused as kfused
from repro.kernels import matmul as kmatmul
from repro.vision import zoo


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # libtpu writes its logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or its lock is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


def _compile(fn, sharding, *shapes):
    specs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
             for s in shapes]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    assert n > 0, "no compiled Pallas kernel in the program"
    return n


@pytest.mark.parametrize("h,c,k,stride,variant,cout", [
    (112, 32, 3, 1, "fuse_half", 16),    # MobileNetV2 block 1
    (112, 96, 3, 2, "fuse_half", 24),    # MobileNetV2 block 2 (stride 2)
    (14, 672, 5, 2, "fuse_full", 160),   # V3-large width, k=5, stride 2
])
def test_fuseconv_fused_compiles(one_chip, h, c, k, stride, variant, cout):
    full = variant == "fuse_full"
    c_r, c_c = (c, c) if full else (c // 2, c - c // 2)
    c_sp = c_r + c_c
    fn = functools.partial(kfused.fuseconv_fused, variant=variant,
                           stride=stride, act="relu6", interpret=False)
    _compile(lambda x, wr, wc, wp, g, b: fn(x, wr, wc, wp, scale=g, bias=b),
             one_chip, (8, h, h, c), (k, c_r), (k, c_c), (c_sp, cout),
             (c_sp,), (c_sp,))


@pytest.mark.parametrize("h,c,k,stride", [
    (14, 672, 5, 2),     # k=5, stride 2: refused before the phase split
    (112, 96, 3, 2),     # MobileNetV2's widest stride-2 stage
    (112, 32, 3, 1),
])
def test_depthwise_kxk_compiles(one_chip, h, c, k, stride):
    _compile(functools.partial(kfused.depthwise_kxk, stride=stride,
                               interpret=False),
             one_chip, (8, h, h, c), (k, k, c))


def test_fuse1d_compiles(one_chip):
    # V3-large SE block, k=5 row bank at 28x28: (B*W, H + K - 1, C)
    _compile(functools.partial(kfuse1d.fuse1d, interpret=False),
             one_chip, (8 * 28, 28 + 4, 120), (5, 120))


@pytest.mark.parametrize("m,k,n", [
    (8 * 56 * 56, 24, 72),          # a 1x1 expand at 56x56, batch 8
    # the widest 1x1s at bucket 32, blocks from matmul_plan
    (32 * 112 * 112, 16, 96),       # MobileNetV2's first expand
    (32 * 56 * 56, 24, 144),        # its 56x56 expand, N no multiple of 128
    (32 * 7 * 7, 1920, 160),        # V3-large's widest project: 784-row
                                    # whole-K blocks ran out of VMEM
    (32 * 7 * 7, 320, 1280),        # MobileNetV2's head 1x1
    (32 * 7 * 7, 1024, 1024),       # MobileNetV1's last 1x1: K split in two
])
def test_matmul_compiles(one_chip, m, k, n):
    # (B*H*W, Cin) @ (Cin, Cout)
    _compile(functools.partial(kmatmul.matmul, interpret=False),
             one_chip, (m, k), (k, n))


def test_network_compiles_at_batch_8(one_chip):
    """MobileNetV2/depthwise at 224px, batch 8, served at fp32: the
    whole program, where a 17.3 MiB stride-2 depthwise program was once
    refused for scoped VMEM next to XLA's own buffers."""
    net = zoo.ZOO["mobilenet_v2"]()
    params = jax.eval_shape(
        lambda: zoo.init_network(jax.random.PRNGKey(0), net, "depthwise"))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32,
                             sharding=one_chip)

    def apply(p, x):
        with jax.default_matmul_precision("highest"):
            return zoo.apply_network(p, net, x, "depthwise",
                                     backend="pallas_tpu")[0]
    text = jax.jit(apply).lower(params, x).compile().as_text()
    # one depthwise kernel per block plus the 1x1 matmuls
    assert text.count('custom_call_target="tpu_custom_call"') > 17
