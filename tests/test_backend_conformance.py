"""Backend-conformance harness: Pallas kernels vs the XLA reference path.

The serving engine dispatches whole networks through
``zoo.apply_network(..., backend=...)``, so any numerical divergence
between the Pallas wrappers (interpret mode on CPU) and the lax reference
silently corrupts served logits.  This suite pins parity at three levels:

  * operator level — every FuSe 2-D wrapper and the pointwise matmul
    kernel over a grid of shapes (odd/even/prime extents), kernel sizes,
    and strides, against ``repro.core.fuseconv``;
  * fused-kernel level — the ``fuseconv_fused`` megakernel and the
    ``depthwise_kxk`` kernel, differentially against (a) their
    slow-but-obviously-correct ``kernels/ref.py`` oracles and (b) the
    decomposed ``fuse_conv2d_{full,half}`` + ``pointwise`` pipeline, over
    a grid of strides {1,2}, odd/even extents, k in {3,5,7}, and channel
    counts that do NOT divide the channel block (the tail-block case PR
    1's fuse1d padding bug lived in), plus property-style sweeps via the
    ``_hypothesis_compat`` shim;
  * network level — every zoo network (width 0.25x, 32px: same topology,
    CPU-sized) and every spatial-operator variant of tiny_net, run
    end-to-end on both backends with identical params, and with the fused
    path on vs off (identical logits AND identical top-1).

A dispatch-spy test additionally pins that ``Backend.interpret`` reaches
every kernel invocation — ``pallas_tpu`` must run compiled, never a
silently hardcoded ``interpret=True``.

The full grids are registered under the ``slow`` marker (``make test``
runs them, ``make test-fast`` skips them); a small representative subset
stays in the fast tier so day-to-day runs still cross-check the backends.
"""
import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import fuseconv as fc
from repro.kernels import fuse1d as kfuse1d
from repro.kernels import fused as kfused
from repro.kernels import matmul as kmatmul
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.vision import zoo

RTOL = ATOL = 1e-4


def _x(shape, seed=0):
    return np.asarray(
        np.random.default_rng(seed).standard_normal(shape), np.float32)


# ---------------------------------------------------------------------------
# Operator level: FuSe 2-D wrappers + pointwise vs the lax reference.
# ---------------------------------------------------------------------------

FAST_GRID = [
    # (h, w, c, k, stride) — one even, one odd/prime, one strided-even case
    (8, 8, 4, 3, 1),
    (13, 7, 6, 5, 1),
    (16, 10, 4, 3, 2),
]
SLOW_GRID = [
    (h, w, c, k, s)
    for (h, w) in [(7, 7), (8, 8), (11, 13), (16, 16), (20, 12), (5, 17)]
    for c in (3, 8)
    for k in (3, 5)
    for s in (1, 2)
]


def _check_fuse_ops(h, w, c, k, stride):
    x = _x((2, h, w, c))
    w_row = _x((k, c), seed=1) * 0.5
    w_col = _x((k, c), seed=2) * 0.5
    got = kops.fuse_conv2d_full(x, w_row, w_col, stride=stride,
                                interpret=True)
    ref = fc.fuse_conv2d_full(x, w_row, w_col, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    c_r = c // 2
    got = kops.fuse_conv2d_half(x, w_row[:, :c_r], w_col[:, c_r:],
                                stride=stride, interpret=True)
    ref = fc.fuse_conv2d_half(x, w_row[:, :c_r], w_col[:, c_r:],
                              stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("h,w,c,k,stride", FAST_GRID)
def test_fuse_ops_match_reference_fast(h, w, c, k, stride):
    _check_fuse_ops(h, w, c, k, stride)


@pytest.mark.slow
@pytest.mark.parametrize("h,w,c,k,stride", SLOW_GRID)
def test_fuse_ops_match_reference_grid(h, w, c, k, stride):
    _check_fuse_ops(h, w, c, k, stride)


@pytest.mark.parametrize("shape,cout", [((2, 8, 8, 4), 6),
                                        ((1, 13, 7, 5), 3),
                                        ((3, 40, 2), 9)])
def test_pointwise_matches_reference(shape, cout):
    x = _x(shape)
    w = _x((shape[-1], cout), seed=3) * 0.3
    got = kops.pointwise(x, w, interpret=True)
    if x.ndim == 4:
        ref = fc.pointwise_conv2d(x, w)
    else:
        ref = x @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# Network level: every zoo network x backend, identical params.
# ---------------------------------------------------------------------------

def _net_logits(net, variant, params, backend, x):
    logits, _ = zoo.apply_network(params, net, x, variant, train=False,
                                  backend=backend)
    return np.asarray(logits)


def _assert_backends_agree(net, variant, *, batch=2, seed=0):
    params = zoo.init_network(jax.random.PRNGKey(seed), net, variant)
    x = _x((batch, net.resolution, net.resolution, net.in_channels),
           seed=seed + 7)
    ref = _net_logits(net, variant, params, "xla", x)
    got = _net_logits(net, variant, params, "pallas", x)
    assert got.shape == ref.shape
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(zoo.ZOO))
def test_zoo_network_backend_parity(name):
    """Every paper evaluation network, CPU-sized (0.25x width, 32px):
    identical logits on the xla and pallas-interpret backends."""
    net = zoo.ZOO[name](num_classes=16, width_mult=0.25, resolution=32)
    _assert_backends_agree(net, "fuse_half")


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["fuse_half", "fuse_full",
                                     ["depthwise", "fuse_half", "fuse_full",
                                      "fuse_half"]])
def test_tiny_net_variant_backend_parity(variant):
    """All spatial-operator variants (including a hybrid per-stage list)
    agree across backends on the CPU-sized network."""
    net = zoo.tiny_net(num_classes=8, resolution=16, width=8)
    _assert_backends_agree(net, variant if isinstance(variant, str)
                           else tuple(variant))


def test_tiny_net_backend_parity_fast():
    """Fast-tier cross-backend sentinel (the full grids are slow-marked)."""
    net = zoo.tiny_net(num_classes=4, resolution=16, width=8)
    _assert_backends_agree(net, "fuse_full")


# ---------------------------------------------------------------------------
# Fused-kernel level: fuseconv_fused / depthwise_kxk vs the ref.py oracles
# AND vs the decomposed pipeline, on xla (core lax / ref) and pallas
# (interpret) implementations of the decomposition.
# ---------------------------------------------------------------------------

# Channel counts chosen to NOT divide the channel blocks used below — the
# tail-block case.  block overrides force multi-tile/multi-block paths even
# at CPU-test sizes.
FUSED_FAST_GRID = [
    # (h, w, c, k, stride, variant, cout)
    (8, 8, 6, 3, 1, "fuse_full", 10),
    (13, 7, 5, 5, 2, "fuse_half", 7),
    (16, 10, 6, 3, 2, "fuse_full", 12),
]
FUSED_SLOW_GRID = [
    (h, w, c, k, s, variant, cout)
    for (h, w) in [(7, 7), (8, 8), (11, 13), (16, 16), (5, 17)]
    for c in (3, 6)
    for k in (3, 5, 7)
    for s in (1, 2)
    for variant in ("fuse_half", "fuse_full")
    for cout in (5,)
]
DW_FAST_GRID = [
    # (h, w, c, k, stride) — c straddles the block_c override below
    (8, 8, 5, 3, 1),
    (13, 7, 9, 5, 2),
    (16, 10, 6, 3, 2),
]
DW_SLOW_GRID = [
    (h, w, c, k, s)
    for (h, w) in [(7, 7), (8, 8), (11, 13), (16, 16), (5, 17)]
    for c in (3, 5, 9)
    for k in (3, 5, 7)
    for s in (1, 2)
]
# Force tail blocks and multi-row-tile paths at test sizes.
_BLK = dict(block_h=4)


def _fused_weights(c, k, variant, cout, seed=0):
    if variant == "fuse_full":
        c_r, c_c, c_sp = c, c, 2 * c
    else:
        c_r = c // 2
        c_c, c_sp = c - c_r, c
    w_row = _x((k, c_r), seed=seed + 1) * 0.5
    w_col = _x((k, c_c), seed=seed + 2) * 0.5
    w_pw = _x((c_sp, cout), seed=seed + 3) * 0.3
    g = _x((c_sp,), seed=seed + 4) * 0.2 + 1.0
    b = _x((c_sp,), seed=seed + 5) * 0.1
    return w_row, w_col, w_pw, g, b


def _check_fused(h, w, c, k, stride, variant, cout, act="relu6"):
    x = _x((2, h, w, c))
    w_row, w_col, w_pw, g, b = _fused_weights(c, k, variant, cout)
    got = kops.fuseconv_fused(x, w_row, w_col, w_pw, variant=variant,
                              stride=stride, scale=g, bias=b, act=act,
                              block_cout=8, interpret=True, **_BLK)
    # (a) vs the slow-but-obviously-correct oracle
    ref = kref.fuseconv_fused_ref(x, w_row, w_col, w_pw, variant=variant,
                                  stride=stride, scale=g, bias=b, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    # (b) vs the decomposed pipeline, on the xla (core lax) and pallas
    # (interpret) implementations of the decomposition
    decom_f = (fc.fuse_conv2d_full if variant == "fuse_full"
               else fc.fuse_conv2d_half)
    kops_f = (kops.fuse_conv2d_full if variant == "fuse_full"
              else kops.fuse_conv2d_half)
    import repro.vision.layers as L
    for sp in (decom_f(x, w_row, w_col, stride=stride),
               kops_f(x, w_row, w_col, stride=stride, interpret=True)):
        y = L.ACTS[act](np.asarray(sp) * g + b)
        dec = np.asarray(kops.pointwise(y.astype(np.float32), w_pw,
                                        interpret=True))
        np.testing.assert_allclose(np.asarray(got), dec, rtol=RTOL, atol=ATOL)


def _check_depthwise(h, w, c, k, stride):
    x = _x((2, h, w, c))
    wt = _x((k, k, c), seed=9) * 0.5
    got = kops.depthwise_kxk(x, wt, stride=stride, block_c=4, interpret=True,
                             **_BLK)
    ref = kref.depthwise_kxk_ref(x, wt, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    xla = fc.depthwise_conv2d(x, wt, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("h,w,c,k,stride,variant,cout", FUSED_FAST_GRID)
def test_fuseconv_fused_matches_references_fast(h, w, c, k, stride, variant,
                                                cout):
    _check_fused(h, w, c, k, stride, variant, cout)


@pytest.mark.slow
@pytest.mark.parametrize("h,w,c,k,stride,variant,cout", FUSED_SLOW_GRID)
def test_fuseconv_fused_matches_references_grid(h, w, c, k, stride, variant,
                                                cout):
    _check_fused(h, w, c, k, stride, variant, cout)


@pytest.mark.parametrize("h,w,c,k,stride", DW_FAST_GRID)
def test_depthwise_kxk_matches_references_fast(h, w, c, k, stride):
    _check_depthwise(h, w, c, k, stride)


@pytest.mark.slow
@pytest.mark.parametrize("h,w,c,k,stride", DW_SLOW_GRID)
def test_depthwise_kxk_matches_references_grid(h, w, c, k, stride):
    _check_depthwise(h, w, c, k, stride)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(h=st.integers(5, 18), w=st.integers(5, 18), c=st.integers(3, 10),
       khalf=st.integers(1, 3), stride=st.integers(1, 2),
       cout=st.integers(3, 12))
def test_fuseconv_fused_property(h, w, c, khalf, stride, cout):
    """Property sweep (hypothesis shim): strides {1,2}, odd/even extents,
    k in {3,5,7}, channel counts landing on tail blocks."""
    k = 2 * khalf + 1
    _check_fused(h, w, c, k, stride, "fuse_full", cout)
    if c >= 2:
        _check_fused(h, w, c, k, stride, "fuse_half", cout)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(h=st.integers(5, 18), w=st.integers(5, 18), c=st.integers(3, 10),
       khalf=st.integers(1, 3), stride=st.integers(1, 2))
def test_depthwise_kxk_property(h, w, c, khalf, stride):
    _check_depthwise(h, w, c, 2 * khalf + 1, stride)


def test_fused_activation_variants():
    """Every activation the zoo can ask the megakernel to apply in-kernel."""
    for act in ("linear", "relu", "relu6", "hswish"):
        _check_fused(9, 8, 4, 3, 1, "fuse_full", 6, act=act)


def _tile(rows, cols):
    """fp32 bytes of a (rows, cols) slab padded to (8, 128) VMEM tiles."""
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4


def test_fused_tile_plan_fits_vmem_budget():
    """Tiling validation: with the default row plan, one program of each
    kernel — its double-buffered input window (all stride phases), its
    double-buffered output tile and weight blocks, and its fp32
    accumulators — fits ``kfused.VMEM_BUDGET`` (half the TPU compiler's
    16 MiB default scoped limit) at every depthwise and fused-eligible
    stage of every zoo network at full paper resolution.  What the
    compiler itself accepts is pinned by tests/test_tpu_compile.py."""
    assert kfused.VMEM_BUDGET <= 16 * 1024 * 1024
    stages = 0
    for name, f in sorted(zoo.ZOO.items()):
        for variant in ("fuse_full", "depthwise"):
            ir = zoo.lower_to_ir(f(), variant)
            for i, op in enumerate(ir):
                if op.kind not in ("fuse_row", "depthwise"):
                    continue
                k, s = op.kernel, op.stride
                out_h, lo_h, hi_h = kfused.same_pad(op.in_h, k, s)
                out_w, lo_w, hi_w = kfused.same_pad(op.in_w, k, s)
                wq = -(-(op.in_w + lo_w + hi_w) // s)
                c = op.in_c
                if op.kind == "fuse_row":
                    pw = next(o for o in ir[i + 1:] if o.kind == "pointwise")
                    c_sp, cblk = pw.in_c, min(kfused.DEFAULT_BLOCK_COUT,
                                              pw.out_c)
                    out_row = (3 * _tile(out_w, cblk) + 2 * _tile(out_w, c)
                               + _tile(out_w, c_sp))
                    fixed = 2 * (_tile(c_sp, cblk) + 2 * _tile(k, c)
                                 + 2 * _tile(1, c_sp))
                    in_c = c
                else:
                    cblk = in_c = min(kfused.DEFAULT_BLOCK_C, c)
                    out_row = 3 * _tile(out_w, cblk)
                    fixed = 2 * k * _tile(k, cblk)
                in_row = s * s * _tile(wq, in_c)
                th, n_tiles, win, _ = kfused._row_plan(
                    out_h, s, k, None, in_row=in_row, out_row=out_row,
                    fixed=fixed)
                assert n_tiles * th >= out_h and win == th + (k - 1) // s
                footprint = 2 * in_row * win + out_row * th + fixed
                assert footprint <= kfused.VMEM_BUDGET, (
                    name, op.name, th, footprint)
                stages += 1
    assert stages > 100


def test_fused_without_affine():
    """scale/bias omitted: pure banks + mix (the decomposed comparison the
    bench case times)."""
    x = _x((2, 8, 8, 4))
    w_row, w_col, w_pw, _, _ = _fused_weights(4, 3, "fuse_full", 6)
    got = kops.fuseconv_fused(x, w_row, w_col, w_pw, interpret=True)
    sp = kops.fuse_conv2d_full(x, w_row, w_col, interpret=True)
    dec = kops.pointwise(np.asarray(sp), w_pw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dec),
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# Network level, fused path: identical logits and top-1 with fusion on/off.
# ---------------------------------------------------------------------------

def _assert_fused_matches_decomposed(net, variant, *, batch=2, seed=0):
    params = zoo.init_network(jax.random.PRNGKey(seed), net, variant)
    x = _x((batch, net.resolution, net.resolution, net.in_channels),
           seed=seed + 7)
    off, _ = zoo.apply_network(params, net, x, variant, train=False,
                               backend="pallas", fused=False)
    on, _ = zoo.apply_network(params, net, x, variant, train=False,
                              backend="pallas", fused=True)
    off, on = np.asarray(off), np.asarray(on)
    np.testing.assert_allclose(on, off, rtol=RTOL, atol=ATOL)
    assert np.array_equal(on.argmax(-1), off.argmax(-1))


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(zoo.ZOO))
def test_zoo_fused_on_off_identical(name):
    """Acceptance: every zoo net produces identical top-1 outputs with the
    fused megakernel path on vs off."""
    net = zoo.ZOO[name](num_classes=16, width_mult=0.25, resolution=32)
    _assert_fused_matches_decomposed(net, "fuse_half")
    _assert_fused_matches_decomposed(net, "fuse_full")


def test_tiny_net_fused_on_off_identical_fast():
    """Fast-tier fused-path sentinel (covers SE-block fallback + hybrid)."""
    net = zoo.tiny_net(num_classes=8, resolution=16, width=8)
    _assert_fused_matches_decomposed(net, "fuse_full")
    _assert_fused_matches_decomposed(
        net, ("depthwise", "fuse_half", "fuse_full", "fuse_half"))


def test_nofused_backend_key_round_trips():
    """The *_nofused debugging backends resolve and gate fusion off."""
    import repro.kernels.backend as kb
    bk = kb.resolve_backend("pallas_nofused")
    assert bk.use_pallas and bk.interpret and not bk.fused
    assert bk.key == "pallas_nofused"
    assert kb.resolve_backend("pallas_tpu_nofused").key == "pallas_tpu_nofused"
    assert kb.PALLAS.fused and kb.PALLAS_TPU.fused


@pytest.mark.slow
def test_zoo_depthwise_backend_parity():
    """Baseline depthwise nets are now servable on pallas: xla parity for
    the depthwise variant end to end (previously a silent XLA fallback)."""
    net = zoo.ZOO["mobilenet_v1"](num_classes=16, width_mult=0.25,
                                  resolution=32)
    _assert_backends_agree(net, "depthwise")


def test_tiny_net_depthwise_backend_parity_fast():
    net = zoo.tiny_net(num_classes=4, resolution=16, width=8)
    _assert_backends_agree(net, "depthwise")


# ---------------------------------------------------------------------------
# Dispatch spy: Backend.interpret must reach every kernel invocation.
# ---------------------------------------------------------------------------

def test_backend_interpret_threading_dispatch_spy(monkeypatch):
    """Run tiny_net on the pallas_tpu backends with every kernel entry
    point wrapped by a spy that records the ``interpret`` it was handed
    (then delegates to interpret=True so the test runs on CPU).  Every
    recorded value must be False — a hardcoded ``interpret=True`` default
    swallowing the flag (the old ``pointwise`` bug) fails here.
    """
    seen = {"fuse1d": [], "matmul": [], "fuseconv_fused": [],
            "depthwise_kxk": []}

    def spy(name, real):
        def wrapper(*args, **kw):
            seen[name].append(kw.get("interpret"))
            kw["interpret"] = True
            return real(*args, **kw)
        return wrapper

    # ops.py resolves these at call time via module-attribute lookup; zoo
    # dispatches the fused kernels through the kops module bindings.
    monkeypatch.setattr(kfuse1d, "fuse1d", spy("fuse1d", kfuse1d.fuse1d))
    monkeypatch.setattr(kmatmul, "matmul", spy("matmul", kmatmul.matmul))
    monkeypatch.setattr(kops, "fuseconv_fused",
                        spy("fuseconv_fused", kfused.fuseconv_fused))
    monkeypatch.setattr(kops, "depthwise_kxk",
                        spy("depthwise_kxk", kfused.depthwise_kxk))

    net = zoo.tiny_net(num_classes=4, resolution=16, width=8)
    x = _x((1, 16, 16, 3))
    params = zoo.init_network(jax.random.PRNGKey(0), net, "fuse_full")
    # fused path: fuseconv_fused + matmul (non-fusable pointwises)
    zoo.apply_network(params, net, x, "fuse_full", backend="pallas_tpu")
    # decomposed path: fuse1d + matmul
    zoo.apply_network(params, net, x, "fuse_full",
                      backend="pallas_tpu_nofused")
    # baseline path: depthwise_kxk
    params_dw = zoo.init_network(jax.random.PRNGKey(0), net, "depthwise")
    zoo.apply_network(params_dw, net, x, "depthwise", backend="pallas_tpu")

    for name, vals in seen.items():
        assert vals, f"{name} was never dispatched"
        assert all(v is False for v in vals), (name, vals)


def test_interpret_default_resolves_to_process_default():
    """Wrappers called without a Backend resolve interpret=None -> True
    (the safe CPU default), not a signature-level hardcode."""
    import repro.kernels.backend as kb
    assert kb.resolve_interpret(None) is True
    assert kb.resolve_interpret(False) is False
    x = _x((2, 6, 4))
    w = _x((4, 3), seed=1)
    got = kops.pointwise(x, w)      # no interpret kwarg anywhere
    ref = (x.reshape(-1, 4) @ w).reshape(2, 6, 3)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=RTOL, atol=ATOL)


def test_interpret_mode_is_cpu_only(monkeypatch):
    """Off CPU, interpret=None resolves to the compiled kernel and an
    explicit interpret=True is refused where the kernel is called —
    never a silent Python-interpreter run on the chip."""
    import repro.kernels.backend as kb
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kb.resolve_interpret(None) is False
    assert kb.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode"):
        kb.resolve_interpret(True)
    with pytest.raises(ValueError, match="interpret mode"):
        # shapes no other test traces: the refusal happens at trace time
        kmatmul.matmul(_x((3, 11)), _x((11, 13), seed=1), interpret=True)
    # selecting the interpret backend is fine until a kernel runs
    assert kb.resolve_backend("pallas").interpret


@pytest.mark.parametrize("variant", ["depthwise", "fuse_half", "fuse_full"])
def test_pallas_backend_never_runs_spatial_stages_on_xla(monkeypatch,
                                                         variant):
    """Under the Pallas backends no depthwise or FuSe stage, and no 1x1
    mix, drops to the XLA reference ops (fused and decomposed paths)."""
    def refuse(*args, **kw):
        raise AssertionError("XLA reference op on the Pallas backend")
    for name in ("apply_spatial_op", "depthwise_conv2d", "fuse_conv2d_half",
                 "fuse_conv2d_full", "pointwise_conv2d"):
        monkeypatch.setattr(fc, name, refuse)
    net = zoo.tiny_net(num_classes=4, resolution=16, width=8)
    params = zoo.init_network(jax.random.PRNGKey(0), net, variant)
    x = _x((1, 16, 16, 3))
    for backend in ("pallas", "pallas_nofused"):
        logits, _ = zoo.apply_network(params, net, x, variant,
                                      backend=backend)
        assert np.all(np.isfinite(np.asarray(logits)))
