"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes (incl. non-multiple fringes), dtypes, and accumulate
forms — the kernel-level contract of the MMA facility."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision import Ger, policy
from repro.kernels import mma_gemm as K
from repro.kernels import mma_conv as KC
from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _rand_for(kind, shape, rng):
    pol = policy(kind)
    dt = jnp.dtype(pol.x_dtype)
    if dt == jnp.int8:
        return jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
    if dt == jnp.uint8:
        return jnp.asarray(rng.integers(0, 256, shape), jnp.uint8)
    if dt == jnp.int16:
        return jnp.asarray(rng.integers(-1000, 1000, shape), jnp.int16)
    return jnp.asarray(rng.normal(size=shape), dt)


GEMM_SHAPES = [
    (8, 128, 128),      # single tile
    (100, 300, 130),    # fringe on all dims
    (256, 512, 256),    # multi-tile aligned
    (33, 64, 257),      # small + fringe
]

FLOAT_KINDS = [Ger.BF16GER2, Ger.F16GER2, Ger.F32GER]
INT_KINDS = [Ger.I8GER4, Ger.I16GER2]


@pytest.mark.parametrize("kind", FLOAT_KINDS)
@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_gemm_float_matches_oracle(kind, m, k, n, rng):
    x = _rand_for(kind, (m, k), rng)
    y = _rand_for(kind, (k, n), rng)
    got = K.mma_gemm(x, y, kind=kind, block=(32, 128, 128), interpret=True)
    want = ref.ger(x, y, kind)
    # atol 3e-5: the blocked kernel accumulates in k-panel order, the
    # oracle in one dot — fp32 rounding differs in the last ulp(s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=3e-5)


@pytest.mark.parametrize("kind", INT_KINDS)
@pytest.mark.parametrize("m,k,n", GEMM_SHAPES[:3])
def test_gemm_int_exact(kind, m, k, n, rng):
    pol = policy(kind)
    x = _rand_for(kind, (m, k), rng)
    y = jnp.asarray(
        rng.integers(0, 256, (k, n)), jnp.uint8) if pol.y_dtype == jnp.uint8 \
        else _rand_for(kind, (k, n), rng)
    got = K.mma_gemm(x, y, kind=kind, block=(32, 128, 128), interpret=True)
    want = ref.ger(x, y, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gemm_int4_packed(rng):
    x = jnp.asarray(rng.integers(-128, 128, (32, 64)), jnp.int8)
    y = jnp.asarray(rng.integers(-128, 128, (64, 128)), jnp.int8)
    got = K.mma_gemm(x, y, kind=Ger.I4GER8, block=(32, 128, 128),
                     interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.ger(x, y, Ger.I4GER8)))


def test_gemm_fp64_interpret(rng):
    """The paper's DGEMM case study dtype (VPU path on TPU)."""
    with jax.enable_x64(True):
        x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float64)
        y = jnp.asarray(rng.normal(size=(128, 128)), jnp.float64)
        got = K.mma_gemm(x, y, kind=Ger.F64GER, block=(32, 128, 128),
                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) @
                                   np.asarray(y), rtol=1e-12)


def test_gemm_fp64_interpret_short_m_k_fringe(rng):
    """Fewer than 8 rows of X and a K fringe: the fringe mask keeps the
    f64 panels at f64."""
    with jax.enable_x64(True):
        x = jnp.asarray(rng.normal(size=(4, 200)), jnp.float64)
        y = jnp.asarray(rng.normal(size=(200, 128)), jnp.float64)
        got = K.mma_gemm(x, y, kind=Ger.F64GER, block=(8, 128, 128),
                         interpret=True)
        # f64 rounding of a 200-term sum; an f32 panel errs by ~1e-6
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) @
                                   np.asarray(y), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("neg_product,neg_acc", [(False, False),
                                                 (True, False),
                                                 (False, True),
                                                 (True, True)])
def test_gemm_accumulate_forms(neg_product, neg_acc, rng):
    """pp / np / pn / nn suffixes (paper eq. 2)."""
    x = jnp.asarray(rng.normal(size=(64, 192)), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(192, 128)), jnp.bfloat16)
    c = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    got = K.mma_gemm(x, y, c, kind=Ger.BF16GER2, block=(32, 128, 128),
                     neg_product=neg_product, neg_acc=neg_acc,
                     interpret=True)
    want = ref.ger(x, y, Ger.BF16GER2, acc=c, neg_product=neg_product,
                   neg_acc=neg_acc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gemm_alpha_beta(rng):
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(128, 128)), jnp.bfloat16)
    c = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    got = K.mma_gemm(x, y, c, kind=Ger.BF16GER2, block=(32, 128, 128),
                     alpha=0.5, beta=2.0, interpret=True)
    want = 0.5 * (ref.ger(x, y, Ger.BF16GER2) + 2.0 * c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_pm_masked_equals_oracle(rng):
    """Prefixed pm* forms (paper eq. 3)."""
    xm = jnp.asarray(rng.random(48) > 0.3)
    ym = jnp.asarray(rng.random(96) > 0.3)
    pm = jnp.asarray(rng.random(64) > 0.3)
    x = jnp.asarray(rng.normal(size=(48, 64)), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(64, 96)), jnp.bfloat16)
    got = ops.mma_pm_dot(x, y, kind=Ger.BF16GER2, xmask=xm, ymask=ym,
                         pmask=pm)
    want = ref.pm_ger(x, y, Ger.BF16GER2, xm, ym, pm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pm_masked_no_nan_from_disabled_lanes(rng):
    """Disabled rows/cols never contaminate the result (architected: no
    exceptions from disabled computations)."""
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    x = x.at[3].set(jnp.nan)
    y = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    xm = jnp.ones(16, bool).at[3].set(False)
    ym = jnp.ones(16, bool)
    got = ops.mma_pm_dot(x, y, kind=Ger.F32GER, xmask=xm, ymask=ym)
    assert not bool(jnp.isnan(got[:3]).any())
    assert not bool(jnp.isnan(got[4:]).any())


def test_saturating_i16(rng):
    xi = jnp.full((4, 8), 32767, jnp.int16)
    yi = jnp.full((8, 4), 32767, jnp.int16)
    assert int(ops.mma_ger_saturating(xi, yi, Ger.I16GER2).max()) == \
        np.iinfo(np.int32).max
    xn = jnp.full((4, 8), -32768, jnp.int16)
    assert int(ops.mma_ger_saturating(xn, yi, Ger.I16GER2).min()) == \
        np.iinfo(np.int32).min
    # agrees with modulo ref when nothing saturates
    xs = jnp.asarray(rng.integers(-100, 100, (8, 16)), jnp.int16)
    ys = jnp.asarray(rng.integers(-100, 100, (16, 8)), jnp.int16)
    np.testing.assert_array_equal(
        np.asarray(ops.mma_ger_saturating(xs, ys, Ger.I16GER2)),
        np.asarray(ref.ger(xs, ys, Ger.I16GER2)))


def test_f32_3xbf16_beats_plain_bf16(rng):
    x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    exact = np.asarray(x) @ np.asarray(y)
    o3 = np.asarray(ops.mma_dot(x, y, kind=Ger.F32GER_3XBF16,
                                block=(64, 128, 128)))
    ob = np.asarray(ref.ger(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                            Ger.BF16GER2))
    assert np.abs(o3 - exact).max() < 0.05 * np.abs(ob - exact).max()


@pytest.mark.parametrize("n,h,w,c,kh,kw,f", [
    (2, 10, 24, 3, 3, 3, 8),      # paper's 3x3, 3-channel SCONV
    (1, 8, 16, 8, 3, 3, 16),
    (1, 6, 12, 4, 2, 2, 4),
    (2, 7, 9, 5, 1, 1, 6),        # pointwise
])
def test_sconv_matches_oracle(n, h, w, c, kh, kw, f, rng):
    img = jnp.asarray(rng.normal(size=(n, h, w, c)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(kh, kw, c, f)), jnp.float32)
    got = KC.mma_conv2d(img, ker, interpret=True)
    want = ref.conv2d(img, ker)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,h,w,c,kh,kw,f,stride", [
    (2, 8, 14, 5, 2, 3, 8, (1, 1)),   # C>1, KW>1: panel order is load-bearing
    (1, 9, 17, 3, 3, 3, 16, (1, 1)),
    (1, 10, 15, 4, 3, 3, 8, (2, 2)),  # strided shifts reorder the panel too
])
def test_sconv_fuse_kw_panel_matches_unfused(n, h, w, c, kh, kw, f, stride,
                                             rng):
    """Regression guard for the fused KW panel: the kw-major concatenation
    in `_sconv_kernel` must match `w_ref.reshape(kw_total * c, -1)`'s
    (kw, c) flattening.  Pin fuse_kw=True against fuse_kw=False and the
    ref backend so a future reorder of either side fails loudly instead of
    producing plausible-but-wrong convolutions."""
    from repro.core import facility, lowering
    img = jnp.asarray(rng.normal(size=(n, h, w, c)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(kh, kw, c, f)), jnp.float32)
    fused = KC.mma_conv2d(img, ker, stride=stride, interpret=True,
                          fuse_kw=True)
    unfused = KC.mma_conv2d(img, ker, stride=stride, interpret=True,
                            fuse_kw=False)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-5)
    want = facility.contract(
        facility.CONV2D, img, ker,
        plan=lowering.Plan(ger=Ger.F32GER, backend="ref", stride=stride,
                           out_dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_sconv_matches_lax_conv(rng):
    """Cross-check the oracle itself against lax.conv."""
    img = jnp.asarray(rng.normal(size=(2, 10, 24, 3)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(3, 3, 3, 8)), jnp.float32)
    want = jax.lax.conv_general_dilated(
        img, ker, (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(ref.conv2d(img, ker)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_vmem_budget_guard():
    """The TPU analogue of 'don't spill accumulators' must reject
    oversized virtual accumulator tiles."""
    from repro.core import tiling
    with pytest.raises(ValueError, match="spilling MMA accumulators"):
        tiling.assert_fits_vmem(tiling.BlockConfig(4096, 4096, 1024),
                                Ger.BF16GER2)


def test_choose_blocks_fits_and_aligned():
    from repro.core import tiling
    for (m, n, k) in [(128, 128, 128), (4096, 4096, 4096), (8, 200, 77),
                      (1000000, 256, 512)]:
        for kind in [Ger.BF16GER2, Ger.F32GER, Ger.I8GER4, Ger.F64GER]:
            cfg = tiling.choose_blocks(m, n, k, kind)
            tiling.assert_fits_vmem(cfg, kind)
            assert cfg.bn % 128 == 0 and cfg.bk % 128 == 0


# ----------------------------------------------------------------------
# Grid-native batch (kernel level)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", [Ger.BF16GER2, Ger.F32GER, Ger.I8GER4],
                         ids=lambda k: k.value)
def test_gemm_batched_matches_per_element(kind, rng):
    """A 3-D operand pair runs the batch axis as a grid dimension and is
    bit-for-bit the per-element 2-D kernel at the same block config —
    fringe shapes included."""
    b, m, k, n = 3, 33, 57, 130
    x = jnp.stack([_rand_for(kind, (m, k), rng) for _ in range(b)])
    pol = policy(kind)
    ydt = jnp.dtype(pol.y_dtype)
    if ydt == jnp.uint8:
        y = jnp.asarray(rng.integers(0, 256, (b, k, n)), jnp.uint8)
    elif ydt == jnp.int16:
        y = jnp.asarray(rng.integers(-1000, 1000, (b, k, n)), jnp.int16)
    else:
        y = jnp.asarray(rng.normal(size=(b, k, n)), ydt)
    blk = (32, 128, 128)
    got = K.mma_gemm(x, y, kind=kind, block=blk, interpret=True)
    base = jnp.stack([K.mma_gemm(x[i], y[i], kind=kind, block=blk,
                                 interpret=True) for i in range(b)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_gemm_batched_acc_and_epilogue(rng):
    """The batched kernel threads accumulator seeds, accumulate forms,
    and the fused epilogue through the batch grid axis."""
    from repro.kernels.epilogue import Epilogue
    b, m, k, n = 2, 16, 32, 24
    x = jnp.asarray(rng.normal(size=(b, m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(b, k, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, m, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(b, m, n)), jnp.float32)
    blk = (16, 128, 128)
    got = K.mma_gemm(x, y, c, kind=Ger.F32GER, block=blk, alpha=0.5,
                     beta=2.0, interpret=True)
    want = 0.5 * (np.einsum("bmk,bkn->bmn", x, y) + 2.0 * np.asarray(c))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    ep = Epilogue(bias=True, activation="relu", residual=True)
    got = K.mma_gemm(x, y, kind=Ger.F32GER, block=blk, ep=ep, bias=bias,
                     residual=res, interpret=True)
    want = np.maximum(np.einsum("bmk,bkn->bmn", x, y)
                      + np.asarray(bias), 0.0) + np.asarray(res)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_gemm_masks_streamed_into_kernel(rng):
    """Kernel-level pm* predicates: masks ride as VMEM operands and match
    the pm_ger oracle; a poisoned disabled row yields exact zeros."""
    m, k, n = 48, 64, 96
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    xm = jnp.asarray(rng.random(m) > 0.3)
    ym = jnp.asarray(rng.random(n) > 0.3)
    pm = jnp.asarray(rng.random(k) > 0.3)
    got = K.mma_gemm(x, y, kind=Ger.F32GER, block=(32, 128, 128),
                     masks=(xm, ym, pm), interpret=True)
    want = ref.pm_ger(x, y, Ger.F32GER, xm, ym, pm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    xbad = x.at[5].set(jnp.nan)
    got = K.mma_gemm(xbad, y, kind=Ger.F32GER, block=(32, 128, 128),
                     masks=(jnp.ones(m, bool).at[5].set(False), None, None),
                     interpret=True)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_array_equal(np.asarray(got[5]), np.zeros(n))


# ----------------------------------------------------------------------
# fuse_kw gating ((KW*C) % 128), as pure logic
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kw,c,interpret,want", [
    (3, 4, True, True),      # interpret mode: no lane constraint
    (3, 4, False, False),    # compiled: 12 lanes -> fall back to KW dots
    (2, 64, False, True),    # compiled: 128 lanes -> MXU-liftable
    (3, 128, False, True),   # compiled: 384 lanes -> aligned
    (3, 129, False, False),  # compiled: 387 lanes -> misaligned
    (1, 128, True, False),   # KW == 1: nothing to fuse, either mode
    (1, 128, False, False),
])
def test_select_fuse_kw_gate(kw, c, interpret, want):
    """The auto gate as pure logic: fused exactly when there is a KW span
    to hoist AND the concatenated panel is lane-aligned (or interpret
    mode, which has no lane constraint)."""
    assert KC.select_fuse_kw(kw, c, interpret) is want


def test_fuse_kw_auto_selection_feeds_compiled_fallback(monkeypatch, rng):
    """fuse_kw=None consults select_fuse_kw with the kernel's actual
    (kw, c, interpret) triple — the compiled-mode fallback is chosen by
    the gate, not hardcoded to interpret behaviour."""
    seen = {}
    real = KC.select_fuse_kw

    def spy(kw, c, interpret):
        seen["args"] = (kw, c, interpret)
        return real(kw, c, interpret)

    monkeypatch.setattr(KC, "select_fuse_kw", spy)
    img = jnp.asarray(rng.normal(size=(1, 5, 6, 4)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.float32)
    out = KC.mma_conv2d(img, ker, interpret=True)
    assert seen["args"] == (3, 4, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.conv2d(img, ker)),
                               rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# Depthwise resident-accumulator kernel
# ----------------------------------------------------------------------

@pytest.mark.parametrize("stride", [(1, 1), (1, 2), (2, 1)])
def test_depthwise_kernel_matches_oracle(stride, rng):
    img = jnp.asarray(rng.normal(size=(2, 9, 11, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(3, 4, 6)), jnp.float32)
    got = KC.mma_depthwise_conv2d(img, taps, stride=stride, interpret=True)
    want = ref.depthwise_conv(img, taps, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_depthwise_kernel_fused_epilogue_and_channel_fringe(rng):
    """bias+silu fuse into the deprime store; a channel count off the
    block lattice exercises the channel-fringe path."""
    from repro.kernels.epilogue import Epilogue, apply as ep_apply
    img = jnp.asarray(rng.normal(size=(1, 7, 8, 5)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(2, 3, 5)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(5,)), jnp.float32)
    ep = Epilogue(bias=True, activation="silu")
    got = KC.mma_depthwise_conv2d(img, taps, bc=4, ep=ep, bias=bias,
                                  interpret=True)
    want = ep_apply(ref.depthwise_conv(img, taps), ep, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
