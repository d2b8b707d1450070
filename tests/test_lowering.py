"""The lowering registry behind ``facility.contract``.

Covers the api_redesign acceptance surface:

  * cross-backend equivalence: for every registered (op-class, ger-family)
    pair, the pallas-interpret / xla / ref lowerings agree to the family's
    policy tolerance on the same Plan — including ``I8GER4``-as-quant
    (Dequant deprime) and the saturating integer forms;
  * the ``F32GER_3XBF16`` expansion hook replaces the branches formerly
    copy-pasted across ``facility.fdot`` / ``fdot_fused`` (regression:
    the kind dispatches identically via both shims and via ``contract``);
  * einsum-only workloads (MoE expert dots, attention scores) normalize to
    GEMMs and dispatch to the Pallas kernels;
  * registry pluggability and the shims' DeprecationWarning escalation for
    in-repo callers.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import facility, lowering, quant
from repro.core.precision import Ger, policy
from repro.kernels import epilogue as E

jax.config.update("jax_platform_name", "cpu")

Plan = lowering.Plan

# Per-family comparison tolerance between backends ("policy tolerance"):
# integer accumulators are exact; fp32/fp64 single-pass lowerings agree to
# blocked-vs-single-dot rounding; reduced-precision inputs and the 3xbf16
# emulation accumulate panel-wise in the kernel, so they get the loosest.
TOL = {
    Ger.F64GER: dict(rtol=1e-12, atol=1e-12),
    Ger.F32GER: dict(rtol=1e-4, atol=3e-5),
    Ger.BF16GER2: dict(rtol=1e-4, atol=3e-5),
    Ger.F16GER2: dict(rtol=1e-4, atol=3e-5),
    Ger.F32GER_3XBF16: dict(rtol=1e-3, atol=1e-3),
    Ger.I16GER2: dict(exact=True),
    Ger.I8GER4: dict(exact=True),
    Ger.I4GER8: dict(exact=True),
}

ALL_KINDS = list(TOL)


def _operands(kind, m, k, n, rng):
    pol = policy(kind)
    if pol.packed_int4:
        x = jnp.asarray(rng.integers(-128, 128, (m, k // 2)), jnp.int8)
        y = jnp.asarray(rng.integers(-128, 128, (k // 2, n)), jnp.int8)
    elif jnp.issubdtype(pol.acc_dtype, jnp.integer):
        x = jnp.asarray(rng.integers(-100, 100, (m, k)), pol.x_dtype)
        hi = 256 if jnp.dtype(pol.y_dtype) == jnp.uint8 else 100
        lo = 0 if jnp.dtype(pol.y_dtype) == jnp.uint8 else -100
        y = jnp.asarray(rng.integers(lo, hi, (k, n)), pol.y_dtype)
    else:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    return x, y


def _assert_close(kind, got, want):
    tol = TOL[kind]
    if tol.get("exact"):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   np.asarray(want, np.float64),
                                   rtol=tol["rtol"], atol=tol["atol"])


# ----------------------------------------------------------------------
# Cross-backend equivalence, per registered (op-class, ger-family) pair
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ALL_KINDS, ids=lambda k: k.value)
def test_gemm_backends_agree(kind, rng):
    """Every backend registered for ('gemm', kind) computes the same
    architected result from the same Plan."""
    backends = lowering.backends_for("gemm", kind)
    assert set(backends) == {"pallas", "xla", "ref"}, backends
    m, k, n = 48, 64, 128
    x, y = _operands(kind, m, k, n, rng)

    def run():
        outs = {}
        for b in backends:
            outs[b] = facility.contract(
                "mk,kn->mn", x, y,
                plan=Plan(ger=kind, backend=b, out_dtype=lowering.ACC,
                          block=(32, 128, 128)))
        return outs

    if kind == Ger.F64GER:
        with jax.enable_x64(True):
            outs = run()
            ref = outs.pop("ref")
            for b, got in outs.items():
                _assert_close(kind, got, ref)
        return
    outs = run()
    ref = outs.pop("ref")
    for b, got in outs.items():
        _assert_close(kind, got, ref)


@pytest.mark.parametrize("kind", [Ger.BF16GER2, Ger.F32GER, Ger.I8GER4],
                         ids=lambda k: k.value)
def test_gemm_backends_agree_with_acc_and_fringe(kind, rng):
    """Accumulate form + fringe shape (non-multiple M/K/N)."""
    m, k, n = 33, 57, 130
    x, y = _operands(kind, m, k, n, rng)
    c = (jnp.asarray(rng.integers(-5, 5, (m, n)), jnp.int32)
         if jnp.issubdtype(policy(kind).acc_dtype, jnp.integer)
         else jnp.asarray(rng.normal(size=(m, n)), jnp.float32))
    outs = [facility.contract(
        "mk,kn->mn", x, y, acc=c,
        plan=Plan(ger=kind, backend=b, out_dtype=lowering.ACC))
        for b in lowering.backends_for("gemm", kind)]
    for got in outs[1:]:
        _assert_close(kind, got, outs[0])


@pytest.mark.parametrize("spec,shapes", [
    ("ecd,edf->ecf", ((4, 8, 32), (4, 32, 16))),        # MoE expert dots
    ("bqhd,bkhd->bhqk", ((2, 8, 4, 16), (2, 12, 4, 16))),  # attn scores
    ("bhqk,bkhd->bqhd", ((2, 4, 8, 12), (2, 12, 4, 16))),  # attn values
    ("bcln,bcsn->bcls", ((2, 3, 8, 16), (2, 3, 8, 16))),   # SSD intra
    ("tkd,tk->td", ((6, 2, 8), (6, 2))),                # MoE un-scatter
    ("bn,bhp->bhnp", ((2, 8), (2, 3, 4))),              # outer product
])
def test_einsum_specs_normalize_and_backends_agree(spec, shapes, rng):
    """feinsum-class specs route through the gemm normalizer on every
    backend and agree with plain jnp.einsum."""
    a = jnp.asarray(rng.normal(size=shapes[0]), jnp.float32)
    b = jnp.asarray(rng.normal(size=shapes[1]), jnp.float32)
    want = jnp.einsum(spec, a, b)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            spec, a, b, plan=Plan(ger=Ger.F32GER, backend=backend,
                                  out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=backend)


def test_batched_expansion_chain_backends_agree(rng):
    """Regression: a batched F32GER_3XBF16 contraction chains three
    BF16GER2 passes per batch element; the ref backend once dropped the
    inter-pass accumulator (returning only the last pass)."""
    a = jnp.asarray(rng.normal(size=(3, 16, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(3, 32, 8)), jnp.float32)
    want = jnp.einsum("bmk,bkn->bmn", a, b)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "bmk,bkn->bmn", a, b,
            plan=Plan(ger=Ger.F32GER_3XBF16, backend=backend,
                      out_dtype=jnp.float32))
        _assert_close(Ger.F32GER_3XBF16, got, want)


def test_ellipsis_right_aligns_like_einsum(rng):
    """Regression: when both operands carry '...' with different ranks,
    the ellipsis dims must pair right-aligned (einsum semantics), not
    left-aligned."""
    a = jnp.asarray(rng.normal(size=(2, 7, 3, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(7, 4, 5)), jnp.float32)
    want = jnp.einsum("...ij,...jk->...ik", a, b)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "...ij,...jk->...ik", a, b,
            plan=Plan(ger=Ger.F32GER, backend=backend,
                      out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=backend)


def test_ellipsis_broadcast_falls_back_to_einsum(rng):
    """A size-1-vs-n ellipsis dim is einsum broadcasting the GEMM
    normalizer cannot express; it must route to the einsum lowering and
    still match jnp.einsum."""
    a = jnp.asarray(rng.normal(size=(1, 3, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(7, 4, 5)), jnp.float32)
    want = jnp.einsum("...ij,...jk->...ik", a, b)
    lowering.DISPATCH_COUNTS.clear()
    got = facility.contract(
        "...ij,...jk->...ik", a, b,
        plan=Plan(ger=Ger.F32GER, backend="xla", out_dtype=jnp.float32))
    assert lowering.DISPATCH_COUNTS[
        ("xla", "einsum", Ger.F32GER.value)] == 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", [Ger.I16GER2, Ger.I8GER4],
                         ids=lambda k: k.value)
def test_saturating_backends_agree(kind, rng):
    """Saturating forms: every registered backend clamps identically —
    at the saturation point and away from it."""
    backends = lowering.backends_for("gemm.saturating", kind)
    assert "xla" in backends and "ref" in backends
    pol = policy(kind)
    hi = 32767 if pol.x_dtype == jnp.int16 else 127
    xs = [jnp.full((4, 32), hi, pol.x_dtype),
          jnp.asarray(rng.integers(-50, 50, (4, 32)), pol.x_dtype)]
    yhi = 255 if jnp.dtype(pol.y_dtype) == jnp.uint8 else hi
    ys = [jnp.full((32, 4), yhi, pol.y_dtype),
          jnp.asarray(rng.integers(0 if yhi == 255 else -50, 50, (32, 4)),
                      pol.y_dtype)]
    for x, y in zip(xs, ys):
        outs = [facility.contract(
            "mk,kn->mn", x, y,
            plan=Plan(ger=kind, saturating=True, backend=b,
                      out_dtype=lowering.ACC)) for b in backends]
        for got in outs[1:]:
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(outs[0]))
    # the saturating path really saturates (seed the accumulator near the
    # positive rail; every rank-r group of positive products then clamps)
    near_top = jnp.full((4, 4), np.iinfo(np.int32).max - 1000, jnp.int32)
    top = facility.contract(
        "mk,kn->mn", xs[0], ys[0], acc=near_top,
        plan=Plan(ger=kind, saturating=True, backend="xla",
                  out_dtype=lowering.ACC))
    assert int(top.max()) == np.iinfo(np.int32).max
    ref_top = facility.contract(
        "mk,kn->mn", xs[0], ys[0], acc=near_top,
        plan=Plan(ger=kind, saturating=True, backend="ref",
                  out_dtype=lowering.ACC))
    np.testing.assert_array_equal(np.asarray(top), np.asarray(ref_top))


def test_saturating_rejects_epilogue_and_forms(rng):
    """Regression: saturating plans must refuse (not silently drop)
    fused epilogues and alpha/beta/neg accumulate forms."""
    x = jnp.ones((4, 32), jnp.int16)
    y = jnp.ones((32, 4), jnp.int16)
    bias = jnp.ones((4,), jnp.int32)
    with pytest.raises(ValueError, match="saturating forms"):
        facility.contract(
            "mk,kn->mn", x, y, bias=bias,
            plan=Plan(ger=Ger.I16GER2, saturating=True, backend="xla",
                      epilogue=E.Epilogue(bias=True)))
    with pytest.raises(ValueError, match="saturating forms"):
        facility.contract(
            "mk,kn->mn", x, y,
            plan=Plan(ger=Ger.I16GER2, saturating=True, backend="xla",
                      alpha=2.0))
    # out_dtype IS honoured
    out = facility.contract(
        "mk,kn->mn", x, y,
        plan=Plan(ger=Ger.I16GER2, saturating=True, backend="xla",
                  out_dtype=jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((4, 4), 32.0, np.float32))


def test_acc_seed_with_leading_dims_agrees_across_backends(rng):
    """Regression: an accumulator seed on an fdot-shaped ND spec must
    lower on every backend (acc reshapes like the residual does)."""
    x = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2, 4, 6)), jnp.float32)
    outs = [facility.contract(
        facility.DOT, x, w, acc=c,
        plan=Plan(ger=Ger.F32GER, backend=b, out_dtype=jnp.float32))
        for b in ("pallas", "xla", "ref")]
    want = jnp.einsum("bsk,kn->bsn", x, w) + c
    for b, got in zip(("pallas", "xla", "ref"), outs):
        assert got.shape == (2, 4, 6), b
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=b)


def test_quant_plan_backends_agree(rng):
    """quant.qdot IS an I8GER4 plan: the int32 ger is exact on every
    backend and the shared Dequant deprime makes the fp32 results
    bit-identical."""
    x = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    wq, ws = quant.quantize_weight(w)
    outs = [np.asarray(quant.qdot(x, wq, ws, backend=b))
            for b in ("pallas", "xla", "ref")]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    rel = float(np.linalg.norm(outs[0] - np.asarray(x @ w))
                / np.linalg.norm(np.asarray(x @ w)))
    assert rel < 0.02, rel


def test_fused_epilogue_backends_agree(rng):
    """A fused-epilogue Plan lowers equivalently on all three backends."""
    m, k, n = 32, 48, 128
    x, y = _operands(Ger.F32GER, m, k, n, rng)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    ep = E.Epilogue(bias=True, activation="gelu", residual=True)
    outs = [facility.contract(
        "mk,kn->mn", x, y, bias=bias, residual=res,
        plan=Plan(ger=Ger.F32GER, backend=b, epilogue=ep,
                  out_dtype=jnp.float32))
        for b in ("pallas", "xla", "ref")]
    for got in outs[1:]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(outs[0]),
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# F32GER_3XBF16: one expansion hook instead of copy-pasted branches
# ----------------------------------------------------------------------

def test_3xbf16_is_an_expansion_hook():
    rep, hook = lowering.expansion_for(Ger.F32GER_3XBF16)
    assert rep == Ger.BF16GER2
    x = jnp.ones((4, 8), jnp.float32) * 1.234567
    passes = hook(x, jnp.ones((8, 4), jnp.float32))
    assert [k for _, _, k in passes] == [Ger.BF16GER2] * 3
    # hi + lo recovers the fp32 operand to ~16 mantissa bits (the
    # emulation's premise: two bf16 limbs per fp32 value)
    (xh, _, _), _, (xl, _, _) = passes
    np.testing.assert_allclose(
        np.asarray(xh, np.float32) + np.asarray(xl, np.float32),
        np.asarray(x), rtol=1e-5, atol=0)


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pallas", "xla"])
def test_3xbf16_dispatches_identically_via_both_shims(use_pallas, rng):
    """Regression for the deduplicated special case: fdot and fdot_fused
    route F32GER_3XBF16 through the same registered expansion, so the
    shims agree bit-for-bit with contract and with each other."""
    x = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    cfg = facility.FacilityConfig(ger=Ger.F32GER_3XBF16,
                                  out_dtype=jnp.float32,
                                  use_pallas=use_pallas, interpret=True)
    with facility.configure(cfg), warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        plain_shim = facility.fdot(x, w)
        fused_shim = facility.fdot_fused(x, w, bias=bias)
        plain = facility.contract(facility.DOT, x, w)
        fused = facility.contract(facility.DOT, x, w, bias=bias)
    np.testing.assert_array_equal(np.asarray(plain_shim), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(fused_shim), np.asarray(fused))
    # fused == plain + bias exactly (single shared deprime)
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(plain) + np.asarray(bias),
                               rtol=1e-6, atol=1e-6)
    # and the emulation still beats plain bf16 accuracy-wise
    exact = np.asarray(x) @ np.asarray(w)
    bf = np.asarray(jnp.asarray(x, jnp.bfloat16) @ jnp.asarray(
        w, jnp.bfloat16), np.float32)
    assert np.abs(np.asarray(plain) - exact).max() \
        < 0.05 * np.abs(bf - exact).max()


def test_3xbf16_special_case_gone_from_facility():
    """The facility surface owns no per-kind branches any more."""
    import inspect
    src = inspect.getsource(facility)
    assert "F32GER_3XBF16" not in src
    from repro.kernels import ops
    src = inspect.getsource(ops.mma_dot) + inspect.getsource(
        ops.mma_dot_fused)
    assert "F32GER_3XBF16" not in src


# ----------------------------------------------------------------------
# Einsum-only workloads now reach the Pallas kernels
# ----------------------------------------------------------------------

def test_moe_expert_dots_dispatch_to_pallas(rng):
    xe = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32, use_pallas=True,
            interpret=True)):
        got = facility.contract("ecd,edf->ecf", xe, w1)
    assert lowering.DISPATCH_COUNTS[("pallas", "gemm", Ger.F32GER.value)] \
        == 1, dict(lowering.DISPATCH_COUNTS)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("ecd,edf->ecf",
                                                     xe, w1)),
                               rtol=1e-4, atol=1e-5)


def test_attention_scores_dispatch_to_pallas(rng):
    q = jnp.asarray(rng.normal(size=(2, 16, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 24, 4, 32)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32, use_pallas=True,
            interpret=True)):
        got = facility.contract("bqhd,bkhd->bhqk", q, k)
    assert lowering.DISPATCH_COUNTS[("pallas", "gemm", Ger.F32GER.value)] \
        == 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("bqhd,bkhd->bhqk",
                                                     q, k)),
                               rtol=1e-4, atol=1e-5)


def test_pallas_consults_autotune_cache(tmp_path, monkeypatch, rng):
    """The registry's block resolver honours planted autotune winners for
    normalized einsum workloads too (cache consulted outside jit)."""
    from repro.core import autotune, tiling
    cache = autotune.AutotuneCache(tmp_path / "at.json")
    monkeypatch.setattr(autotune, "_DEFAULT_CACHE", cache)
    cache.put(autotune.cache_key(Ger.F32GER, 16, 64, 32),
              tiling.BlockConfig(8, 128, 128), source="traced", score=0.0)
    assert lowering.resolve_block(Ger.F32GER, 16, 64, 32, None) \
        == (8, 128, 128)
    # explicit block still wins
    assert lowering.resolve_block(Ger.F32GER, 16, 64, 32, (32, 128, 128)) \
        == (32, 128, 128)
    xe = jnp.asarray(rng.normal(size=(4, 16, 32)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(4, 32, 64)), jnp.float32)
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32, use_pallas=True,
            interpret=True)):
        got = facility.contract("ecd,edf->ecf", xe, w1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("ecd,edf->ecf",
                                                     xe, w1)),
                               rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# Spec normalizer
# ----------------------------------------------------------------------

def test_parse_spec_classification():
    p = lowering.parse_spec("bqhd,bkhd->bhqk", 4, 4)
    assert p.batch == ("b", "h")
    assert p.contract == ("d",)
    assert p.x_free == ("q",) and p.y_free == ("k",)
    assert p.out_perm is None
    p = lowering.parse_spec("...k,kn->...n", 3, 2)
    # ellipsis labels come off the END of the pool (right-aligned pairing)
    assert p.x_free == ("V", "U") and p.contract == ("k",)
    assert p.is_plain_2d is False
    assert lowering.parse_spec("mk,kn->mn", 2, 2).is_plain_2d
    # sum-reductions and diagonals fall back to the einsum lowering
    assert lowering.parse_spec("mk,kn->n", 2, 2) is None
    assert lowering.parse_spec("mm,mn->mn", 2, 2) is None


def test_unparseable_spec_falls_back_to_einsum(rng):
    x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32, use_pallas=True,
            interpret=True)):
        got = facility.contract("mm,mn->mn", x, y)   # diagonal of x
    assert lowering.DISPATCH_COUNTS[("xla", "einsum", Ger.F32GER.value)] \
        == 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("mm,mn->mn", x, y)),
                               rtol=1e-5, atol=1e-5)


def test_label_size_mismatch_raises(rng):
    x = jnp.zeros((4, 8), jnp.float32)
    y = jnp.zeros((9, 4), jnp.float32)
    with pytest.raises(ValueError, match="size mismatch"):
        facility.contract("mk,kn->mn", x, y,
                          plan=Plan(ger=Ger.F32GER,
                                    out_dtype=jnp.float32))


# ----------------------------------------------------------------------
# Conv op-class: the canonical conv specs on every backend
# ----------------------------------------------------------------------

def _lax_conv(img, ker, stride, padding):
    return jax.lax.conv_general_dilated(
        img, ker, stride, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("stride,padding", [
    ((1, 1), "valid"), ((2, 2), "same"), ((2, 3), "valid")])
def test_conv2d_backends_agree(stride, padding, rng):
    """facility.CONV2D lowers equivalently on pallas/xla/ref and matches
    the lax.conv oracle, across strides and paddings."""
    assert set(lowering.backends_for("conv", Ger.F32GER)) \
        == {"pallas", "xla", "ref"}
    img = jnp.asarray(rng.normal(size=(2, 10, 13, 3)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(3, 3, 3, 8)), jnp.float32)
    want = _lax_conv(img, ker, stride, padding.upper())
    lowering.DISPATCH_COUNTS.clear()
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            facility.CONV2D, img, ker,
            plan=Plan(ger=Ger.F32GER, backend=backend, stride=stride,
                      padding=padding, out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=backend)
        assert lowering.DISPATCH_COUNTS[
            (backend, "conv", Ger.F32GER.value)] == 1


def test_conv1d_stride2_same_backends_agree(rng):
    """The whisper-stem shape: 1-D conv, stride 2, SAME, fused bias+gelu."""
    x = jnp.asarray(rng.normal(size=(2, 16, 5)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 5, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    want = _lax_conv(x[:, None], w[None], (1, 2), "SAME")[:, 0]
    want = np.asarray(E.apply(jnp.asarray(want),
                              E.Epilogue(bias=True, activation="gelu"),
                              bias=bias))
    outs = [facility.contract(
        facility.CONV1D, x, w, bias=bias,
        plan=Plan(ger=Ger.F32GER, backend=b, stride=2, padding="same",
                  epilogue=E.Epilogue(bias=True, activation="gelu"),
                  out_dtype=jnp.float32))
        for b in ("pallas", "xla", "ref")]
    for b, got in zip(("pallas", "xla", "ref"), outs):
        assert got.shape == (2, 8, 8), b
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4, err_msg=b)


@pytest.mark.parametrize("padding", ["causal", "valid"])
def test_depthwise_conv1d_backends_agree(padding, rng):
    """The mamba2 causal-conv shape: per-channel taps, left padding."""
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    xin = jnp.pad(x, ((0, 0), (3, 0), (0, 0))) if padding == "causal" else x
    ol = xin.shape[1] - 3
    want = sum(np.asarray(xin[:, i:i + ol, :], np.float64) * np.asarray(
        w[i], np.float64) for i in range(4))
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            facility.CONV1D_DEPTHWISE, x, w,
            plan=Plan(ger=Ger.F32GER, backend=backend, padding=padding,
                      out_dtype=jnp.float32))
        assert got.shape == (2, ol, 6), backend
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-5, err_msg=backend)


def test_conv_bf16_policy_casts_inputs(rng):
    """A BF16GER2 conv plan rounds the operands to bf16 before the update
    (the family's architected input dtype) on every backend."""
    img = jnp.asarray(rng.normal(size=(1, 6, 8, 4)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.float32)
    want = _lax_conv(img.astype(jnp.bfloat16).astype(jnp.float32),
                     ker.astype(jnp.bfloat16).astype(jnp.float32),
                     (1, 1), "VALID")
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            facility.CONV2D, img, ker,
            plan=Plan(ger=Ger.BF16GER2, backend=backend,
                      out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=backend)


def test_conv_3xbf16_expansion_applies(rng):
    """Regression: a F32GER_3XBF16 conv plan must run the family's three
    chained BF16GER2 passes (conv is bilinear, so the hi/lo split applies
    exactly as for GEMM) — not a silent plain-f32 convolution."""
    img = jnp.asarray(rng.normal(size=(2, 4, 6, 16)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(1, 1, 16, 8)), jnp.float32)
    # A 1x1 conv IS a GEMM: the gemm op-class's 3xbf16 chain is the oracle.
    want = facility.contract(
        "mk,kn->mn", img.reshape(-1, 16), ker.reshape(16, 8),
        plan=Plan(ger=Ger.F32GER_3XBF16, backend="ref",
                  out_dtype=jnp.float32)).reshape(2, 4, 6, 8)
    f32 = facility.contract(
        facility.CONV2D, img, ker,
        plan=Plan(ger=Ger.F32GER, backend="ref", out_dtype=jnp.float32))
    assert float(jnp.abs(want - f32).max()) > 0  # families ARE distinct
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            facility.CONV2D, img, ker,
            plan=Plan(ger=Ger.F32GER_3XBF16, backend=backend,
                      out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=backend)


def test_depthwise_f32_runs_the_pallas_kernel(rng):
    """Depthwise (groups == C) no longer reroutes to XLA for f32
    accumulators: the resident-accumulator VPU kernel runs and matches
    the shift-and-sum oracle."""
    x = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    got = facility.contract(
        facility.CONV1D_DEPTHWISE, x, w,
        plan=Plan(ger=Ger.F32GER, backend="pallas", padding="causal",
                  out_dtype=jnp.float32))
    assert lowering.DISPATCH_COUNTS[
        ("pallas", "conv", Ger.F32GER.value)] == 1
    assert not any(k[0] == "xla" for k in lowering.DISPATCH_COUNTS)
    want = facility.contract(
        facility.CONV1D_DEPTHWISE, x, w,
        plan=Plan(ger=Ger.F32GER, backend="ref", padding="causal",
                  out_dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_depthwise_non_f32_acc_still_reroutes_to_xla(rng):
    """The conv kernels accumulate in f32 only: non-f32 families keep the
    pre-dispatch-count XLA reroute."""
    x = jnp.asarray(rng.normal(size=(1, 8, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    with jax.enable_x64(True):
        facility.contract(
            facility.CONV1D_DEPTHWISE, x.astype(jnp.float64),
            w.astype(jnp.float64),
            plan=Plan(ger=Ger.F64GER, backend="pallas", padding="causal",
                      out_dtype=jnp.float64))
    assert lowering.DISPATCH_COUNTS[("xla", "conv", Ger.F64GER.value)] == 1
    assert not any(k[0] == "pallas" for k in lowering.DISPATCH_COUNTS)


def test_depthwise_pallas_fused_epilogue_and_stride_backends_agree(rng):
    """The depthwise kernel threads the fused bias+silu deprime (mamba2's
    causal-conv epilogue) and strided reads, agreeing with xla/ref."""
    x = jnp.asarray(rng.normal(size=(2, 11, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    for stride in (1, 2):
        outs = {}
        for backend in ("pallas", "xla", "ref"):
            outs[backend] = facility.contract(
                facility.CONV1D_DEPTHWISE, x, w, bias=b,
                plan=Plan(ger=Ger.F32GER, backend=backend, stride=stride,
                          padding="same",
                          epilogue=E.Epilogue(bias=True, activation="silu"),
                          out_dtype=jnp.float32))
        for bk in ("xla", "ref"):
            np.testing.assert_allclose(
                np.asarray(outs["pallas"]), np.asarray(outs[bk]),
                rtol=1e-5, atol=1e-5, err_msg=f"stride={stride} vs {bk}")


def test_batched_conv_matches_per_image_baseline_bitwise(rng):
    """The conv kernels' batch axis (grid row axis) is bit-for-bit the
    per-image loop at fp32 — dense and depthwise."""
    x = jnp.asarray(rng.normal(size=(3, 7, 9, 4)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(3, 3, 4, 8)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    x1d = jnp.asarray(rng.normal(size=(3, 9, 4)), jnp.float32)
    plan2d = Plan(ger=Ger.F32GER, backend="pallas", out_dtype=jnp.float32)
    got = facility.contract(facility.CONV2D, x, ker, plan=plan2d)
    base = jnp.concatenate([
        facility.contract(facility.CONV2D, x[i:i + 1], ker, plan=plan2d)
        for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    pland = Plan(ger=Ger.F32GER, backend="pallas", padding="causal",
                 out_dtype=jnp.float32)
    got = facility.contract(facility.CONV1D_DEPTHWISE, x1d, taps, plan=pland)
    base = jnp.concatenate([
        facility.contract(facility.CONV1D_DEPTHWISE, x1d[i:i + 1], taps,
                          plan=pland)
        for i in range(3)])
    # The depthwise update is an elementwise VPU multiply-add, which XLA
    # CPU FMA-contracts differently with the grid trip count — one-ulp
    # drift, unlike the MXU dot updates above (those stay bit-for-bit).
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=0, atol=1e-6)


def test_causal_padding_is_1d_only(rng):
    img = jnp.zeros((1, 6, 8, 4), jnp.float32)
    ker = jnp.zeros((3, 3, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal padding is 1-D"):
        facility.contract(facility.CONV2D, img, ker,
                          plan=Plan(ger=Ger.F32GER, padding="causal"))


def test_conv_rejects_acc_and_forms(rng):
    img = jnp.zeros((1, 6, 8, 4), jnp.float32)
    ker = jnp.zeros((3, 3, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="conv contractions"):
        facility.contract(facility.CONV2D, img, ker,
                          acc=jnp.zeros((1, 4, 6, 8), jnp.float32),
                          plan=Plan(ger=Ger.F32GER))
    with pytest.raises(ValueError, match="conv contractions"):
        facility.contract(facility.CONV2D, img, ker,
                          plan=Plan(ger=Ger.F32GER, alpha=2.0))
    # and stride/padding are conv-only vocabulary
    x = jnp.zeros((4, 8), jnp.float32)
    y = jnp.zeros((8, 4), jnp.float32)
    with pytest.raises(ValueError, match="conv specs only"):
        facility.contract("mk,kn->mn", x, y, plan=Plan(stride=2))


def test_whisper_frontend_routes_through_conv_op_class():
    """De-stubbed whisper: the encoder conv stem dispatches two conv-class
    contractions per forward (frontend_stub is OFF in the config)."""
    from repro.configs import get
    from repro.configs.base import reduced
    from repro.models import model as M
    cfg = reduced(get("whisper-small"))
    assert not cfg.frontend_stub and cfg.n_mels > 0
    params = M.init_params(cfg, jax.random.key(0))
    batch = {"tokens": jnp.zeros((1, cfg.decoder_len), jnp.int32),
             "labels": jnp.zeros((1, cfg.decoder_len), jnp.int32),
             "frames": jnp.ones((1, 16, cfg.n_mels), jnp.float32)}
    lowering.DISPATCH_COUNTS.clear()
    logits, _, _ = M.forward(params, batch, cfg)
    conv_calls = sum(v for k, v in lowering.DISPATCH_COUNTS.items()
                     if k[1] == "conv")
    assert conv_calls == 2, dict(lowering.DISPATCH_COUNTS)
    assert bool(jnp.isfinite(logits).all())


def test_mamba_causal_conv_routes_through_conv_op_class(rng):
    """The mamba2 depthwise causal conv is a registry dispatch now."""
    from repro.models import mamba2 as M2
    x = jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    b = jnp.zeros((6,), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    out, state = M2._causal_conv(x, w, b)
    assert sum(v for k, v in lowering.DISPATCH_COUNTS.items()
               if k[1] == "conv") == 1
    assert out.shape == x.shape and out.dtype == x.dtype
    assert state.shape == (2, 3, 6)
    # matches the hand-rolled shift-and-sum it replaced
    xin = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = jax.nn.silu(sum(
        xin[:, i:i + 8, :].astype(jnp.float32) * w[i] for i in range(4)) + b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------------
# Complex op-class: four real accumulate-form gers (pp/np)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", [Ger.F32GER, Ger.BF16GER2, Ger.F16GER2],
                         ids=lambda k: k.value)
def test_complex_backends_agree(kind, rng):
    assert set(lowering.backends_for("complex", kind)) \
        == {"pallas", "xla", "ref"}
    ar, ai = rng.normal(size=(16, 24)), rng.normal(size=(16, 24))
    br, bi = rng.normal(size=(24, 8)), rng.normal(size=(24, 8))
    a = jnp.asarray(ar + 1j * ai, jnp.complex64)
    b = jnp.asarray(br + 1j * bi, jnp.complex64)
    outs = {}
    lowering.DISPATCH_COUNTS.clear()
    for backend in ("pallas", "xla", "ref"):
        outs[backend] = facility.contract(
            "mk,kn->mn", a, b,
            plan=Plan(ger=kind, backend=backend, out_dtype=lowering.ACC))
        assert lowering.DISPATCH_COUNTS[
            (backend, "complex", kind.value)] == 1
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        _assert_close(kind, np.asarray(got).real, ref.real)
        _assert_close(kind, np.asarray(got).imag, ref.imag)
    if kind == Ger.F32GER:   # exact-dtype family: compare to numpy too
        want = (ar + 1j * ai) @ (br + 1j * bi)
        np.testing.assert_allclose(ref, want, rtol=1e-4, atol=1e-4)


def test_complex_np_accumulate_form_backends_agree(rng):
    """The negative-product (np) form with a complex accumulator seed —
    the accumulate form only blas3.complex_gemm's hand-coded chain used to
    exercise: out = C - X @ Y."""
    a = jnp.asarray(rng.normal(size=(8, 12)) + 1j * rng.normal(size=(8, 12)),
                    jnp.complex64)
    b = jnp.asarray(rng.normal(size=(12, 6)) + 1j * rng.normal(size=(12, 6)),
                    jnp.complex64)
    c = jnp.asarray(rng.normal(size=(8, 6)) + 1j * rng.normal(size=(8, 6)),
                    jnp.complex64)
    want = np.asarray(c) - np.asarray(a) @ np.asarray(b)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "mk,kn->mn", a, b, acc=c,
            plan=Plan(ger=Ger.F32GER, backend=backend, neg_product=True,
                      out_dtype=lowering.ACC))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4, err_msg=backend)


def test_complex_rejects_epilogue_and_permuted_output(rng):
    a = jnp.zeros((4, 8), jnp.complex64)
    b = jnp.zeros((8, 4), jnp.complex64)
    bias = jnp.zeros((4,), jnp.float32)
    with pytest.raises(ValueError, match="complex contractions"):
        facility.contract("mk,kn->mn", a, b, bias=bias,
                          plan=Plan(ger=Ger.F32GER,
                                    epilogue=E.Epilogue(bias=True)))
    # transposed output: the four-ger chain seeds accumulators in natural
    # order, so permuted specs are rejected rather than silently mis-seeded
    with pytest.raises(ValueError, match="natural output order"):
        facility.contract("mk,kn->nm", a, b, plan=Plan(ger=Ger.F32GER))


def test_complex_batched_backends_agree_and_match_vmapped_baseline(rng):
    """Batched complex contractions (the paper's batched-DFT case) lower
    through the grid-native batched gemm path on every backend; on pallas
    the result is bit-for-bit the per-element (vmapped-era) baseline at
    fp32 when the block config is pinned."""
    b = 3
    a = jnp.asarray(rng.normal(size=(b, 8, 12))
                    + 1j * rng.normal(size=(b, 8, 12)), jnp.complex64)
    c = jnp.asarray(rng.normal(size=(b, 12, 6))
                    + 1j * rng.normal(size=(b, 12, 6)), jnp.complex64)
    want = np.einsum("bmk,bkn->bmn", np.asarray(a), np.asarray(c))
    blk = (8, 128, 128)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "bmk,bkn->bmn", a, c,
            plan=Plan(ger=Ger.F32GER, backend=backend, block=blk,
                      out_dtype=lowering.ACC))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4, err_msg=backend)
    got = facility.contract(
        "bmk,bkn->bmn", a, c,
        plan=Plan(ger=Ger.F32GER, backend="pallas", block=blk,
                  out_dtype=lowering.ACC))
    base = jnp.stack([facility.contract(
        "mk,kn->mn", a[i], c[i],
        plan=Plan(ger=Ger.F32GER, backend="pallas", block=blk,
                  out_dtype=lowering.ACC)) for i in range(b)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_batched_dft_matches_per_signal_plan(rng):
    """blas3.dft on a (B, N, M) stack is one plan (single kernel launch
    per accumulate-form ger, shared twiddles) and matches the per-signal
    2-D plan and numpy's FFT."""
    from repro.kernels import blas3
    xb = jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32)
    for backend in ("pallas", "xla", "ref"):
        re, im = blas3.dft(xb, backend=backend)
        assert re.shape == xb.shape and im.shape == xb.shape
        want = np.fft.fft(np.asarray(xb, np.float64), axis=-2)
        np.testing.assert_allclose(np.asarray(re) + 1j * np.asarray(im),
                                   want, rtol=1e-3, atol=1e-3,
                                   err_msg=backend)
    re_b, im_b = blas3.dft(xb, backend="pallas")
    re1, im1 = blas3.dft(xb[2], backend="pallas")
    np.testing.assert_allclose(np.asarray(re_b[2]), np.asarray(re1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(im_b[2]), np.asarray(im1),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# Registry mechanics
# ----------------------------------------------------------------------

def test_lookup_falls_back_most_specific_first():
    key_args = ("gemm", Ger.BF16GER2, True)
    base = lowering.lookup("xla", *key_args)
    assert base is not None
    marker = lambda op: "specialized"              # noqa: E731
    lowering._REGISTRY[("xla", "gemm", Ger.BF16GER2, True)] = marker
    try:
        assert lowering.lookup("xla", "gemm", Ger.BF16GER2, True) is marker
        assert lowering.lookup("xla", "gemm", Ger.BF16GER2, False) is base
        assert lowering.lookup("xla", "gemm", Ger.F32GER, True) is base
    finally:
        del lowering._REGISTRY[("xla", "gemm", Ger.BF16GER2, True)]


def test_registered_lowering_is_pluggable(rng):
    """A plugged-in specialization wins dispatch for its exact key and is
    cleanly removable — the swappable-lowering claim."""
    calls = []

    @lowering.register("xla", "gemm", ger=Ger.F16GER2, fused=False)
    def _spy(op):
        calls.append(op.spec)
        return lowering._lower_xla_gemm(op)

    try:
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        out = facility.contract(
            "mk,kn->mn", x, y,
            plan=Plan(ger=Ger.F16GER2, backend="xla",
                      out_dtype=jnp.float32))
        assert calls == ["mk,kn->mn"]
        assert out.shape == (8, 8)
    finally:
        del lowering._REGISTRY[("xla", "gemm", Ger.F16GER2, False)]


def test_unknown_backend_raises():
    x = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown backend"):
        facility.contract("mk,kn->mn", x, x,
                          plan=Plan(backend="tpu-v9"))


# ----------------------------------------------------------------------
# Deprecation contract
# ----------------------------------------------------------------------

def test_shims_warn_and_match_contract(rng):
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32)):
        with pytest.warns(DeprecationWarning, match="facility.contract"):
            a = facility.fdot(x, w)
        b = facility.contract(facility.DOT, x, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shim_warning_attributed_to_in_repo_caller(rng):
    """The DeprecationWarning is raised at the *caller's* stacklevel, so
    the tier-1 filter (conftest) escalates repro.* callers to errors —
    the mechanism that keeps production code off the shims."""
    x = jnp.zeros((4, 8), jnp.float32)
    w = jnp.zeros((8, 4), jnp.float32)
    ns = {"__name__": "repro._fake_in_repo_caller",
          "facility": facility, "x": x, "w": w}
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", category=DeprecationWarning, module=r"repro\.")
        with pytest.raises(DeprecationWarning):
            eval("facility.fdot(x, w)", ns)
        # non-repro callers only get the warning
        ns["__name__"] = "somewhere.else"
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            eval("facility.fdot(x, w)", ns)


# ----------------------------------------------------------------------
# Grid-native batched execution (batch is a grid dimension, not a vmap)
# ----------------------------------------------------------------------

def test_batched_contraction_is_one_pallas_call(monkeypatch, rng):
    """A batched contraction (the MoE expert-dot spec) traces to exactly
    ONE pallas_call with the batch axis leading the grid — not a vmapped
    per-element re-trace."""
    from repro.kernels import mma_gemm as G
    calls = []
    real = G.pl.pallas_call

    def spy(*args, **kwargs):
        calls.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(G.pl, "pallas_call", spy)
    # distinctive shapes so the jit cache cannot satisfy this trace
    xe = jnp.asarray(rng.normal(size=(5, 23, 37)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(5, 37, 41)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    got = facility.contract(
        "ecd,edf->ecf", xe, w1,
        plan=Plan(ger=Ger.F32GER, backend="pallas", block=(16, 128, 128),
                  out_dtype=jnp.float32))
    assert lowering.DISPATCH_COUNTS[
        ("pallas", "gemm", Ger.F32GER.value)] == 1
    assert len(calls) == 1, calls
    assert len(calls[0]) == 4 and calls[0][0] == 5, calls
    np.testing.assert_allclose(
        np.asarray(got), np.einsum("ecd,edf->ecf", xe, w1),
        rtol=1e-4, atol=1e-5)


def test_batched_grid_native_bitwise_vs_vmapped_baseline_with_fringe(rng):
    """Grid-native batch == the per-element (vmapped-era) dispatch
    bit-for-bit at fp32 under a pinned block config — including
    non-divisible M/N/K fringes at b > 1."""
    b, m, k, n = 3, 50, 33, 70          # every dim off the block lattice
    x = jnp.asarray(rng.normal(size=(b, m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(b, k, n)), jnp.float32)
    blk = (32, 128, 128)
    plan = Plan(ger=Ger.F32GER, backend="pallas", block=blk,
                out_dtype=jnp.float32)
    got = facility.contract("bmk,bkn->bmn", x, y, plan=plan)
    base = jnp.stack([
        facility.contract("mk,kn->mn", x[i], y[i], plan=plan)
        for i in range(b)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("bmk,bkn->bmn", x, y),
                               rtol=1e-4, atol=1e-4)


def test_batched_acc_and_fused_epilogue_thread_through(rng):
    """Accumulator seeds, accumulate forms, and fused epilogues — formerly
    rejected on the batched Pallas path — thread through the batch grid
    axis on every backend."""
    b, m, k, n = 2, 16, 24, 32
    x = jnp.asarray(rng.normal(size=(b, m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(b, k, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, m, n)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    want_acc = 0.5 * (np.einsum("bmk,bkn->bmn", x, y)
                      + 2.0 * np.asarray(c))
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "bmk,bkn->bmn", x, y, acc=c,
            plan=Plan(ger=Ger.F32GER, backend=backend, block=(16, 128, 128),
                      alpha=0.5, beta=2.0, out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), want_acc,
                                   rtol=1e-4, atol=1e-4, err_msg=backend)
    want_ep = np.maximum(np.einsum("bmk,bkn->bmn", x, y)
                         + np.asarray(bias), 0.0)
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "bmk,bkn->bmn", x, y, bias=bias,
            plan=Plan(ger=Ger.F32GER, backend=backend, block=(16, 128, 128),
                      epilogue=E.Epilogue(bias=True, activation="relu"),
                      out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), want_ep,
                                   rtol=1e-4, atol=1e-4, err_msg=backend)


def test_batched_autotune_cache_keyed_on_b(tmp_path, monkeypatch, rng):
    """Batched dispatch consults the (b, m, n, k) cache key: a winner
    planted under b=4 drives the batched launch and is invisible to the
    same per-element shape at b=1 (and vice versa)."""
    from repro.core import autotune, tiling
    cache = autotune.AutotuneCache(tmp_path / "at.json")
    monkeypatch.setattr(autotune, "_DEFAULT_CACHE", cache)
    kind, m, n, k = Ger.F32GER, 16, 64, 32
    planted = tiling.BlockConfig(8, 128, 128)
    cache.put(autotune.cache_key(kind, m, n, k, b=4), planted,
              source="traced", score=0.0)
    assert lowering.resolve_block(kind, m, n, k, None, b=4) == (8, 128, 128)
    assert lowering.resolve_block(kind, m, n, k, None) is None
    assert autotune.lookup(kind, m, n, k, b=2) is None
    # and the batched kernel consumes the planted winner end-to-end
    xe = jnp.asarray(rng.normal(size=(4, m, k)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    got = facility.contract(
        "ecd,edf->ecf", xe, w1,
        plan=Plan(ger=kind, backend="pallas", out_dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("ecd,edf->ecf", xe, w1),
                               rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# gemm.masked: the pm* prefixed forms as in-kernel predicates
# ----------------------------------------------------------------------

def test_masked_backends_agree_with_pm_oracle(rng):
    """contract(..., masks=...) lowers via gemm.masked on every backend
    and matches the ref.pm_ger oracle (exactly for integer families)."""
    from repro.kernels import ref
    m, k, n = 48, 64, 96
    xm = jnp.asarray(rng.random(m) > 0.3)
    ym = jnp.asarray(rng.random(n) > 0.3)
    pm = jnp.asarray(rng.random(k) > 0.3)
    for kind in (Ger.F32GER, Ger.BF16GER2, Ger.I16GER2):
        x, y = _operands(kind, m, k, n, rng)
        pol = policy(kind)
        x, y = x.astype(pol.x_dtype), y.astype(pol.y_dtype)
        want = ref.pm_ger(x, y, kind, xm, ym, pm)
        for backend in ("pallas", "xla", "ref"):
            got = facility.contract(
                "mk,kn->mn", x, y, masks=(xm, ym, pm),
                plan=Plan(ger=kind, backend=backend, block=(32, 128, 128),
                          out_dtype=lowering.ACC))
            _assert_close(kind, got, want)


def test_masked_dispatches_via_gemm_masked_without_premasking(monkeypatch,
                                                             rng):
    """The acceptance check: dispatch counts name gemm.masked, the kernel
    receives the ORIGINAL operands (no pre-masked HBM materialization),
    and a NaN in a disabled row never reaches the output — the in-kernel
    predicate disables the lane instead of multiplying it."""
    from repro.core import lowering as L
    seen = []
    real = L._pallas_gemm_impl

    def spy(x, y, c, bias, residual, xmask, ymask, pmask, **kw):
        seen.append((np.asarray(x), np.asarray(y), xmask is not None))
        return real(x, y, c, bias, residual, xmask, ymask, pmask, **kw)

    monkeypatch.setattr(L, "_pallas_gemm_impl", spy)
    m, k, n = 16, 32, 16
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    x = x.at[3].set(jnp.nan)                    # disabled row poisoned
    y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    xm = jnp.ones(m, bool).at[3].set(False)
    ym = jnp.ones(n, bool)
    lowering.DISPATCH_COUNTS.clear()
    got = facility.contract(
        "mk,kn->mn", x, y, masks=(xm, ym, None),
        plan=Plan(ger=Ger.F32GER, backend="pallas", block=(16, 128, 128),
                  out_dtype=jnp.float32))
    assert lowering.DISPATCH_COUNTS[
        ("pallas", "gemm.masked", Ger.F32GER.value)] == 1
    [(x_seen, y_seen, had_masks)] = seen
    assert had_masks
    np.testing.assert_array_equal(x_seen, np.asarray(x))  # un-masked x
    np.testing.assert_array_equal(y_seen, np.asarray(y))
    # the disabled row is exact zeros — never NaN — because the lane was
    # disabled in-kernel, not multiplied by zero
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_array_equal(np.asarray(got[3]), np.zeros(n))


def test_masked_batched_and_with_acc(rng):
    """Masked forms compose with the batch grid axis and accumulator
    seeds (matrix-granularity pm* chaining)."""
    from repro.kernels import ref
    b, m, k, n = 3, 24, 32, 40
    x = jnp.asarray(rng.normal(size=(b, m, k)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(b, k, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, m, n)), jnp.float32)
    xm = jnp.asarray(rng.random(m) > 0.4)
    ym = jnp.asarray(rng.random(n) > 0.4)
    pm = jnp.asarray(rng.random(k) > 0.4)
    want = np.stack([np.asarray(ref.pm_ger(x[i], y[i], Ger.F32GER,
                                           xm, ym, pm, acc=c[i]))
                     for i in range(b)])
    for backend in ("pallas", "xla", "ref"):
        got = facility.contract(
            "bmk,bkn->bmn", x, y, acc=c, masks=(xm, ym, pm),
            plan=Plan(ger=Ger.F32GER, backend=backend, block=(16, 128, 128),
                      out_dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-4, err_msg=backend)


def test_masked_requires_natural_gemm_layout(rng):
    x = jnp.zeros((4, 8), jnp.float32)
    m = jnp.ones(4, bool)
    with pytest.raises(ValueError, match="normalized"):
        facility.contract("km,kn->mn", x, jnp.zeros((4, 6), jnp.float32),
                          masks=(m, None, None))
    with pytest.raises(ValueError, match="gemm-class"):
        facility.contract("mk,nk->m", x, x, masks=(m, None, None))
    with pytest.raises(ValueError, match="mask 0 has shape"):
        facility.contract("mk,kn->mn", x, jnp.zeros((8, 6), jnp.float32),
                          masks=(jnp.ones(5, bool), None, None))


# ----------------------------------------------------------------------
# Attn op-class: fused attention as a registry dispatch
# ----------------------------------------------------------------------

ATTN_PLAN_KW = dict(ger=Ger.F32GER, out_dtype=jnp.float32, block=(32, 32))


def _attn_operands(rng, b=2, sq=64, sk=64, h=4, kvh=2, d=32,
                   dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, sk, kvh, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, sk, kvh, d)), dtype)
    return q, k, v


def _attn_all_backends(q, k, v, plan_kw, masks=None, **contract_kw):
    outs = {}
    for backend in ("pallas", "xla", "ref"):
        outs[backend] = facility.contract(
            facility.ATTN, q, k, v, masks=masks,
            plan=Plan(backend=backend, **plan_kw), **contract_kw)
    return outs


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_attn_backends_agree(causal, rng):
    """facility.contract(ATTN, q, k, v) lowers equivalently on
    pallas (bounded flash grid) / xla (chunked two-dot) / ref (pinned
    two-contract oracle), and dispatch counts name the attn op-class."""
    assert set(lowering.backends_for("attn", Ger.F32GER)) \
        == {"pallas", "xla", "ref"}
    q, k, v = _attn_operands(rng)
    lowering.DISPATCH_COUNTS.clear()
    outs = _attn_all_backends(q, k, v, dict(ATTN_PLAN_KW, causal=causal))
    for backend in ("pallas", "xla", "ref"):
        assert lowering.DISPATCH_COUNTS[
            (backend, "attn", Ger.F32GER.value)] == 1
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=backend)


@pytest.mark.parametrize("kvh", [1, 2, 4])
def test_attn_gqa_group_sizes_agree(kvh, rng):
    """GQA head groups: every KV head serves H/KVH query heads through the
    kernel's BlockSpec index maps — equivalent to the materialized-repeat
    oracle at every group size."""
    q, k, v = _attn_operands(rng, kvh=kvh)
    outs = _attn_all_backends(q, k, v, dict(ATTN_PLAN_KW, causal=True))
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=f"{backend} kvh={kvh}")
    # and groups really differ from MHA when kvh < h
    if kvh < 4:
        q2, k2, v2 = _attn_operands(rng, kvh=4)
        alt = facility.contract(facility.ATTN, q, k2, v2,
                                plan=Plan(backend="ref", causal=True,
                                          **ATTN_PLAN_KW))
        assert float(jnp.abs(alt - ref).max()) > 1e-3


@pytest.mark.parametrize("window,q_offset", [(17, 0), (None, 16), (13, 16)])
def test_attn_window_and_q_offset_agree(window, q_offset, rng):
    """Sliding-window and decode-offset predicates (in-kernel pm*-style,
    grid-bounding on pallas) match across backends."""
    q, k, v = _attn_operands(rng, sq=32, sk=64)
    outs = _attn_all_backends(
        q, k, v, dict(ATTN_PLAN_KW, causal=True, window=window,
                      q_offset=q_offset))
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=backend)


def test_attn_valid_slot_mask_agrees(rng):
    """The (B, Sk) filled-slot predicate rides as masks=(valid,) and is
    applied to the streamed score tile on every backend."""
    q, k, v = _attn_operands(rng)
    valid = jnp.asarray(rng.random((2, 64)) > 0.3)
    outs = _attn_all_backends(q, k, v, dict(ATTN_PLAN_KW, causal=True),
                              masks=(valid,))
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=backend)


def test_attn_bf16_with_f32_accumulator(rng):
    """BF16GER2 attn plans round operands to bf16 but keep the online
    softmax / O accumulator in f32 (out_dtype=ACC exposes it)."""
    q, k, v = _attn_operands(rng, dtype=jnp.bfloat16)
    outs = _attn_all_backends(
        q, k, v, dict(ger=Ger.BF16GER2, causal=True, block=(32, 32),
                      out_dtype=lowering.ACC))
    for backend, got in outs.items():
        assert got.dtype == jnp.float32, backend
    ref = np.asarray(outs.pop("ref"), np.float32)
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=3e-2, atol=3e-2, err_msg=backend)


def test_attn_fused_residual_epilogue_backends_agree(rng):
    """The decoder-block residual hookup rides the attn deprime store
    (epilogue contract) equivalently on all backends, bit-for-bit equal
    to unfused + epilogue on pallas."""
    q, k, v = _attn_operands(rng)
    res = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    ep = E.Epilogue(residual=True)
    outs = _attn_all_backends(
        q, k, v, dict(ATTN_PLAN_KW, causal=True, epilogue=ep),
        residual=res)
    ref = np.asarray(outs.pop("ref"))
    for backend, got in outs.items():
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                                   atol=2e-4, err_msg=backend)
    base = facility.contract(facility.ATTN, q, k, v,
                             plan=Plan(backend="pallas", causal=True,
                                       **ATTN_PLAN_KW))
    fused = facility.contract(facility.ATTN, q, k, v, residual=res,
                              plan=Plan(backend="pallas", causal=True,
                                        epilogue=ep, **ATTN_PLAN_KW))
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(base + res))


def test_attn_rejects_bad_plans(rng):
    q, k, v = _attn_operands(rng)
    with pytest.raises(ValueError, match="three-operand"):
        facility.contract(facility.ATTN, q, k)
    with pytest.raises(ValueError, match="attn-spec vocabulary"):
        facility.contract("mk,kn->mn", jnp.zeros((4, 8), jnp.float32),
                          jnp.zeros((8, 4), jnp.float32),
                          jnp.zeros((8, 4), jnp.float32))
    with pytest.raises(ValueError, match="attn spec only"):
        facility.contract("mk,kn->mn", jnp.zeros((4, 8), jnp.float32),
                          jnp.zeros((8, 4), jnp.float32),
                          plan=Plan(causal=True))
    with pytest.raises(ValueError, match="float families"):
        facility.contract(facility.ATTN, q, k, v, plan=Plan(ger=Ger.I8GER4))
    with pytest.raises(ValueError, match="no accumulator seed"):
        facility.contract(facility.ATTN, q, k, v,
                          acc=jnp.zeros_like(q), plan=Plan(causal=True))
    _, k4, v4 = _attn_operands(rng, kvh=4)
    with pytest.raises(ValueError, match="multiple of KVH"):
        facility.contract(facility.ATTN, q, k4[:, :, :3], v4[:, :, :3],
                          plan=Plan())
    with pytest.raises(ValueError, match="valid mask"):
        facility.contract(facility.ATTN, q, k, v,
                          masks=(jnp.ones(7, bool),))


def test_attn_autotune_cache_consulted(tmp_path, monkeypatch, rng):
    """The attn lowering consults the (bh, sq, sk, d)-keyed (bq, bk)
    winner on dispatch; the planted block drives the kernel's grid."""
    from repro.core import autotune
    import repro.kernels.mma_attention as MA
    cache = autotune.AutotuneCache(tmp_path / "at.json")
    monkeypatch.setattr(autotune, "_DEFAULT_CACHE", cache)
    b, sq, sk, h, d = 1, 64, 64, 2, 32
    cache.put_raw(autotune.attn_cache_key(Ger.F32GER, b * h, sq, sk, d),
                  [16, 32], source="traced", score=0.0)
    assert autotune.lookup_attn(Ger.F32GER, b * h, sq, sk, d) == (16, 32)
    # a stale winner that no longer divides is ignored
    cache.put_raw(autotune.attn_cache_key(Ger.F32GER, 9, 9, 9, 9),
                  [16, 32], source="traced", score=0.0)
    assert autotune.lookup_attn(Ger.F32GER, 9, 9, 9, 9) is None
    grids = []
    real = MA.pl.pallas_call

    def spy(kernel, **kw):
        grids.append(kw.get("grid_spec").grid)
        return real(kernel, **kw)

    monkeypatch.setattr(MA.pl, "pallas_call", spy)
    q, k, v = _attn_operands(rng, b=b, sq=sq, sk=sk, h=h, kvh=h, d=d)
    got = facility.contract(
        facility.ATTN, q, k, v,
        plan=Plan(ger=Ger.F32GER, backend="pallas", causal=True,
                  out_dtype=jnp.float32))
    # bq=16, bk=32: live steps = sum_qi cdiv((qi+1)*16, 32) = 1+1+2+2
    assert grids == [(b, h, 6)], grids
    want = facility.contract(
        facility.ATTN, q, k, v,
        plan=Plan(ger=Ger.F32GER, backend="ref", out_dtype=jnp.float32,
                  causal=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_attn_autotune_search_persists_dividing_winner(tmp_path, rng):
    from repro.core import autotune
    cache = autotune.AutotuneCache(tmp_path / "at.json")
    best = autotune.autotune_attn(Ger.BF16GER2, 4, 96, 96, 32,
                                  causal=True, cache=cache)
    assert 96 % best[0] == 0 and 96 % best[1] == 0
    assert autotune.lookup_attn(Ger.BF16GER2, 4, 96, 96, 32,
                                cache=cache) == best


def test_sdpa_prefill_dispatches_attn_op_class(rng):
    """layers.sdpa routes prefill (dense positions, static q_offset)
    through the contract path; ring-buffer decode (kv_positions) keeps
    the explicit chunked scan."""
    from repro.models import layers as L
    q = jnp.asarray(rng.normal(size=(2, 16, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    out = L.sdpa(q, k, k, causal=True)
    assert sum(v for key, v in lowering.DISPATCH_COUNTS.items()
               if key[1] == "attn") == 1, dict(lowering.DISPATCH_COUNTS)
    assert out.shape == q.shape
    # ring-buffer decode: kv_positions present -> no attn-op-class dispatch
    lowering.DISPATCH_COUNTS.clear()
    kv_pos = jnp.arange(16)[None].repeat(2, 0)
    out = L.sdpa(q[:, :1], k, k, causal=True,
                 q_offset=jnp.asarray(3), kv_positions=kv_pos,
                 valid=kv_pos >= 0)
    assert not any(key[1] == "attn" for key in lowering.DISPATCH_COUNTS)
    assert out.shape == (2, 1, 4, 8)


def test_sdpa_ragged_sq_keeps_query_chunking(monkeypatch, rng):
    """Regression: sq % q_chunk != 0 (e.g. 1536 at the default 1024) used
    to silently fall back to unchunked attention, materializing the full
    (B, H, Sq, Sk) scores.  Both attn paths now process a ragged tail
    chunk: live chunks never exceed q_chunk."""
    from repro.core import lowering as LW
    from repro.models import layers as L
    b, sq, sk, h, d = 1, 24, 16, 2, 8
    monkeypatch.setattr(L, "Q_CHUNK", 16)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)

    # contract path (xla lowering): spy the shared chunk worker
    chunks = []
    real_chunk = LW.attend_chunk

    def spy_chunk(qc, *a, **kw):
        chunks.append(qc.shape[1])
        return real_chunk(qc, *a, **kw)

    monkeypatch.setattr(LW, "attend_chunk", spy_chunk)
    got = L.sdpa(q, k, k, causal=True)
    assert chunks and max(chunks) <= 16 and sum(chunks) == sq, chunks
    want = L.sdpa(q, k, k, causal=True, q_chunk=sq)   # one full chunk
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # legacy ring-buffer path: spy _attend
    attend_chunks = []
    real_attend = L._attend

    def spy_attend(qb, *a, **kw):
        attend_chunks.append(qb.shape[1])
        return real_attend(qb, *a, **kw)

    monkeypatch.setattr(L, "_attend", spy_attend)
    kv_pos = jnp.arange(sk)[None].repeat(b, 0)
    got = L.sdpa(q, k, k, causal=True, kv_positions=kv_pos)
    assert attend_chunks and max(attend_chunks) <= 16 \
        and sum(attend_chunks) == sq, attend_chunks
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_path_zeroes_fully_masked_rows(rng):
    """Regression (review finding): the ring-buffer decode path shares
    lowering.attend_chunk, so rows with no live KV slot yield exact zeros
    there too — not the uniform-softmax mean(V) the old layers._attend
    produced when the sliding window slid past the cached K."""
    from repro.models import layers as L
    b, sq, sk, h, d = 1, 64, 64, 1, 16
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
    kv_pos = jnp.arange(sk)[None]
    with facility.configure(facility.FacilityConfig(
            ger=Ger.F32GER, out_dtype=jnp.float32)):
        got = L.sdpa(q, k, k, causal=True, q_offset=jnp.asarray(64),
                     window=48, kv_positions=kv_pos)
        # the decode path agrees with the attn op-class at the same shape
        want = L.sdpa(q, k, k, causal=True, q_offset=64, window=48)
    # rows with q_pos >= 112 have window (q_pos-47, q_pos] beyond sk=64
    np.testing.assert_array_equal(np.asarray(got)[0, 48:],
                                  np.zeros((16, h, d), np.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_shim_routes_through_attn_op_class(rng):
    """mma_attention.flash_attention is a deprecated shim over
    contract(facility.ATTN, ...): it warns, dispatches via the attn
    op-class, and matches the oracle."""
    from repro.kernels import mma_attention as FA
    q = jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.float32)
    lowering.DISPATCH_COUNTS.clear()
    with pytest.warns(DeprecationWarning, match="facility.contract"):
        got = FA.flash_attention(q, q, q, causal=True, block_q=32,
                                 block_k=32, interpret=True)
    assert lowering.DISPATCH_COUNTS[
        ("pallas", "attn", Ger.F32GER.value)] == 1
    want = FA.ref_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_mma_pm_dot_shim_routes_through_gemm_masked(rng):
    """ops.mma_pm_dot is a deprecated shim over contract(..., masks=...):
    it warns, dispatches via gemm.masked, and matches the oracle."""
    from repro.kernels import ops, ref
    x = jnp.asarray(rng.normal(size=(48, 64)), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(64, 96)), jnp.bfloat16)
    xm = jnp.asarray(rng.random(48) > 0.3)
    ym = jnp.asarray(rng.random(96) > 0.3)
    pm = jnp.asarray(rng.random(64) > 0.3)
    lowering.DISPATCH_COUNTS.clear()
    with pytest.warns(DeprecationWarning, match="facility.contract"):
        got = ops.mma_pm_dot(x, y, kind=Ger.BF16GER2, xmask=xm, ymask=ym,
                             pmask=pm)
    assert lowering.DISPATCH_COUNTS[
        ("pallas", "gemm.masked", Ger.BF16GER2.value)] == 1
    want = ref.pm_ger(x, y, Ger.BF16GER2, xm, ym, pm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", [Ger.I8GER4, Ger.F16GER2])
def test_compiled_pallas_routes_refused_families_to_xla(kind, rng):
    """Dtype rule: a compiled (not interpreted) Pallas dispatch of a family
    the TPU compiler refuses takes the XLA lowering, before counting."""
    pol = policy(kind)
    if jnp.issubdtype(pol.x_dtype, jnp.integer):
        x = jnp.asarray(rng.integers(-50, 50, (16, 64)), pol.x_dtype)
        y = jnp.asarray(rng.integers(0, 200, (64, 128)), pol.y_dtype)
    else:
        x = jnp.asarray(rng.normal(size=(16, 64)), pol.x_dtype)
        y = jnp.asarray(rng.normal(size=(64, 128)), pol.y_dtype)
    lowering.DISPATCH_COUNTS.clear()
    got = facility.contract("mk,kn->mn", x, y, plan=Plan(
        ger=kind, backend="pallas", interpret=False, out_dtype=lowering.ACC))
    assert lowering.DISPATCH_COUNTS == {("xla", "gemm", kind.value): 1}
    want = facility.contract("mk,kn->mn", x, y, plan=Plan(
        ger=kind, backend="xla", out_dtype=lowering.ACC))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sk,valid,routed", [
    (1500, False, True),    # bk = 125: not a multiple of 8
    (200, True, True),      # bk = 100 with a valid mask: not 128-aligned
    (100, True, False),     # bk = Sk: the whole row is always tileable
], ids=["bk125", "valid-bk100", "valid-whole-sk"])
def test_attn_tiling_rule_routes_unaligned_blocks(sk, valid, routed, rng):
    """Shape rule: compiled Pallas attention whose resolved blocks the TPU
    tiling refuses takes the XLA lowering; interpret mode keeps Pallas."""
    q = jnp.asarray(rng.normal(size=(2, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, sk, 2, 16)), jnp.float32)
    masks = (jnp.asarray(rng.random((2, sk)) > 0.3),) if valid else None
    for interpret in (False, True):
        lowering.DISPATCH_COUNTS.clear()

        def call():
            return facility.contract(
                facility.ATTN, q, k, k, masks=masks,
                plan=Plan(ger=Ger.F32GER, backend="pallas",
                          interpret=interpret))

        if interpret or routed:
            call()
        else:
            with pytest.raises(Exception):   # no compiled Pallas on a CPU
                call()
        want = "xla" if routed and not interpret else "pallas"
        assert lowering.DISPATCH_COUNTS[
            (want, "attn", Ger.F32GER.value)] == 1
