"""Kernel-level entry points — now thin shims over ``facility.contract``.

Historically this module owned the dispatch logic (pallas-vs-XLA switch,
autotune-cache consult, the F32GER_3XBF16 three-pass split).  All of that
moved into the lowering registry (``repro.core.lowering``): ``mma_dot`` /
``mma_dot_fused`` / ``mma_conv2d`` survive as deprecated shims so existing
callers and the tier-1 suite keep working, while in-repo code calls
``facility.contract`` directly (convolution is the registry's ``conv``
op-class since the facility.CONV* specs landed, and the prefixed masked
forms are its ``gemm.masked`` op-class via ``contract(..., masks=...)``
since the grid-native-batch PR).  ``mma_ger_saturating`` (clamped
accumulate forms) remains the supported kernel-level builtin for the one
operation ``contract`` specs do not name; ``mma_pm_dot`` is now a
deprecated shim too (except packed int4, which keeps the ref oracle).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import facility, precision
# The registry's block resolver is not part of the facility surface, but
# external tooling pokes at _resolve_block; the int4 pm oracle likewise
# stays on the ref kernel (nibble unpack and rank predicates do not
# compose in the streamed kernel).  Both are deliberate layer crossings
# in a deprecated-shim module.
from repro.core import lowering  # repro: allow(layer-stratification)
from repro.kernels import ref as _ref  # repro: allow(layer-stratification)

Ger = precision.Ger
Epilogue = facility.Epilogue

_GEMM = "mk,kn->mn"


def _resolve_block(x, y, kind: Ger,
                   block: tuple[int, int, int] | None,
                   epilogue_key: str = "none",
                   use_pallas: bool = True):
    """Dispatch-time autotune-cache consult (delegates to the registry's
    resolver; kept here because external tooling pokes at it)."""
    if block is not None or not use_pallas:
        return block
    pack = 2 if precision.policy(kind).packed_int4 else 1
    m, k = x.shape[0], x.shape[1] * pack
    n = y.shape[1]
    return lowering.resolve_block(kind, m, n, k, None, epilogue_key)


def _plan(kind, block, use_pallas, interpret, out_dtype, *,
          epilogue=None, neg_product=False, neg_acc=False,
          alpha=1.0, beta=1.0, saturating=False) -> facility.Plan:
    return facility.Plan(
        ger=kind, block=block,
        backend="pallas" if use_pallas else "xla",
        interpret=interpret,
        out_dtype=out_dtype if out_dtype is not None else facility.ACC,
        epilogue=epilogue, neg_product=neg_product, neg_acc=neg_acc,
        alpha=alpha, beta=beta, saturating=saturating)


def mma_dot(x: jnp.ndarray, y: jnp.ndarray,
            c: jnp.ndarray | None = None, *,
            kind: Ger = Ger.BF16GER2,
            block: tuple[int, int, int] | None = None,
            use_pallas: bool = True, interpret: bool | None = None,
            out_dtype=None) -> jnp.ndarray:
    """Deprecated: ``facility.contract("mk,kn->mn", x, y, acc=c,
    plan=Plan(ger=kind, ...))``.

    ``C <- X @ Y [+ C]`` under a ger-kind policy.  x:(M,K) y:(K,N).  When
    ``block`` is None the autotune cache is consulted by the registry.
    """
    facility.deprecated_shim(
        "ops.mma_dot", 'contract("mk,kn->mn", x, y, acc=c, '
        "plan=Plan(ger=kind, backend=..., block=...))")
    return facility.contract(
        _GEMM, x, y, acc=c,
        plan=_plan(kind, block, use_pallas, interpret, out_dtype))


def mma_dot_fused(x: jnp.ndarray, y: jnp.ndarray,
                  c: jnp.ndarray | None = None, *,
                  kind: Ger = Ger.BF16GER2,
                  epilogue: Epilogue | None = None,
                  bias: jnp.ndarray | None = None,
                  residual: jnp.ndarray | None = None,
                  block: tuple[int, int, int] | None = None,
                  use_pallas: bool = True, interpret: bool | None = None,
                  neg_product: bool = False, neg_acc: bool = False,
                  alpha: float = 1.0, beta: float = 1.0,
                  out_dtype=None) -> jnp.ndarray:
    """Deprecated: ``facility.contract`` with an epilogue-carrying Plan.

    ``mma_dot`` with the fused epilogue contract (epilogue.py) and the
    pp/np/pn/nn accumulate forms — both now owned by the registry's ACC
    lifecycle (prime/update/deprime).
    """
    facility.deprecated_shim(
        "ops.mma_dot_fused", 'contract("mk,kn->mn", x, y, acc=c, '
        "plan=Plan(ger=kind, epilogue=ep, alpha=..., beta=...), "
        "bias=..., residual=...)")
    epilogue = epilogue or facility.make_epilogue(bias=bias, residual=residual)
    return facility.contract(
        _GEMM, x, y, acc=c, bias=bias, residual=residual,
        plan=_plan(kind, block, use_pallas, interpret, out_dtype,
                   epilogue=epilogue, neg_product=neg_product,
                   neg_acc=neg_acc, alpha=alpha, beta=beta))


def mma_ger_saturating(x: jnp.ndarray, y: jnp.ndarray,
                       kind: Ger = Ger.I16GER2,
                       acc: jnp.ndarray | None = None) -> jnp.ndarray:
    """Saturating accumulation forms (xvi16ger2s / xvi8ger4spp).

    Architected semantics: each rank-``arch_rank`` update saturates the
    int32 accumulator instead of wrapping.  Lowered by the registry's
    ``gemm.saturating`` op-class (clamped ``lax.scan`` on the XLA backend
    — saturating integer accumulate has no MXU analogue; DESIGN.md).
    """
    return facility.contract(
        _GEMM, x, y, acc=acc,
        plan=facility.Plan(ger=kind, saturating=True, backend="xla",
                           out_dtype=facility.ACC))


def mma_pm_dot(x, y, *, kind: Ger, xmask, ymask, pmask=None, acc=None,
               use_pallas: bool = True, interpret: bool | None = None):
    """Deprecated: ``facility.contract("mk,kn->mn", x, y, masks=(xmask,
    ymask, pmask), plan=Plan(ger=kind, ...))``.

    Prefixed masked rank-k update (paper eq. 3), matrix granularity,
    lowered by the registry's ``gemm.masked`` op-class: the predicates
    stream into the Pallas kernel and disable lanes on the VMEM-resident
    panels — the operands are never pre-masked in HBM (this shim used to
    materialize ``x * mask`` before dispatch).  Packed int4 stays on the
    ``ref.pm_ger`` oracle (nibble unpacking and rank predicates do not
    compose in the streamed kernel).
    """
    pol = precision.policy(kind)
    if pol.packed_int4:
        return _ref.pm_ger(x, y, kind, xmask, ymask, pmask, acc)
    facility.deprecated_shim(
        "ops.mma_pm_dot", 'contract("mk,kn->mn", x, y, '
        "masks=(xmask, ymask, pmask), acc=acc, plan=Plan(ger=kind, ...))")
    return facility.contract(
        _GEMM, x, y, acc=acc, masks=(xmask, ymask, pmask),
        plan=_plan(kind, None, use_pallas, interpret, None))


def mma_conv2d(image, kernels, *, use_pallas: bool = True,
               interpret: bool | None = None, bf: int | None = None):
    """Deprecated: ``facility.contract(facility.CONV2D, image, kernels,
    plan=Plan(ger=Ger.F32GER, backend=..., stride=..., padding=...))``.

    SCONV: VALID stride-1 2-D convolution (paper section V-B), now owned
    by the registry's ``conv`` op-class (``use_pallas=False`` maps to the
    ``ref`` materialized-Abar lowering this shim used to call directly).
    """
    facility.deprecated_shim(
        "ops.mma_conv2d", "contract(facility.CONV2D, image, kernels, "
        "plan=Plan(ger=Ger.F32GER, backend=..., block=...))")
    return facility.contract(
        facility.CONV2D, image, kernels,
        plan=facility.Plan(
            ger=Ger.F32GER, backend="pallas" if use_pallas else "ref",
            block=(8, bf, 128) if bf is not None else None,
            interpret=interpret, out_dtype=jnp.float32))
