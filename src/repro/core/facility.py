"""The MMA facility: ONE architected builtin in front of all matrix math.

Every matrix contraction in the framework — attention projections, FFN and
MoE expert GEMMs, attention scores/values, Mamba2 SSD chunk products,
logits, the int8 serving path — routes through :func:`contract`.  That is
the system-level reading of the paper's programming model (section IV): a
small set of *built-ins* with architected semantics (ger kind = input
dtypes + accumulator dtype + accumulate form), beneath which the compiler
owns scheduling and register (here: sharding, layout, and block) allocation.

    contract(spec, x, y, plan=Plan(...))

``spec`` is an einsum-like contraction spec (``"mk,kn->mn"``,
``"...k,kn->...n"``, ``"ecd,edf->ecf"``, ...) and :class:`Plan` bundles the
static policy: ger family, epilogue, accumulate forms, out dtype, backend,
and block override.  Lowering is owned by the pluggable registry in
``repro.core.lowering``: backends (``pallas`` / ``xla`` / ``ref``) register
implementations per (op-class, ger-family, fused) key, all built on the
same explicit ACC lifecycle (prime -> rank-k updates -> deprime).

The legacy entry points (``fdot``, ``fdot_fused``, ``feinsum``, and
``kernels.ops.mma_dot[_fused]``) survive as thin deprecated shims over
``contract``; in-repo callers must use ``contract`` directly (the tier-1
suite escalates the shims' DeprecationWarnings to errors for ``repro.*``
callers, and ``scripts/ci.sh`` lints raw ``jnp.dot/einsum/matmul`` use).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools

import jax.numpy as jnp

from repro.core import lowering, precision

Ger = precision.Ger
Plan = lowering.Plan
Dequant = lowering.Dequant
ACC = lowering.ACC

# The facility is the models' single import surface: the fused-epilogue
# dataclass, the shared chunked-attention math, and the shim-deprecation
# hook are all re-exported here (via lowering, which owns the kernels'
# public names) so clients never reach past this layer.
Epilogue = lowering.Epilogue
make_epilogue = lowering.make_epilogue
attend_chunk = lowering.attend_chunk
deprecated_shim = lowering.deprecated_shim

# The workhorse spec: contract the last axis of x with the first of w.
DOT = "...k,kn->...n"

# Canonical convolution specs (the conv op-class; stride/padding ride in
# the Plan).  Convolutions are not two-operand einsums, so the facility
# names them architecturally instead (paper section V-B).
CONV2D = lowering.CONV2D                      # "nhwc,hwio->nhwo"
CONV1D = lowering.CONV1D                      # "nlc,lio->nlo"
CONV1D_DEPTHWISE = lowering.CONV1D_DEPTHWISE  # "nlc,lc->nlc"

# Canonical fused-attention spec (the attn op-class): a three-operand
# builtin — softmax couples the score and value contractions, so no
# two-operand spec can name it.  q (B, Sq, H, D); k, v (B, Sk, KVH, D);
# causal/window/q_offset ride in the Plan, the (B, Sk) valid-slot
# predicate as ``masks=(valid,)``.
ATTN = lowering.ATTN                          # "bqhd,bkhd->bqhd"

# Canonical SSD spec (the ssd op-class): the Mamba2 chunk scan's
# intra-chunk branch, a four-operand builtin.  C, B (B, NC, L, N); x
# (B, NC, L, H, P); cum, the (B, NC, H, L) within-chunk cumulative
# log-decays, whose differences make the causal decay mask.
SSD = lowering.SSD                     # "bcln,bcsn,bcshp,bchl->bclhp"


@dataclasses.dataclass(frozen=True)
class FacilityConfig:
    """Numeric policy for a model's matrix math."""

    ger: Ger = Ger.BF16GER2          # activation-side GEMM family
    out_dtype: jnp.dtype = jnp.bfloat16   # activation dtype between ops
    # Hand-tiled Pallas kernels (the TPU hot path) or the shardable XLA
    # lowering.  None follows the platform: Pallas where the default
    # backend is a TPU, XLA elsewhere.
    use_pallas: bool | None = None
    # Pallas interpret mode.  None follows the platform: interpreted only
    # where the default backend is the CPU, compiled everywhere else.
    interpret: bool | None = None
    # Guarded dispatch (DESIGN.md section 8): wrap contract outputs with a
    # NaN/Inf detector and demote lowering failures down the
    # pallas -> xla -> ref ladder (per-(op-class, shape) quarantine).  Off
    # by default: the unguarded dispatch tail is bitwise-identical and
    # pays no detector sync.
    guards: bool = False
    # ABFT checksum verification (DESIGN.md section 8, core/abft.py):
    # guarded dispatch additionally verifies column/row checksums of each
    # eligible contract output against its Huang–Abraham references, so
    # *finite but wrong* outputs (silent data corruption) are a guard
    # outcome too — retry once, then demote down the ladder.  Requires
    # guards=True; kept a separate flag because attn/conv verification
    # augments operands with a checksum column, which is
    # tolerance-identical but not bitwise-identical to the plain path
    # (guards alone stays bitwise-unchanged).
    abft: bool = False


_CONFIG = contextvars.ContextVar("mma_facility", default=FacilityConfig())


def current() -> FacilityConfig:
    return _CONFIG.get()


@contextlib.contextmanager
def configure(cfg: FacilityConfig):
    token = _CONFIG.set(cfg)
    try:
        yield cfg
    finally:
        _CONFIG.reset(token)


def contract(spec: str, x: jnp.ndarray, y: jnp.ndarray,
             z: jnp.ndarray | None = None,
             w: jnp.ndarray | None = None, *,
             plan: Plan | None = None,
             acc: jnp.ndarray | None = None,
             bias: jnp.ndarray | None = None,
             residual: jnp.ndarray | None = None,
             dequant: Dequant | None = None,
             masks: tuple | None = None) -> jnp.ndarray:
    """The facility's single architected builtin.

    ``spec`` names the contraction; ``plan`` (static) selects ger family,
    accumulate form, epilogue, out dtype, backend, and block override —
    unset fields resolve against the ambient :class:`FacilityConfig`.
    ``acc`` seeds the accumulator (the pp/np/pn/nn forms, scaled by
    ``plan.beta``); ``bias``/``residual`` are the fused-epilogue operands;
    ``dequant`` is the quant path's deprime rescale; ``masks`` =
    ``(xmask, ymask, pmask)`` bool predicates on the normalized M/N/K
    axes (the pm* prefixed masked forms, paper section II-C — the Pallas
    lowering applies them to the streamed panels in-kernel, never
    pre-masking operands in HBM).

    ``z`` is the value operand of the canonical :data:`ATTN` spec — a
    three-operand builtin (``contract(facility.ATTN, q, k,
    v, plan=Plan(causal=..., window=..., q_offset=...))``); there,
    ``masks`` is the 1-tuple ``(valid,)`` filled-KV-slot predicate.  The
    canonical :data:`SSD` spec takes four operands (``contract(
    facility.SSD, C, B, x, cum)``): ``z`` is x and ``w`` the cumulative
    log-decays.

    Dispatch goes through the lowering registry (``repro.core.lowering``):
    specs that normalize to (batched) 2-D GEMMs reach the autotuned Pallas
    kernels — batch rides as a grid dimension, one ``pallas_call`` per
    contraction — or the shardable ``lax.dot_general`` lowering; the
    canonical conv/attn/ssd specs reach their op-classes; everything else
    falls back to the general einsum lowering.
    """
    return lowering.execute(spec, x, y, z, w, cfg=current(), plan=plan,
                            acc=acc, bias=bias, residual=residual,
                            dequant=dequant, masks=masks)


# ----------------------------------------------------------------------
# Deprecated shims (kept so external callers and the tier-1 suite keep
# working unchanged; in-repo callers use `contract`)
# ----------------------------------------------------------------------

def fdot(x: jnp.ndarray, w: jnp.ndarray, *, ger: Ger | None = None,
         out_dtype=None) -> jnp.ndarray:
    """Deprecated: ``contract(facility.DOT, x, w, plan=Plan(ger=...))``.

    Contracts the last axis of ``x`` with the first axis of ``w``:
    ``(..., K) x (K, N) -> (..., N)`` with ger-policy input casting and
    high-precision resident accumulation.
    """
    lowering.deprecated_shim(
        "facility.fdot", "contract(facility.DOT, x, w, "
        "plan=Plan(ger=..., out_dtype=...))")
    return contract(DOT, x, w, plan=Plan(ger=ger, out_dtype=out_dtype))


def fdot_fused(x: jnp.ndarray, w: jnp.ndarray, *,
               bias: jnp.ndarray | None = None,
               activation: str | None = None,
               residual: jnp.ndarray | None = None,
               ger: Ger | None = None, out_dtype=None) -> jnp.ndarray:
    """Deprecated: ``contract(facility.DOT, x, w, plan=Plan(epilogue=...),
    bias=..., residual=...)``.

    ``fdot`` with a fused epilogue: activation/bias/residual applied to
    the resident accumulator before the out_dtype cast (epilogue contract,
    DESIGN.md), in acc dtype (fp32) rather than the cast-down activation
    dtype.
    """
    lowering.deprecated_shim(
        "facility.fdot_fused", "contract(facility.DOT, x, w, "
        "plan=Plan(epilogue=Epilogue(...)), bias=..., residual=...)")
    ep = make_epilogue(bias=bias, activation=activation, residual=residual)
    return contract(DOT, x, w, plan=Plan(ger=ger, out_dtype=out_dtype,
                                         epilogue=ep),
                    bias=bias, residual=residual)


def feinsum(spec: str, a: jnp.ndarray, b: jnp.ndarray, *,
            ger: Ger | None = None, out_dtype=None) -> jnp.ndarray:
    """Deprecated: ``contract(spec, a, b, plan=Plan(...))``.

    Facility-routed einsum for contractions that are not plain fdot
    (attention scores/values, batched expert GEMMs, SSD chunk products).
    """
    lowering.deprecated_shim(
        "facility.feinsum",
        "contract(spec, a, b, plan=Plan(ger=..., out_dtype=...))")
    return contract(spec, a, b, plan=Plan(ger=ger, out_dtype=out_dtype))


@functools.lru_cache(maxsize=None)
def flops_per_dot(m: int, n: int, k: int) -> int:
    """Model-FLOPs bookkeeping used by the roofline layer."""
    return 2 * m * n * k
