"""The lowering registry beneath ``facility.contract``.

The paper's programming model (section IV) is one small set of architected
built-ins in front of every matrix operation, with the compiler owning the
lowering; Kuzma et al. (PAPERS.md) push the same split further by making the
lowering a swappable compiler layer.  This module is that layer for the
repo: the single builtin ``facility.contract(spec, x, y, plan=...)`` parses
an einsum-like contraction spec, resolves a :class:`Plan` against the
ambient :class:`~repro.core.facility.FacilityConfig`, and dispatches to a
registered lowering.

Registry
--------
Lowerings register per ``(backend, op_class, ger, fused)`` key:

  * ``backend``:  ``"pallas"`` (hand-tiled kernels, ``interpret=True`` on
    CPU), ``"xla"`` (one ``lax.dot_general`` the SPMD partitioner can
    shard), ``"ref"`` (eager architected oracles — ground truth).
  * ``op_class``: ``"gemm"`` (any spec that normalizes to a — possibly
    batched — 2-D GEMM; batch is a grid dimension of the Pallas kernel,
    never a vmapped re-trace), ``"gemm.masked"`` (the pm* prefixed masked
    forms — row/column/rank predicates fused into the kernel's VMEM panel
    loads, paper section II-C), ``"gemm.saturating"`` (xvi16ger2s-style
    clamped accumulation), ``"conv"`` (the canonical NHWC conv specs —
    normalized to the implicit-im2col rank-(KW*C) update form; depthwise
    runs a resident-accumulator VPU kernel), ``"complex"`` (complex-dtype
    operands — four real accumulate-form gers, pp/np, batched or not),
    ``"attn"`` (the canonical three-operand ATTN spec — fused flash
    attention on Pallas with a causal-bounded grid, the chunked two-dot
    math on xla, the pinned two-contract oracle on ref), ``"ssd"`` (the
    canonical SSD spec — the Mamba2 chunk scan's intra-chunk branch, one
    fused kernel on Pallas, the decay-mask-and-two-gemm chain on xla and
    ref),
    ``"einsum"`` (general contraction fallback).
  * ``ger``/``fused``: optional specializations; lookup falls back from the
    most specific key to ``(backend, op_class, None, None)``.

ACC lifecycle
-------------
Every gemm-class lowering implements the same three-phase accumulator
lifecycle (paper fig. 4 — prime, rank-k updates, deprime):

    prime    acc <- 0 | [-] beta * C          (xxsetaccz / accumulate forms)
    update   acc <- acc [-] X_i @ Y_i         (one per rank-k pass)
    deprime  out <- cast(epilogue(alpha * acc))   (single results-bus store)

The Pallas kernel realizes it inside VMEM scratch (``mma_gemm``); the XLA
and ref lowerings realize it with the explicit :class:`Accumulator` object
below.  Two plug-in points hang off the lifecycle:

  * *expansion hooks* (``register_expansion``) rewrite one architected
    pass into several — ``F32GER_3XBF16`` becomes three chained
    ``BF16GER2`` updates over one resident accumulator, replacing the
    special-case branches that used to be copy-pasted across
    ``facility.fdot`` / ``facility.fdot_fused``;
  * the *deprime stage* takes the fused epilogue contract
    (``kernels/epilogue.py``) and the :class:`Dequant` rescale that turns
    ``quant.qdot`` into an ``I8GER4`` plan instead of a parallel code path.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as _P

from repro.core import abft as _abft
from repro.core import precision
from repro.core import packing as _packing
from repro.kernels import epilogue as _epilogue_mod
from repro.runtime import faults as _faults

Ger = precision.Ger

# Re-exported for the layers above: the lowering layer owns the kernels'
# public surface, so facility (and through it the models) name the fused
# epilogue without a layer-skipping import into repro.kernels.
Epilogue = _epilogue_mod.Epilogue
make_epilogue = _epilogue_mod.make

# Sentinel for Plan.out_dtype: keep the accumulator dtype (what the kernel
# entry points mean by ``out_dtype=None``, distinct from "facility default").
ACC = "acc"

# Observability: execute() counts dispatches per (backend, op_class, ger
# value).  Tests assert on deltas (e.g. "MoE expert dots reached the Pallas
# gemm path"); reset with ``DISPATCH_COUNTS.clear()``.
DISPATCH_COUNTS: collections.Counter = collections.Counter()
# Of those, the dispatches a shape rule sent from pallas to xla (attn block
# plans the TPU tiling refuses, ssd shapes the kernel does not tile), per
# (op_class, ger value): the facility's own choice, where any other xla
# dispatch under a Pallas config is a fallback.  Cleared with
# ``DISPATCH_COUNTS``.
SHAPE_RULE_FALLBACKS: collections.Counter = collections.Counter()


def _dispatch_scope(op_class: str, backend: str):
    """The name every device op of one dispatch carries in its HLO
    ``op_name``, forward and backward alike:
    ``contract.<op_class>.<backend>``, naming the backend that ran, as the
    ``DISPATCH_COUNTS`` key does."""
    return jax.named_scope(f"contract.{op_class}.{backend}")


# ----------------------------------------------------------------------
# Plan: the architected call signature of the builtin
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Plan:
    """Static description of one ``contract`` call (jit-hashable).

    Bundles what used to be scattered kwargs across ``fdot`` /
    ``fdot_fused`` / ``mma_dot`` / ``mma_dot_fused`` / ``qdot``.  ``None``
    fields resolve against the ambient FacilityConfig at dispatch.
    """

    ger: Ger | None = None            # rank-k family; None -> config
    out_dtype: object = None          # None -> config; ACC -> acc dtype
    backend: str | None = None        # None -> config, then the platform
    epilogue: object = None           # kernels.epilogue.Epilogue | None
    block: tuple[int, int, int] | None = None   # Pallas block override
    # Accumulate forms (paper eq. 2): out = alpha * [-](X@Y) + beta * [-]C
    neg_product: bool = False
    neg_acc: bool = False
    alpha: float = 1.0
    beta: float = 1.0
    saturating: bool = False          # xvi16ger2s-style clamped updates
    interpret: bool | None = None     # None -> config, then the platform
    # Conv op-class only (spec is one of the canonical conv specs below):
    stride: object = 1                # int or per-spatial-dim tuple
    padding: str = "valid"            # valid | same | causal (1-D left pad)
    # Attn op-class only (spec is the canonical ATTN spec below):
    causal: bool = False              # q attends k with k_pos <= q_pos
    window: int | None = None         # sliding window: q_pos - k_pos < window
    q_offset: int = 0                 # absolute position of q[0] (decode)
    q_chunk: int = 0                  # xla lowering's q-chunk (0 = default)
    # Mesh binding for the shard-aware dispatch (DESIGN.md section 11):
    # None -> the ambient parallel.api rules (model code stays
    # annotation-only); False -> single-device lowering even under an
    # active mesh (e.g. contracts issued *inside* a shard_map body); a
    # jax.sharding.Mesh or parallel.api.ShardingRules binds explicitly.
    mesh: object = None


# ----------------------------------------------------------------------
# Conv specs: the architected convolution surface (paper section V-B)
# ----------------------------------------------------------------------
# Convolutions are not expressible as two-operand einsums (the sliding
# window reuses input elements), so the facility names them with canonical
# specs instead; ``execute`` routes them to the ``conv`` op-class, which
# normalizes to the implicit-im2col rank-(KW*C) update form.  Labels follow
# lax dimension_numbers mnemonics (NHWC / HWIO).

CONV2D = "nhwc,hwio->nhwo"            # dense 2-D conv, stride/padding in Plan
CONV1D = "nlc,lio->nlo"               # dense 1-D conv over the L (time) axis
CONV1D_DEPTHWISE = "nlc,lc->nlc"      # per-channel taps (groups == C)

# spec -> (spatial ndim, depthwise)
_CONV_SPECS = {CONV2D: (2, False), CONV1D: (1, False),
               CONV1D_DEPTHWISE: (1, True)}


# ----------------------------------------------------------------------
# Attn spec: fused scaled-dot-product attention (paper's "building blocks
# of other computations" close) — a three-operand op no two-operand einsum
# can name (the softmax couples the two contractions), so the facility
# names it architecturally, like the conv specs.  q: (B, Sq, H, D);
# k, v: (B, Sk, KVH, D) with H % KVH == 0 (GQA head groups).
# ----------------------------------------------------------------------

ATTN = "bqhd,bkhd->bqhd"

# The xla attn lowering's default query-chunk length: at most
# (B, H, chunk, Sk) scores are live at once (memory-efficient attention).
ATTN_Q_CHUNK = 1024

# Families the fused kernel accepts: float operands, f32 accumulator.
_ATTN_GERS = (Ger.F32GER, Ger.BF16GER2, Ger.F16GER2)

# ----------------------------------------------------------------------
# SSD spec: the intra-chunk branch of Mamba2's chunked scan (arXiv
# 2405.21060) — C (B, NC, L, N), B (B, NC, L, N), x (B, NC, L, H, P) and
# cum, the (B, NC, H, L) within-chunk cumulative log-decays whose
# differences make the causal 1-semiseparable mask:
# y[l, h] = sum_{s <= l} (C_l . B_s) exp(cum[h, l] - cum[h, s]) x[s, h].
# Like attention, the mask couples two contractions, so the facility
# names it architecturally.
# ----------------------------------------------------------------------

SSD = "bcln,bcsn,bcshp,bchl->bclhp"

# Families whose kernels the TPU compiler takes (execute's dtype rule;
# expansion hooks count as the family they chain).
_COMPILED_GERS = (Ger.F32GER, Ger.BF16GER2)


# ----------------------------------------------------------------------
# Spec parsing: einsum-like contraction specs -> GEMM structure
# ----------------------------------------------------------------------

_ELL_LABELS = "ZYXWVU"   # reserved labels for '...' expansion


@dataclasses.dataclass(frozen=True)
class ParsedSpec:
    """Static contraction structure for one (spec, x.ndim, y.ndim)."""

    x_labels: tuple[str, ...]
    y_labels: tuple[str, ...]
    out_labels: tuple[str, ...]
    batch: tuple[str, ...]       # in both inputs and the output
    contract: tuple[str, ...]    # in both inputs, not the output
    x_free: tuple[str, ...]      # "M" labels
    y_free: tuple[str, ...]      # "N" labels

    @property
    def dnums(self):
        """lax.dot_general dimension_numbers for the un-normalized form."""
        xi = {d: i for i, d in enumerate(self.x_labels)}
        yi = {d: i for i, d in enumerate(self.y_labels)}
        return ((tuple(xi[d] for d in self.contract),
                 tuple(yi[d] for d in self.contract)),
                (tuple(xi[d] for d in self.batch),
                 tuple(yi[d] for d in self.batch)))

    @property
    def natural_out(self) -> tuple[str, ...]:
        """dot_general's output order: batch, then M, then N labels."""
        return self.batch + self.x_free + self.y_free

    @property
    def out_perm(self) -> tuple[int, ...] | None:
        """Transpose taking natural_out to the spec's output order."""
        nat = self.natural_out
        if nat == self.out_labels:
            return None
        return tuple(nat.index(d) for d in self.out_labels)

    @property
    def is_plain_2d(self) -> bool:
        """True when the spec IS "mk,kn->mn" up to label names."""
        return (not self.batch and len(self.x_free) == 1
                and len(self.y_free) == 1 and len(self.contract) == 1
                and self.x_labels == (self.x_free[0], self.contract[0])
                and self.y_labels == (self.contract[0], self.y_free[0])
                and self.out_perm is None)

    @property
    def is_natural_gemm(self) -> bool:
        """True when operands/output are already in the normalized
        (batch..., M, K) x (batch..., K, N) -> (batch..., M, N) layout
        with single M/N/K labels — the layout the masked op-class requires
        so its (M,), (N,), (K,) predicates name unambiguous axes."""
        return (len(self.x_free) == 1 and len(self.y_free) == 1
                and len(self.contract) == 1
                and self.x_labels == self.batch + self.x_free + self.contract
                and self.y_labels == self.batch + self.contract + self.y_free
                and self.out_perm is None)


def _expand_ellipsis(labels: str, ndim: int, spec: str) -> tuple[str, ...]:
    if "..." not in labels:
        out = tuple(labels)
        if len(out) != ndim:
            raise ValueError(
                f"spec {spec!r}: operand term {labels!r} has "
                f"{len(out)} labels for a {ndim}-d operand")
        return out
    head, _, tail = labels.partition("...")
    n_ell = ndim - len(head) - len(tail)
    if n_ell < 0:
        raise ValueError(f"spec {spec!r}: {labels!r} over-labels "
                         f"a {ndim}-d operand")
    if n_ell > len(_ELL_LABELS):
        raise ValueError(f"spec {spec!r}: '...' spans {n_ell} dims "
                         f"(max {len(_ELL_LABELS)})")
    # Labels come off the END of the pool so that, einsum-style, the
    # ellipses of two operands with different ranks align on their LAST
    # dims ('...ij,...jk' with a 4-d x 3-d: x's trailing batch dim pairs
    # with y's only one).
    return (tuple(head) + tuple(_ELL_LABELS[len(_ELL_LABELS) - n_ell:])
            + tuple(tail))


@functools.lru_cache(maxsize=None)
def parse_spec(spec: str, x_ndim: int, y_ndim: int) -> ParsedSpec | None:
    """Parse a two-operand contraction spec; None when it is not a
    (batched) GEMM the registry's gemm lowerings can take — the caller
    then falls back to the general einsum lowering.
    """
    s = spec.replace(" ", "")
    try:
        lhs, out_s = s.split("->")
        xs_s, ys_s = lhs.split(",")
    except ValueError:
        raise ValueError(f"bad contraction spec {spec!r}; want 'ab,bc->ac'")
    for term in (xs_s, ys_s):
        if any(c in _ELL_LABELS for c in term.replace(".", "")):
            return None   # user labels collide with the ellipsis pool
    xs = _expand_ellipsis(xs_s, x_ndim, spec)
    ys = _expand_ellipsis(ys_s, y_ndim, spec)
    if "..." in out_s:
        n_ell = max(len(xs) - len(xs_s.replace("...", "")),
                    len(ys) - len(ys_s.replace("...", "")))
        head, _, tail = out_s.partition("...")
        outs = (tuple(head) + tuple(_ELL_LABELS[len(_ELL_LABELS) - n_ell:])
                + tuple(tail))
    else:
        outs = tuple(out_s)
    xset, yset, oset = set(xs), set(ys), set(outs)
    if (len(xset) != len(xs) or len(yset) != len(ys)
            or len(oset) != len(outs)):
        return None   # repeated label within a term (diagonal): not a GEMM
    if not oset <= (xset | yset):
        raise ValueError(f"spec {spec!r}: output labels {oset - xset - yset}"
                         f" appear in no input")
    # Labels in exactly one input must survive to the output, otherwise the
    # spec asks for a plain sum-reduction — not GEMM-shaped.
    if (xset - yset) - oset or (yset - xset) - oset:
        return None
    batch = tuple(d for d in xs if d in yset and d in oset)
    contract = tuple(d for d in xs if d in yset and d not in oset)
    x_free = tuple(d for d in xs if d not in yset)
    y_free = tuple(d for d in ys if d not in xset)
    return ParsedSpec(xs, ys, outs, batch, contract, x_free, y_free)


def _ellipsis_broadcasts(parsed: ParsedSpec, x, y) -> bool:
    """True when an ellipsis-derived label has size 1 on one operand and
    >1 on the other — einsum broadcasting the GEMM normalizer cannot
    express, so the caller routes to the general einsum lowering."""
    sizes: dict[str, int] = {}
    for labels, shape in ((parsed.x_labels, jnp.shape(x)),
                          (parsed.y_labels, jnp.shape(y))):
        for d, n in zip(labels, shape):
            prev = sizes.setdefault(d, n)
            if prev != n and d in _ELL_LABELS and 1 in (prev, n):
                return True
    return False


def _sizes(parsed: ParsedSpec, x, y) -> dict[str, int]:
    sizes: dict[str, int] = {}
    for labels, arr in ((parsed.x_labels, x), (parsed.y_labels, y)):
        for d, n in zip(labels, arr.shape):
            if sizes.setdefault(d, n) != n:
                raise ValueError(
                    f"size mismatch for label {d!r}: {sizes[d]} vs {n} "
                    f"({x.shape} x {y.shape})")
    return sizes


def _prod(ns) -> int:
    out = 1
    for n in ns:
        out *= n
    return out


# ----------------------------------------------------------------------
# The explicit ACC lifecycle (XLA / ref lowerings; the Pallas kernel
# implements the same phases inside VMEM scratch — mma_gemm.py)
# ----------------------------------------------------------------------

class Accumulator:
    """prime -> rank-k updates -> deprime, at matrix granularity.

    Mirrors the architected accumulator lifecycle: ``prime`` is
    ``xxsetaccz`` or the accumulate-form seed, each ``update`` is one
    rank-k ``xv*ger*`` pass, and ``deprime`` is the single store through
    the results bus — where the epilogue contract and the quant
    :class:`Dequant` rescale plug in.
    """

    def __init__(self, pol: precision.GerPolicy):
        self.pol = pol
        self.value = None

    def prime(self, c=None, *, beta: float = 1.0, neg_acc: bool = False):
        if c is None:
            self.value = None       # lazy zeros: first update sets it
            return self
        v = c.astype(self.pol.acc_dtype)
        if beta != 1.0:
            v = v * jnp.asarray(beta, self.pol.acc_dtype)
        self.value = -v if neg_acc else v
        return self

    def update(self, x, y, dnums=(((1,), (0,)), ((), ())), *,
               neg_product: bool = False):
        """acc <- acc [-] X @ Y, accumulating in the family's acc dtype."""
        if jnp.issubdtype(self.pol.acc_dtype, jnp.integer):
            x = x.astype(jnp.int32)
            y = y.astype(jnp.int32)
        prod = lax.dot_general(
            x, y, dnums,
            preferred_element_type=self.pol.acc_dtype).astype(
                self.pol.acc_dtype)
        if neg_product:
            prod = -prod
        self.value = prod if self.value is None else prod + self.value
        return self

    def deprime(self, *, alpha: float = 1.0, epilogue=None, bias=None,
                residual=None, out_dtype=None):
        from repro.kernels import epilogue as _epilogue
        out = self.value
        if alpha != 1.0:
            out = out * jnp.asarray(alpha, out.dtype)
        out = _epilogue.apply(out, epilogue, bias=bias, residual=residual)
        return out.astype(out_dtype) if out_dtype is not None else out


@dataclasses.dataclass
class Dequant:
    """Deprime-stage rescale turning an int32 ``I8GER4`` accumulator into
    floating point — the W8A8 zero-point form used by ``quant.qdot``:

        out = row_scale * (acc - row_zp * col_sum) * col_scale

    Applied by ``execute`` on the accumulator-dtype matrix in output
    orientation, shared verbatim by every backend, so cross-backend
    equivalence of the quant path reduces to the exactness of the int32
    ger itself.
    """

    row_scale: jnp.ndarray    # (M, 1) activation scales
    row_zp: jnp.ndarray       # (M, 1) activation zero points
    col_sum: jnp.ndarray      # (N,)  weight column sums (int32 -> fp32)
    col_scale: jnp.ndarray    # (1, N) or (N,) weight scales

    def apply(self, acc):
        out = acc.astype(jnp.float32)
        out = self.row_scale * out \
            - (self.row_scale * self.row_zp) * self.col_sum[None, :]
        return out * self.col_scale


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------

_REGISTRY: dict[tuple, object] = {}
_EXPANSIONS: dict[Ger, tuple[Ger, object]] = {}

BACKENDS = ("pallas", "xla", "ref")


def register(backend: str, op_class: str, *, ger: Ger | None = None,
             fused: bool | None = None):
    """Decorator: register a lowering for ``(backend, op_class[, ger,
    fused])``.  ``None`` wildcards match any family / fusion state."""

    def deco(fn):
        _REGISTRY[(backend, op_class, ger, fused)] = fn
        return fn
    return deco


def lookup(backend: str, op_class: str, ger: Ger, fused: bool):
    """Most-specific-first lookup with wildcard fallbacks."""
    for key in ((backend, op_class, ger, fused),
                (backend, op_class, ger, None),
                (backend, op_class, None, fused),
                (backend, op_class, None, None)):
        fn = _REGISTRY.get(key)
        if fn is not None:
            return fn
    return None


def backends_for(op_class: str, ger: Ger, fused: bool = False) -> list[str]:
    """Which backends can lower this key (cross-backend test surface)."""
    return [b for b in BACKENDS if lookup(b, op_class, ger, fused)]


def register_expansion(ger: Ger, rep: Ger):
    """Register a pre-processing hook rewriting one ``ger`` pass into a
    chain of passes over the same resident accumulator.  ``rep`` is the
    family the chained passes run as (used for block autotuning)."""

    def deco(fn):
        _EXPANSIONS[ger] = (rep, fn)
        return fn
    return deco


def expansion_for(ger: Ger):
    return _EXPANSIONS.get(ger)


@register_expansion(Ger.F32GER_3XBF16, Ger.BF16GER2)
def _expand_f32_3xbf16(x, y):
    """fp32 operands emulated on the MXU: split hi/lo bf16 and chain
    hi*hi + hi*lo + lo*hi rank-k passes (xvbf16ger2pp chaining)."""

    def split(v):
        v = v.astype(jnp.float32)
        hi = v.astype(jnp.bfloat16)
        lo = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    xh, xl = split(x)
    yh, yl = split(y)
    return [(xh, yh, Ger.BF16GER2), (xh, yl, Ger.BF16GER2),
            (xl, yh, Ger.BF16GER2)]


def _passes(ger: Ger, x, y):
    hook = _EXPANSIONS.get(ger)
    if hook is None:
        return [(x, y, ger)]
    return hook[1](x, y)


def rep_kind(ger: Ger) -> Ger:
    """The family whose policy governs blocks/tolerances after expansion."""
    hook = _EXPANSIONS.get(ger)
    return ger if hook is None else hook[0]


def resolve_block(kind: Ger, m: int, n: int, k: int,
                  block: tuple[int, int, int] | None,
                  epilogue_key: str = "none", b: int = 1):
    """Dispatch-time autotune-cache consult (outside jit, so later tuning
    is picked up on the next call instead of being frozen into a trace).
    Explicit ``block`` wins; then a cached winner — batched contractions
    consult their own ``(b, m, n, k)`` key; else None ->
    ``tiling.choose_blocks`` inside the kernel."""
    if block is not None:
        return block
    from repro.core import autotune as _autotune
    cfg = _autotune.lookup(rep_kind(kind), m, n, k, epilogue_key, b=b)
    return (cfg.bm, cfg.bn, cfg.bk) if cfg is not None else None


# ----------------------------------------------------------------------
# Resolved op: everything a lowering needs
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Op:
    """One fully-resolved contract invocation handed to a lowering."""

    x: jnp.ndarray
    y: jnp.ndarray
    acc: jnp.ndarray | None
    bias: jnp.ndarray | None
    residual: jnp.ndarray | None
    parsed: ParsedSpec | None
    spec: str
    ger: Ger
    pol: precision.GerPolicy
    out_dtype: object             # final dtype for THIS lowering call
    epilogue: object              # Epilogue (never None; identity allowed)
    block: tuple | None
    interpret: bool
    neg_product: bool
    neg_acc: bool
    alpha: float
    beta: float
    backend: str = "xla"              # the backend this op dispatched to
    stride: tuple[int, ...] = ()      # conv op-class: per-spatial-dim stride
    padding: str = "valid"            # conv op-class: valid | same | causal
    # gemm.masked op-class: (xmask (M,), ymask (N,), pmask (K,)) bool
    # predicates on the normalized GEMM axes; each entry may be None.
    masks: tuple | None = None
    # attn op-class: the value operand, the (B, Sk) valid-slot predicate,
    # and the static attention vocabulary resolved from the Plan.
    z: jnp.ndarray | None = None
    valid: jnp.ndarray | None = None
    causal: bool = False
    window: int | None = None
    q_offset: int = 0
    q_chunk: int = 0
    # ssd op-class (x, y, z = C, B, x): the (B, NC, H, L) within-chunk
    # cumulative log-decays.
    w: jnp.ndarray | None = None

    @property
    def fused(self) -> bool:
        return not self.epilogue.is_identity

    @property
    def has_forms(self) -> bool:
        return (self.neg_product or self.neg_acc
                or self.alpha != 1.0 or self.beta != 1.0)

    def to_batched_2d(self):
        """Normalize operands to ``(B, M, K) x (B, K, N)`` (B omitted when
        there are no batch labels).  Returns (x2, y2, (b, m, n, k),
        assemble) where ``assemble`` maps the (B?, M, N) result back to
        the spec's output shape/order."""
        p = self.parsed
        x, y = self.x, self.y
        sizes = _sizes(p, x, y)
        bshape = tuple(sizes[d] for d in p.batch)
        mshape = tuple(sizes[d] for d in p.x_free)
        nshape = tuple(sizes[d] for d in p.y_free)
        kshape = tuple(sizes[d] for d in p.contract)
        b, m, n, k = (_prod(bshape), _prod(mshape), _prod(nshape),
                      _prod(kshape))

        def arrange(arr, labels, order):
            perm = tuple(labels.index(d) for d in order)
            if perm != tuple(range(len(perm))):
                arr = jnp.transpose(arr, perm)
            return arr

        batched = bool(p.batch)

        def norm(arr, labels, order, shape):
            if _packing.is_packed(arr):
                # Prepacked operand: already in the kernel-native tiled
                # layout (orientation validated at dispatch admission) —
                # normalization is exactly the per-call relayout the pack
                # paid once, so it is skipped.
                return arr
            return arrange(arr, labels, order).reshape(shape)

        x2 = norm(x, p.x_labels, p.batch + p.x_free + p.contract,
                  (b, m, k) if batched else (m, k))
        y2 = norm(y, p.y_labels, p.batch + p.contract + p.y_free,
                  (b, k, n) if batched else (k, n))

        def assemble(out):
            out = out.reshape(bshape + mshape + nshape)
            # out_perm permutes *labels*; grouped label blocks may span
            # several axes, so rebuild the axis permutation label-wise.
            if p.out_perm is not None:
                axis_of = {d: i for i, d in enumerate(p.natural_out)}
                out = jnp.transpose(
                    out, tuple(axis_of[d] for d in p.out_labels))
            return out

        return x2, y2, (b if batched else None, m, n, k), assemble


def _combine_expanded(op: Op, prod, acc_seed, residual):
    """Shared tail of a multi-pass expansion chain: apply the accumulate
    forms to the chained product, then deprime once.  ``acc_seed`` and
    ``residual`` arrive already normalized to the backend's layout."""
    acc = Accumulator(op.pol)
    acc.value = -prod if op.neg_product else prod
    if acc_seed is not None:
        seed = acc_seed.astype(prod.dtype)
        if op.beta != 1.0:
            seed = seed * jnp.asarray(op.beta, prod.dtype)
        acc.value = acc.value + (-seed if op.neg_acc else seed)
    return acc.deprime(alpha=op.alpha, epilogue=op.epilogue, bias=op.bias,
                       residual=residual, out_dtype=op.out_dtype)


# ----------------------------------------------------------------------
# Built-in lowerings
# ----------------------------------------------------------------------
# The jit'd impls take operands positionally (None allowed) and all static
# configuration by keyword, exactly like the former ops._mma_dot*_impl
# pair, so fused and unfused calls share one trace shape and remain
# bit-for-bit comparable under an outer jit (tests/test_epilogue.py).

@functools.partial(jax.jit, static_argnames=(
    "kind", "block", "interpret", "out_dtype", "epilogue", "neg_product",
    "neg_acc", "alpha", "beta", "x_layout", "y_layout", "checksum"))
def _pallas_gemm_impl(x, y, c, bias, residual, xmask, ymask, pmask, *,
                      kind, block, interpret, out_dtype, epilogue,
                      neg_product, neg_acc, alpha, beta,
                      x_layout=None, y_layout=None, checksum=False):
    from repro.kernels import mma_gemm as _gemm
    pol = precision.policy(kind)
    # Packed operands arrive as their raw tile arrays; the elementwise
    # policy cast commutes with tiling, so the values the kernel reads
    # match the natural path bit for bit.
    x = x.astype(pol.x_dtype) if not pol.packed_int4 else x
    y = y.astype(pol.y_dtype) if not pol.packed_int4 else y
    ep = epilogue if epilogue is not None and not epilogue.is_identity \
        else None
    masks = ((xmask, ymask, pmask)
             if any(m is not None for m in (xmask, ymask, pmask)) else None)
    return _gemm.mma_gemm(x, y, c, kind=kind, block=block,
                          neg_product=neg_product, neg_acc=neg_acc,
                          alpha=alpha, beta=beta,
                          ep=ep, bias=bias, residual=residual, masks=masks,
                          out_dtype=out_dtype, interpret=interpret,
                          x_layout=x_layout, y_layout=y_layout,
                          checksum=checksum)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dnums", "out_perm", "out_dtype", "epilogue", "neg_product",
    "neg_acc", "alpha", "beta"))
def _xla_gemm_impl(x, y, c, bias, residual, *, kind, dnums, out_perm,
                   out_dtype, epilogue, neg_product, neg_acc, alpha, beta):
    """One shardable dot_general + the explicit ACC lifecycle."""
    pol = precision.policy(kind)
    if pol.packed_int4:
        from repro.kernels import mma_gemm as _gemm
        # int4 nibble *dtype decode* (I4GER8 stores two lanes per byte),
        # not a tile relayout — pack-once governs layout, not precision.
        x = _gemm._unpack_int4(x, axis=dnums[0][0][0])  # repro: allow(pack-once)
        y = _gemm._unpack_int4(y, axis=dnums[0][1][0])  # repro: allow(pack-once)
    else:
        x = x.astype(pol.x_dtype)
        y = y.astype(pol.y_dtype)
    acc = Accumulator(pol)
    acc.prime(c, beta=beta, neg_acc=neg_acc)
    acc.update(x, y, dnums, neg_product=neg_product)
    if out_perm is not None:
        # values are perm-invariant; reorder before the (last-dim
        # broadcast) epilogue operands attach
        acc.value = jnp.transpose(acc.value, out_perm)
    return acc.deprime(alpha=alpha, epilogue=epilogue, bias=bias,
                       residual=residual, out_dtype=out_dtype)


@register("pallas", "gemm")
@register("pallas", "gemm.masked")
def _lower_pallas_gemm(op: Op):
    """Batch is a grid dimension: batched specs issue ONE ``pallas_call``
    over grid (b, i, j, k) — never a vmapped per-element re-trace — with
    accumulate forms, fused epilogues, and expansion chains threading
    through unchanged.  The masked op-class streams its pm* predicates
    into the same kernel as VMEM operands."""
    x2, y2, (b, m, n, k), assemble = op.to_batched_2d()
    pack = 2 if op.pol.packed_int4 else 1
    xl = yl = None
    if _packing.is_packed(x2):
        x2, xl = _packing.refresh_gemm(
            x2, kind=op.ger, m=m, n=n, k=k * pack, b=b or 1,
            epilogue_key=op.epilogue.key, explicit_block=op.block)
    if _packing.is_packed(y2):
        y2, yl = _packing.refresh_gemm(
            y2, kind=op.ger, m=m, n=n, k=k * pack, b=b or 1,
            epilogue_key=op.epilogue.key, explicit_block=op.block)
    lay = yl if yl is not None else xl
    if lay is not None:
        # Fresh (or just-repacked) layout: its block config IS the
        # dispatch block — the kernel streams the packed panels directly.
        block = lay.block
    else:
        block = resolve_block(op.ger, m, n, k * pack, op.block,
                              op.epilogue.key, b=b or 1)
    passes = _passes(op.ger, x2, y2)
    xm, ym, pm = op.masks if op.masks is not None else (None, None, None)

    # acc/residual arrive in the spec's output shape; the kernel wants
    # (M, N) — or (B, M, N) with the batch axis folded.
    norm = (m, n) if b is None else (b, m, n)
    res2 = (op.residual.reshape(norm)
            if op.residual is not None else None)
    acc2 = op.acc.reshape(norm) if op.acc is not None else None

    def one(kind, xi, yi, c, ep, out_dtype, *, forms=True, checksum=False):
        use_ep = ep is not None and not ep.is_identity
        return _pallas_gemm_impl(
            xi, yi, c, op.bias if use_ep else None,
            res2 if use_ep else None, xm, ym, pm,
            kind=kind, block=block,
            interpret=op.interpret, out_dtype=out_dtype, epilogue=ep,
            neg_product=op.neg_product and forms,
            neg_acc=op.neg_acc and forms,
            alpha=op.alpha if forms else 1.0,
            beta=op.beta if forms else 1.0,
            x_layout=xl, y_layout=yl, checksum=checksum)

    if len(passes) == 1:
        xi, yi, kind = passes[0]
        slot = _abft.capture_slot()
        if slot is not None and op.masks is None:
            # ABFT-verified dispatch: fold the per-tile column/row sums
            # into the kernel's deprime store and hand the reduced
            # checksum vectors to the dispatcher's capture slot — no
            # second HBM read of the output.
            out, ckc, ckr = one(kind, xi, yi, acc2, op.epilogue,
                                op.out_dtype, checksum=True)
            _abft.deposit(slot, ckc, ckr)
            return assemble(out)
        out = one(kind, xi, yi, acc2, op.epilogue, op.out_dtype)
        return assemble(out)

    # Expansion chain (e.g. F32GER_3XBF16): the product accumulates across
    # passes in one resident accumulator; accumulate forms and the fused
    # epilogue then apply once, at deprime, on the chained product.
    identity_ep = type(op.epilogue)()
    if not op.fused and not op.has_forms:
        out = acc2       # plain: the C seed primes the first pass
        for xi, yi, kind in passes:
            out = one(kind, xi, yi, out, identity_ep, None, forms=False)
        return assemble(out.astype(op.out_dtype)
                        if op.out_dtype is not None else out)
    prod = None
    for xi, yi, kind in passes:
        prod = one(kind, xi, yi, prod, identity_ep, None, forms=False)
    return assemble(_combine_expanded(op, prod, acc2, res2))


@register("xla", "gemm")
def _lower_xla_gemm(op: Op):
    """SPMD path: no normalization — batch labels become dot_general batch
    dims on the original operands, so the partitioner sees the same
    contraction ``jnp.einsum`` would have built and shards it unchanged."""
    op = _packing.demote_op(op, "xla-gemm")
    folded = _fold_batched_n(op)
    if folded is not None:
        sub, unfold = folded
        return unfold(_lower_xla_gemm(sub))
    p = op.parsed
    _sizes(p, op.x, op.y)     # label-consistency check
    passes = _passes(op.ger, op.x, op.y)
    if len(passes) == 1:
        xi, yi, kind = passes[0]
        return _xla_gemm_impl(
            xi, yi, op.acc, op.bias, op.residual, kind=kind,
            dnums=p.dnums, out_perm=p.out_perm, out_dtype=op.out_dtype,
            epilogue=op.epilogue, neg_product=op.neg_product,
            neg_acc=op.neg_acc, alpha=op.alpha, beta=op.beta)

    identity_ep = type(op.epilogue)()

    def plain(kind, xi, yi, c):
        return _xla_gemm_impl(
            xi, yi, c, None, None, kind=kind, dnums=p.dnums,
            out_perm=None, out_dtype=None, epilogue=identity_ep,
            neg_product=False, neg_acc=False, alpha=1.0, beta=1.0)

    if not op.fused and not op.has_forms:
        out = op.acc
        for xi, yi, kind in passes:
            out = plain(kind, xi, yi, out)
        if p.out_perm is not None:
            out = jnp.transpose(out, p.out_perm)
        return out.astype(op.out_dtype) if op.out_dtype is not None else out
    prod = None
    for xi, yi, kind in passes:
        prod = plain(kind, xi, yi, prod)
    # out_perm is None here (execute rejects fused/acc + permuted output)
    return _combine_expanded(op, prod, op.acc, op.residual)


def _fold_batched_n(op: Op):
    """Fold a batched spec's multi-label N side into one label.

    The CPU runtime refuses a batched dot_general whose y operand keeps
    two or more free dims at mixed precision ("BF16 x BF16 = F32", e.g.
    the SSD chunk-state spec ``"bcln,bclhp->bchnp"``).  When those labels
    sit adjacent in y, one free reshape folds them; the dot is the same
    contraction on every backend, and the result unfolds with the spec's
    own output permutation.  Returns ``(sub_op, unfold)`` or None."""
    p = op.parsed
    if (not p.batch or len(p.y_free) < 2 or op.acc is not None
            or op.fused):
        return None
    at = p.y_labels.index(p.y_free[0])
    if p.y_labels[at:at + len(p.y_free)] != p.y_free:
        return None
    sizes = _sizes(p, op.x, op.y)
    f = next(c for c in "ABCDEFGHIJKLMNOPQRST"
             if c not in p.x_labels + p.y_labels)
    y_labels = p.y_labels[:at] + (f,) + p.y_labels[at + len(p.y_free):]
    y = op.y.reshape(op.y.shape[:at] + (-1,)
                     + op.y.shape[at + len(p.y_free):])
    natural = p.batch + p.x_free + (f,)
    sub = dataclasses.replace(op, y=y, parsed=ParsedSpec(
        p.x_labels, y_labels, natural, p.batch, p.contract, p.x_free,
        (f,)))

    def unfold(out):
        out = out.reshape(tuple(sizes[d] for d in p.natural_out))
        return out if p.out_perm is None else jnp.transpose(out, p.out_perm)

    return sub, unfold


@register("xla", "gemm.masked")
def _lower_xla_masked(op: Op):
    """pm* masked forms on the shardable backend: the predicates fold into
    the operands as selects (execute() guarantees the natural normalized
    layout, so the masks name the trailing axes directly) and the plain
    gemm lowering runs unchanged — XLA fuses the selects into the dot's
    operand reads."""
    op = _packing.demote_op(op, "xla-masked")
    x2, y2 = _fold_masks(op.x, op.y, op.masks)
    return _lower_xla_gemm(dataclasses.replace(op, x=x2, y=y2, masks=None))


def _fold_masks(x2, y2, masks):
    """Fold the pm* predicates into normalized operands (xla/ref masked
    lowerings; the Pallas kernel streams them into VMEM instead).
    Matches the kernel: disabled lanes become exact zeros via select, and
    the rank predicate zeroes BOTH panels.  The 2-D mask reshapes
    right-align-broadcast over any leading batch axes."""
    xm, ym, pm = masks
    if xm is not None:
        x2 = jnp.where(xm.reshape(-1, 1), x2, jnp.zeros_like(x2))
    if pm is not None:
        x2 = jnp.where(pm.reshape(1, -1), x2, jnp.zeros_like(x2))
        y2 = jnp.where(pm.reshape(-1, 1), y2, jnp.zeros_like(y2))
    if ym is not None:
        y2 = jnp.where(ym.reshape(1, -1), y2, jnp.zeros_like(y2))
    return x2, y2


@register("ref", "gemm")
@register("ref", "gemm.masked")
def _lower_ref_gemm(op: Op):
    """Eager architected oracle: per-batch-element ref.ger, the ground
    truth the other backends are tested against.  Masked ops fold their
    predicates into the normalized operands (= the pm_ger oracle's
    semantics at matrix granularity)."""
    from repro.kernels import ref as _ref
    op = _packing.demote_op(op, "ref-gemm")
    x2, y2, (b, m, n, k), assemble = op.to_batched_2d()
    if op.masks is not None:
        x2, y2 = _fold_masks(x2, y2, op.masks)
    norm = (m, n) if b is None else (b, m, n)
    res2 = (op.residual.reshape(norm)
            if op.residual is not None else None)
    acc2 = op.acc.reshape(norm) if op.acc is not None else None
    passes = _passes(op.ger, x2, y2)

    def cast(v, want, pol):
        return v if pol.packed_int4 else v.astype(want)

    def ger2d(xi, yi, kind, c):
        pol = precision.policy(kind)
        return _ref.ger(cast(xi, pol.x_dtype, pol),
                        cast(yi, pol.y_dtype, pol), kind, acc=c)

    def chain(xi, yi, kind, c):
        if b is None:
            return ger2d(xi, yi, kind, c)
        return jnp.stack([ger2d(xi[i], yi[i], kind,
                                None if c is None else c[i])
                          for i in range(b)])

    if not op.fused and not op.has_forms and len(passes) == 1:
        xi, yi, kind = passes[0]
        pol = precision.policy(kind)
        if b is None and acc2 is None:
            out = _ref.ger(cast(xi, pol.x_dtype, pol),
                           cast(yi, pol.y_dtype, pol), kind,
                           neg_product=op.neg_product)
        else:
            out = chain(xi, yi, kind, acc2)
        return assemble(out.astype(op.out_dtype)
                        if op.out_dtype is not None else out)

    prod = None
    for xi, yi, kind in passes:
        prod = chain(xi, yi, kind, prod)
    return assemble(_combine_expanded(op, prod, acc2, res2))


# ---- saturating accumulate forms (xvi16ger2s / xvi8ger4spp) ----------

@register("xla", "gemm.saturating")
def _lower_xla_saturating(op: Op):
    """Clamped rank-r accumulation as a lax.scan over K groups (VPU path —
    saturating integer accumulate has no MXU analogue; DESIGN.md)."""
    pol = op.pol
    if not jnp.issubdtype(pol.acc_dtype, jnp.integer):
        raise ValueError("saturating forms are integer-only")
    x2, y2, (b, m, n, k), assemble = op.to_batched_2d()
    if b is not None:
        raise ValueError("saturating forms are 2-D only")
    r = pol.arch_rank
    assert k % r == 0, (k, r)
    i32max = jnp.int32(jnp.iinfo(jnp.int32).max)
    i32min = jnp.int32(jnp.iinfo(jnp.int32).min)
    # One architected rank-r product group cannot overflow int32
    # (2 * 32767^2 < 2^31 - 1 for int16; 4 * 127 * 255 for int8), so group
    # products are exact in int32; only the accumulate saturates.
    # K-group axis must lead for lax.scan; this reshapes the *already
    # unpacked* saturating operand, not a tile layout.
    # repro: allow(pack-once)
    xg = x2.reshape(m, k // r, r).swapaxes(0, 1).astype(jnp.int32)
    yg = y2.reshape(k // r, r, n).astype(jnp.int32)

    def step(a, xy):
        xs, ys = xy
        p = lax.dot_general(xs, ys, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
        s = a + p  # wraps (two's complement) — detect and saturate
        overflow_pos = (p > 0) & (s < a)
        overflow_neg = (p < 0) & (s > a)
        s = jnp.where(overflow_pos, i32max, s)
        s = jnp.where(overflow_neg, i32min, s)
        return s, None

    init = (jnp.zeros((m, n), jnp.int32) if op.acc is None
            else op.acc.reshape(m, n).astype(jnp.int32))
    out, _ = lax.scan(step, init, (xg, yg))
    return assemble(out.astype(op.out_dtype)
                    if op.out_dtype is not None else out)


@register("ref", "gemm.saturating")
def _lower_ref_saturating(op: Op):
    """Independent oracle: exact int64 group sums, clamped per update."""
    pol = op.pol
    x2, y2, (b, m, n, k), assemble = op.to_batched_2d()
    if b is not None:
        raise ValueError("saturating forms are 2-D only")
    r = pol.arch_rank
    assert k % r == 0, (k, r)
    import numpy as np
    x64 = np.asarray(x2).astype(np.int64)
    y64 = np.asarray(y2).astype(np.int64)
    acc = (np.zeros((m, n), np.int64) if op.acc is None
           else np.asarray(op.acc).reshape(m, n).astype(np.int64))
    for g in range(k // r):
        p = x64[:, g * r:(g + 1) * r] @ y64[g * r:(g + 1) * r, :]
        acc = np.clip(acc + p, np.iinfo(np.int32).min,
                      np.iinfo(np.int32).max)
    out = jnp.asarray(acc.astype(np.int32))
    return assemble(out.astype(op.out_dtype)
                    if op.out_dtype is not None else out)


# ---- conv op-class (SCONV, paper section V-B) ------------------------
# One shared geometry normalizer (padding math identical across backends),
# three lowerings: Pallas (implicit im2col via mma_conv's fused KW panel),
# XLA (one shardable conv_general_dilated), ref (materialized-Abar oracle).

def _conv_norm(op: Op):
    """Normalize a conv invocation to padded NHWC x HWIO form.

    Returns ``(x4, w4, (sh, sw), depthwise, squeeze)``: 1-D specs gain a
    size-1 H axis (``squeeze`` strips it from the output), and the
    ``same``/``causal`` paddings become one explicit ``jnp.pad`` here so
    every backend sees identical VALID geometry.
    """
    nd, depthwise = _CONV_SPECS[op.spec]
    x, w = op.x, op.y
    packed_w = _packing.is_packed(w)
    if nd == 1:
        x = x[:, None]                           # (N, 1, L, C)
        if not packed_w:
            w = w[None]                          # (1, KW, C[, F])
        strides = (1,) + op.stride
    else:
        strides = op.stride
    if packed_w:
        # Prepacked filter bank (1-D layouts already carry the size-1 KH
        # axis): geometry comes from the layout, the tile stream flows
        # through to the kernel untouched.
        kh, kw, c = w.layout.kh, w.layout.kw, w.layout.c
    else:
        kh, kw = w.shape[0], w.shape[1]
        c = w.shape[2]
    if x.shape[-1] != c:
        raise ValueError(f"conv channel mismatch: image {x.shape} vs "
                         f"filter {w.shape}")
    pads = []
    for k, st, size in zip((kh, kw), strides, x.shape[1:3]):
        if op.padding == "valid":
            lo = hi = 0
        elif op.padding == "same":
            out = -(-size // st)
            total = max((out - 1) * st + k - size, 0)
            lo, hi = total // 2, total - total // 2
        elif op.padding == "causal":       # left pad: output t sees <= t
            if nd != 1:
                raise ValueError(
                    "causal padding is 1-D (time-axis) vocabulary; "
                    f"spec {op.spec!r} is 2-D")
            lo, hi = k - 1, 0
        else:
            raise ValueError(f"unknown conv padding {op.padding!r}; "
                             f"want valid | same | causal")
        pads.append((lo, hi))
    if any(p != (0, 0) for p in pads):
        x = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    return x, w, strides, depthwise, nd == 1


@functools.partial(jax.jit, static_argnames=(
    "kind", "strides", "depthwise", "squeeze", "out_dtype", "epilogue"))
def _xla_conv_impl(x, w, bias, residual, *, kind, strides, depthwise,
                   squeeze, out_dtype, epilogue):
    """One shardable conv_general_dilated per architected pass + the
    epilogue at deprime.

    Per pass, inputs are rounded to that pass family's operand dtype, then
    up-cast to the accumulator dtype for the conv itself — the same
    numerics as a reduced-precision MXU pass with a high-precision
    accumulator, and (unlike a ``preferred_element_type`` widening, whose
    transpose rule rejects the dtype mix) cleanly differentiable.
    Convolution is bilinear, so expansion hooks (F32GER_3XBF16) apply
    exactly as for GEMM: the hi/lo-split passes chain over one resident
    accumulator.
    """
    pol = precision.policy(kind)

    def one(xi, wi):
        if depthwise:
            c = wi.shape[2]
            return lax.conv_general_dilated(
                xi, wi.reshape(wi.shape[0], wi.shape[1], 1, c), strides,
                "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=c)
        return lax.conv_general_dilated(
            xi, wi, strides, "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    out = None
    for xi, wi, k in _passes(kind, x, w):
        pk = precision.policy(k)
        o = one(xi.astype(pk.x_dtype).astype(pol.acc_dtype),
                wi.astype(pk.y_dtype).astype(pol.acc_dtype))
        out = o if out is None else out + o
    out = out.astype(pol.acc_dtype)
    if squeeze:
        out = out[:, 0]
    from repro.kernels import epilogue as _epilogue
    out = _epilogue.apply(out, epilogue, bias=bias, residual=residual)
    return out.astype(out_dtype) if out_dtype is not None else out


@register("xla", "conv")
def _lower_xla_conv(op: Op):
    op = _packing.demote_op(op, "xla-conv")
    x4, w4, strides, depthwise, squeeze = _conv_norm(op)
    return _xla_conv_impl(
        x4, w4, op.bias, op.residual, kind=op.ger, strides=strides,
        depthwise=depthwise, squeeze=squeeze, out_dtype=op.out_dtype,
        epilogue=op.epilogue)


@functools.partial(jax.jit, static_argnames=(
    "kind", "bf", "strides", "interpret", "out_dtype", "epilogue",
    "squeeze", "w_layout"))
def _pallas_conv_impl(x, w, bias, residual, *, kind, bf, strides,
                      interpret, out_dtype, epilogue, squeeze,
                      w_layout=None):
    from repro.kernels import epilogue as _epilogue
    from repro.kernels import mma_conv as _conv
    pol = precision.policy(kind)
    ep = epilogue if epilogue is not None and not epilogue.is_identity \
        else None
    passes = _passes(kind, x, w)
    if len(passes) == 1:
        xi, wi, k = passes[0]
        pk = precision.policy(k)
        out = _conv.mma_conv2d(
            xi.astype(pk.x_dtype), wi.astype(pk.y_dtype), bf=bf,
            stride=strides,
            out_dtype=out_dtype if out_dtype is not None else pol.acc_dtype,
            ep=ep, bias=bias, residual=residual, interpret=interpret,
            w_layout=w_layout)
        return out[:, 0] if squeeze else out
    if w_layout is not None:      # execute() demotes packed expansion gers
        raise ValueError("prepacked filters do not compose with expansion "
                         "chains; demote via packing.demote_op first")
    # Expansion chain (F32GER_3XBF16): conv is bilinear, so the hi/lo
    # split passes sum over one accumulator; the epilogue then applies
    # once on the chained product (mirrors the gemm expansion tail).
    prod = None
    for xi, wi, k in passes:
        pk = precision.policy(k)
        o = _conv.mma_conv2d(
            xi.astype(pk.x_dtype), wi.astype(pk.y_dtype), bf=bf,
            stride=strides, out_dtype=pol.acc_dtype, interpret=interpret)
        prod = o if prod is None else prod + o
    # epilogue on the 4-D chained product (residual arrives 4-D), then
    # squeeze, matching the kernel's in-store application order.
    prod = _epilogue.apply(prod, ep, bias=bias, residual=residual)
    if squeeze:
        prod = prod[:, 0]
    return prod.astype(out_dtype) if out_dtype is not None else prod


@functools.partial(jax.jit, static_argnames=(
    "kind", "bc", "strides", "interpret", "out_dtype", "epilogue",
    "squeeze"))
def _pallas_depthwise_impl(x, w, bias, residual, *, kind, bc, strides,
                           interpret, out_dtype, epilogue, squeeze):
    """Resident-accumulator depthwise kernel (mma_conv), expansion chain
    included — depthwise conv is bilinear too, so the F32GER_3XBF16 hi/lo
    passes sum over one accumulator exactly like the dense conv."""
    from repro.kernels import epilogue as _epilogue
    from repro.kernels import mma_conv as _conv
    pol = precision.policy(kind)
    ep = epilogue if epilogue is not None and not epilogue.is_identity \
        else None
    passes = _passes(kind, x, w)
    if len(passes) == 1:
        xi, wi, k = passes[0]
        pk = precision.policy(k)
        out = _conv.mma_depthwise_conv2d(
            xi.astype(pk.x_dtype), wi.astype(pk.y_dtype), bc=bc,
            stride=strides,
            out_dtype=out_dtype if out_dtype is not None else pol.acc_dtype,
            ep=ep, bias=bias, residual=residual, interpret=interpret)
        return out[:, 0] if squeeze else out
    prod = None
    for xi, wi, k in passes:
        pk = precision.policy(k)
        o = _conv.mma_depthwise_conv2d(
            xi.astype(pk.x_dtype), wi.astype(pk.y_dtype), bc=bc,
            stride=strides, out_dtype=pol.acc_dtype, interpret=interpret)
        prod = o if prod is None else prod + o
    prod = _epilogue.apply(prod, ep, bias=bias, residual=residual)
    if squeeze:
        prod = prod[:, 0]
    return prod.astype(out_dtype) if out_dtype is not None else prod


@register("pallas", "conv")
def _lower_pallas_conv(op: Op):
    """Implicit-im2col kernel: the resident (OW, bf) accumulator takes one
    rank-(KW*C) update per KH step (mma_conv's fused KW panel).  Depthwise
    (groups == C) runs the resident-accumulator VPU kernel — no more XLA
    reroute.  Non-f32-accumulator convs never reach this lowering —
    ``execute`` reroutes them to the shardable XLA backend (same precedent
    as gemm.saturating) before the dispatch is counted."""
    x4, w4, strides, depthwise, squeeze = _conv_norm(op)
    res = op.residual
    if res is not None and squeeze:
        res = res[:, None]
    if depthwise:
        return _pallas_depthwise_impl(
            x4, w4, op.bias, res, kind=op.ger,
            bc=op.block[1] if op.block is not None else None,
            strides=strides, interpret=op.interpret,
            out_dtype=op.out_dtype, epilogue=op.epilogue, squeeze=squeeze)
    if _packing.is_packed(w4):
        lay0 = w4.layout
        kh, kw, c, f = lay0.kh, lay0.kw, lay0.c, lay0.f
        ow = (x4.shape[2] - kw) // strides[1] + 1
        w4, lay = _packing.refresh_conv(
            w4, kind=op.ger, ow=ow, f=f, kwc=kw * c,
            epilogue_key=op.epilogue.key, explicit_block=op.block)
        if lay is not None:
            return _pallas_conv_impl(
                x4, w4, op.bias, res, kind=op.ger, bf=lay.bf,
                strides=strides, interpret=op.interpret,
                out_dtype=op.out_dtype, epilogue=op.epilogue,
                squeeze=squeeze, w_layout=lay)
        # stale under trace: w4 is the demoted natural filter — fall
        # through to the natural dispatch below
    kh, kw, c, f = w4.shape
    ow = (x4.shape[2] - kw) // strides[1] + 1
    # Best-effort autotune-cache reuse: the panel dot is (OW, KW*C) x
    # (KW*C, bf), so consult the gemm cache at that shape; only the N-tile
    # (bf) of a winner applies to the conv grid.
    block = resolve_block(op.ger, ow, f, kw * c, op.block, op.epilogue.key)
    return _pallas_conv_impl(
        x4, w4, op.bias, res, kind=op.ger,
        bf=block[1] if block is not None else None, strides=strides,
        interpret=op.interpret, out_dtype=op.out_dtype,
        epilogue=op.epilogue, squeeze=squeeze)


@register("ref", "conv")
def _lower_ref_conv(op: Op):
    """Materialized-Abar oracle (ref.conv2d) — exactly the patch matrix
    the Pallas kernel avoids building; depthwise: eager shift-and-sum.
    Expansion hooks chain per-pass like the gemm oracle."""
    from repro.kernels import epilogue as _epilogue
    from repro.kernels import ref as _ref
    op = _packing.demote_op(op, "ref-conv")
    x4, w4, strides, depthwise, squeeze = _conv_norm(op)
    pol = op.pol
    out = None
    for xi, wi, k in _passes(op.ger, x4, w4):
        pk = precision.policy(k)
        xi = xi.astype(pk.x_dtype)
        wi = wi.astype(pk.y_dtype)
        if depthwise:
            o = _ref.depthwise_conv(xi, wi, stride=strides,
                                    acc_dtype=pol.acc_dtype)
        else:
            o = _ref.conv2d(xi, wi, stride=strides)
        o = o.astype(pol.acc_dtype)
        out = o if out is None else out + o
    if squeeze:
        out = out[:, 0]
    out = _epilogue.apply(out, op.epilogue, bias=op.bias,
                          residual=op.residual)
    return out.astype(op.out_dtype) if op.out_dtype is not None else out


# ---- complex op-class (complex matmul / DFT, paper section III) ------

def _lower_complex(op: Op):
    """Complex contraction as the four real accumulate-form gers the paper
    composes (re <- re@re - im@im via the np form, im <- re@im + im@re via
    pp) — the decomposition ``blas3.complex_gemm`` used to hand-code.  Runs
    on whichever backend's gemm lowering this op resolved to, so the
    cross-backend equivalence surface extends to complex for free —
    including batched specs (the paper's batched-DFT case), now that the
    Pallas gemm lowering threads accumulator seeds through its batch grid
    axis."""
    fn = lookup(op.backend, "gemm", op.ger, False)
    identity_ep = type(op.epilogue)()
    xr, xi = jnp.real(op.x), jnp.imag(op.x)
    yr, yi = jnp.real(op.y), jnp.imag(op.y)

    def ger(a, b, acc=None, neg=False):
        sub = dataclasses.replace(
            op, x=a, y=b, acc=acc, bias=None, residual=None, out_dtype=None,
            epilogue=identity_ep, neg_product=neg, neg_acc=False,
            alpha=1.0, beta=1.0)
        return fn(sub)

    re = ger(xr, yr)
    re = ger(xi, yi, acc=re, neg=True)           # np accumulate form
    im = ger(xr, yi)
    im = ger(xi, yr, acc=im)                     # pp accumulate form

    # External accumulate forms, per component (mirrors Accumulator:
    # out = alpha * ([-]prod + beta * [-]C)).
    if op.neg_product:
        re, im = -re, -im
    if op.acc is not None:
        cr = jnp.real(op.acc).astype(re.dtype)
        ci = jnp.imag(op.acc).astype(im.dtype)
        if op.beta != 1.0:
            cr = cr * jnp.asarray(op.beta, cr.dtype)
            ci = ci * jnp.asarray(op.beta, ci.dtype)
        if op.neg_acc:
            cr, ci = -cr, -ci
        re, im = re + cr, im + ci
    if op.alpha != 1.0:
        re = re * jnp.asarray(op.alpha, re.dtype)
        im = im * jnp.asarray(op.alpha, im.dtype)

    if op.out_dtype is None:
        return lax.complex(re, im)
    od = jnp.dtype(op.out_dtype)
    if jnp.issubdtype(od, jnp.complexfloating):
        return lax.complex(re, im).astype(od)
    # Real out_dtype: round each component to it, then re-embed (bf16/f16
    # have no complex pairing, so the container stays complex64).
    re, im = re.astype(od), im.astype(od)
    f = jnp.float64 if od == jnp.dtype(jnp.float64) else jnp.float32
    return lax.complex(re.astype(f), im.astype(f))


for _b in BACKENDS:
    _REGISTRY[(_b, "complex", None, None)] = _lower_complex


# ---- attn op-class (fused scaled-dot-product attention) --------------
# Three lowerings over one convention: causal/window/q_offset/valid are
# structural predicates on the score tile; rows whose every slot is masked
# yield exact zeros.  Pallas runs the flash kernel with the causal-bounded
# grid; xla runs the chunked two-dot math the SPMD partitioner can shard;
# ref is the pinned two-contract oracle (mma_attention.ref_attention).

def _attn_blocks(op: Op, bh: int, sq: int, sk: int, d: int
                 ) -> tuple[int, int]:
    """Resolve the (bq, bk) attention blocks: explicit Plan.block wins,
    then a cached autotune winner keyed on (bh, sq, sk, d), else the
    largest divisors of Sq/Sk not above 128 (the kernel requires dividing
    blocks; the fringe lives in the grid plan, not padded operands)."""
    if op.block is not None:
        bq, bk = op.block
        return min(bq, sq), min(bk, sk)
    from repro.core import autotune as _autotune
    hit = _autotune.lookup_attn(op.ger, bh, sq, sk, d, op.epilogue.key)
    if hit is not None:
        return hit

    def divisor(s: int, want: int) -> int:
        for cand in range(min(want, s), 0, -1):
            if s % cand == 0:
                return cand
        return 1

    return divisor(sq, 128), divisor(sk, 128)


def _attn_tiles_fit(op: Op) -> bool:
    """Shape rule of the compiled flash kernel.  Its head-major blocks
    end in (rows, D), which the TPU tiling takes when rows is a multiple
    of 8 or the whole sequence; the (1, bk) ``valid`` block further wants
    bk a multiple of 128 or the whole Sk.  Interpret mode has no tiling."""
    b, sq, h, d = op.x.shape
    sk = op.y.shape[1]
    bq, bk = _attn_blocks(op, b * h, sq, sk, d)
    return ((bq == sq or bq % 8 == 0) and (bk == sk or bk % 8 == 0)
            and (op.valid is None or bk == sk or bk % 128 == 0))


@functools.partial(jax.jit, static_argnames=(
    "kind", "block", "causal", "window", "q_offset", "interpret",
    "out_dtype", "epilogue"))
def _pallas_attn_impl(q, k, v, bias, residual, valid, *, kind, block,
                      causal, window, q_offset, interpret, out_dtype,
                      epilogue):
    from repro.kernels import mma_attention as _attn
    pol = precision.policy(kind)
    ep = epilogue if epilogue is not None and not epilogue.is_identity \
        else None
    return _attn.mma_flash_attention(
        q.astype(pol.x_dtype), k.astype(pol.x_dtype),
        v.astype(pol.y_dtype), causal=causal, q_offset=q_offset,
        window=window, valid=valid, block_q=block[0], block_k=block[1],
        ep=ep, bias=bias, residual=residual,
        out_dtype=out_dtype if out_dtype is not None else pol.acc_dtype,
        interpret=interpret)


@register("pallas", "attn")
def _lower_pallas_attn(op: Op):
    """The flash kernel: grid-native (B, H, live-kv-steps) with GQA
    head-group broadcast in the BlockSpec index maps, the causal/window
    bounds shrinking the flattened KV grid, and the autotune cache
    consulted per (bh, sq, sk, d) for the (bq, bk) blocks."""
    b, sq, h, d = op.x.shape
    sk = op.y.shape[1]
    block = _attn_blocks(op, b * h, sq, sk, d)
    return _pallas_attn_impl(
        op.x, op.y, op.z, op.bias, op.residual, op.valid, kind=op.ger,
        block=block, causal=op.causal, window=op.window,
        q_offset=op.q_offset, interpret=op.interpret,
        out_dtype=op.out_dtype, epilogue=op.epilogue)


def attend_chunk(q, k, v, *, q_pos, kv_pos, causal, window, valid):
    """One query chunk against full K/V — THE chunked-attention math,
    shared by the xla attn lowering's scan below and by ``layers.sdpa``'s
    ring-buffer decode path (so the two can never drift).

    q (B, C, H, D) with K/V already head-repeated; ``q_pos`` (1|B, C) and
    ``kv_pos`` (1|B, Sk) absolute positions (ring-buffer caches pass
    data-dependent kv_pos); ``valid`` (1|B, Sk) or None.  Returns the
    fp32 accumulator; rows whose every slot is masked yield exact zeros —
    the convention shared with the flash kernel's masked-block guard and
    l == 0 deprime guard.
    """
    s = lax.dot_general(
        q, k, (((3,), (3,)), ((0, 2), (0, 2))),
        preferred_element_type=jnp.float32)              # (B, H, C, Sk)
    s = s * (q.shape[-1] ** -0.5)
    mask = jnp.ones((1, q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        mask = mask & (q_pos[:, :, None] >= kv_pos[:, None, :])
    if window is not None:
        mask = mask & (q_pos[:, :, None] - kv_pos[:, None, :] < window)
    if valid is not None:
        mask = mask & valid[:, None, :]
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax degenerates to uniform mean(V); zero them
    p = jnp.where(mask.any(-1)[:, None, :, None], p, 0.0)
    return lax.dot_general(
        p.astype(v.dtype), v, (((3,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=(
    "kind", "causal", "window", "q_offset", "q_chunk", "out_dtype",
    "epilogue"))
def _xla_attn_impl(q, k, v, bias, residual, valid, *, kind, causal, window,
                   q_offset, q_chunk, out_dtype, epilogue):
    """Chunked two-dot attention (the layers._attend math, facility-owned):
    a lax.scan over query chunks bounds live scores to (B, H, chunk, Sk),
    and a ragged tail chunk keeps the bound for any Sq — no silent
    fall-back to unchunked attention when Sq % q_chunk != 0."""
    from repro.kernels import epilogue as _epilogue
    pol = precision.policy(kind)
    q = q.astype(pol.x_dtype)
    k = k.astype(pol.x_dtype)
    v = v.astype(pol.y_dtype)
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             (b, k.shape[1], kvh, rep, d)
                             ).reshape(b, k.shape[1], h, d)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             (b, v.shape[1], kvh, rep, d)
                             ).reshape(b, v.shape[1], h, d)
    if valid is not None:
        valid = jnp.asarray(valid, bool).reshape(-1, k.shape[1])
    pos = (jnp.arange(sq) + q_offset)[None]              # (1, Sq)
    kv_pos = jnp.arange(k.shape[1])[None]                # (1, Sk)

    chunk = min(q_chunk or ATTN_Q_CHUNK, sq)
    nc, tail = divmod(sq, chunk)
    main = nc * chunk
    if nc > 1:
        qc = q[:, :main].reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)
        pc = pos[:, :main].reshape(1, nc, chunk).transpose(1, 0, 2)

        def body(_, xs):
            qb, pb = xs
            return None, attend_chunk(qb, k, v, q_pos=pb, kv_pos=kv_pos,
                                      causal=causal, window=window,
                                      valid=valid)

        _, outs = lax.scan(body, None, (qc, pc))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, main, h, d)
    else:
        out = attend_chunk(q[:, :main], k, v, q_pos=pos[:, :main],
                           kv_pos=kv_pos, causal=causal, window=window,
                           valid=valid)
    if tail:
        out_tail = attend_chunk(q[:, main:], k, v, q_pos=pos[:, main:],
                                kv_pos=kv_pos, causal=causal,
                                window=window, valid=valid)
        out = jnp.concatenate([out, out_tail], axis=1)
    out = _epilogue.apply(out, epilogue, bias=bias, residual=residual)
    return out.astype(out_dtype) if out_dtype is not None else out


@register("xla", "attn")
def _lower_xla_attn(op: Op):
    return _xla_attn_impl(
        op.x, op.y, op.z, op.bias, op.residual, op.valid, kind=op.ger,
        causal=op.causal, window=op.window, q_offset=op.q_offset,
        q_chunk=op.q_chunk, out_dtype=op.out_dtype, epilogue=op.epilogue)


@register("ref", "attn")
def _lower_ref_attn(op: Op):
    """The pinned two-contract oracle: scores and values run as architected
    gers on the pinned xla gemm lowering, softmax eagerly between them."""
    from repro.kernels import epilogue as _epilogue
    from repro.kernels import mma_attention as _attn
    pol = op.pol
    out = _attn.ref_attention(
        op.x.astype(pol.x_dtype), op.y.astype(pol.x_dtype),
        op.z.astype(pol.y_dtype), causal=op.causal, window=op.window,
        q_offset=op.q_offset, valid=op.valid)
    out = _epilogue.apply(out, op.epilogue, bias=op.bias,
                          residual=op.residual)
    return out.astype(op.out_dtype) if op.out_dtype is not None else out


# ---- ssd op-class (the SSD chunk scan's intra-chunk branch) ----------
# Pallas runs one kernel per (sequence, chunk, head block) that keeps the
# scores, the decays and their product in VMEM; xla and ref run the chain
# the models used to inline — the (L, L) decay mask, the C·Bᵀ scores as a
# gemm, their product, and the gemm with x — on that backend's gemm.

def ssd_shape_fits(l: int, n: int, h: int, p: int, *, ger: Ger,
                   c_dtype, x_dtype, out_dtype) -> bool:
    """Shape rule of the kernel at chunk ``l``, state ``n`` and ``h``
    heads of width ``p``: whole 128-row tiles, head blocks whose x/y
    lanes are whole 128-lane tiles, heads of a width that tiles the
    lanes, a float family that runs as one pass with an f32 accumulator,
    and a working set within the VMEM budget."""
    from repro.core import tiling as _tiling
    from repro.kernels import mma_ssd as _ssd
    hb = _ssd.ssd_head_block(h)
    if (ger not in _ATTN_GERS or l % _ssd.ROW_TILE
            or (hb * p) % _ssd.LANES
            or (p % _ssd.LANES and _ssd.LANES % p)):
        return False
    return _ssd.ssd_vmem_bytes(
        l, n, hb, p, in_bytes=jnp.dtype(c_dtype).itemsize,
        x_bytes=jnp.dtype(x_dtype).itemsize,
        out_bytes=jnp.dtype(out_dtype).itemsize) <= _tiling.VMEM_BUDGET


def _ssd_fits(op: Op, heads: int | None = None) -> bool:
    """:func:`ssd_shape_fits` for ``op``, at ``heads`` heads (default:
    all of x's)."""
    l, n = op.x.shape[2:]
    h, p = op.z.shape[3:]
    return ssd_shape_fits(l, n, heads or h, p, ger=op.ger,
                          c_dtype=op.x.dtype, x_dtype=op.z.dtype,
                          out_dtype=op.out_dtype)


# The kernel's custom call takes this function's name.  The branch's work
# is two contractions (C·Bᵀ and the decayed scores times x), so the name
# carries "gemm", as `_pallas_gemm_impl`'s does: a trace's reader classes
# the kernel's time with the contractions whose FLOPs it computes.
@functools.partial(jax.jit, static_argnames=(
    "kind", "out_dtype", "interpret"))
def _pallas_gemm_ssd_impl(c, b, x, cum, *, kind, out_dtype, interpret):
    from repro.kernels import mma_ssd as _ssd
    pol = precision.policy(kind)
    return _ssd.mma_ssd(
        c, b, x, cum, c_dtype=pol.x_dtype, b_dtype=pol.y_dtype,
        att_dtype=x.dtype, prod_dtype=pol.x_dtype, x_dtype=pol.y_dtype,
        out_dtype=out_dtype, interpret=interpret)


@register("pallas", "ssd")
def _lower_pallas_ssd(op: Op):
    return _pallas_gemm_ssd_impl(op.x, op.y, op.z, op.w, kind=op.ger,
                                 out_dtype=op.out_dtype,
                                 interpret=op.interpret)


def _ssd_chain(op: Op, gemm):
    """The intra-chunk branch as XLA ops: the causal decay mask from the
    cumulative sums' differences, the scores ``C·Bᵀ`` in f32, their
    product, and the decayed scores — rounded to x's dtype — times x, each
    contraction through ``gemm`` (a registered gemm lowering)."""
    c, b, x, cum = op.x, op.y, op.z, op.w
    l = cum.shape[-1]
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), 0)
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))     # (b,nc,h,L,L)

    def contract(spec, u, v, out_dtype):
        return gemm(dataclasses.replace(
            op, x=u, y=v, z=None, w=None, spec=spec, out_dtype=out_dtype,
            parsed=parse_spec(spec, u.ndim, v.ndim)))

    scores = contract("bcln,bcsn->bcls", c, b, jnp.float32)   # (b,nc,L,L)
    att = scores[:, :, None] * decay                          # (b,nc,h,L,L)
    return contract("bchls,bcshp->bclhp", att.astype(x.dtype), x,
                    op.out_dtype)


@register("xla", "ssd")
def _lower_xla_ssd(op: Op):
    return _ssd_chain(op, _lower_xla_gemm)


@register("ref", "ssd")
def _lower_ref_ssd(op: Op):
    return _ssd_chain(op, _lower_ref_gemm)


# ---- general einsum fallback -----------------------------------------

@register("xla", "einsum")
def _lower_xla_einsum(op: Op):
    """Specs the GEMM normalizer rejects (diagonals, sum-reductions):
    policy-cast inputs, high-precision accumulation, one einsum."""
    pol = op.pol
    if op.acc is not None or op.fused or op.has_forms:
        raise ValueError(
            f"spec {op.spec!r} is not GEMM-shaped; accumulate forms and "
            f"fused epilogues need a gemm-class contraction")
    x = op.x if pol.packed_int4 else op.x.astype(pol.x_dtype)
    y = op.y if pol.packed_int4 else op.y.astype(pol.y_dtype)
    out = jnp.einsum(op.spec, x, y, preferred_element_type=pol.acc_dtype)
    return out.astype(op.out_dtype) if op.out_dtype is not None else out


_REGISTRY[("ref", "einsum", None, None)] = _lower_xla_einsum


# ----------------------------------------------------------------------
# Guarded dispatch: the degradation ladder (DESIGN.md section 8)
# ----------------------------------------------------------------------
# Opt-in via FacilityConfig(guards=True): contract outputs pass a NaN/Inf
# detector and lowering failures (compile error, unsupported shape,
# injected fault) demote down the ladder pallas -> xla -> ref — the MX
# argument (arXiv:2401.04012) that an aggressive fast path is safe to ship
# exactly when a cheaper always-correct lowering backs it.  Each demotion
# is logged and quarantined per (op-class, ger, spec, shapes) so a
# poisoned kernel config is demoted ONCE, not re-tried on every call.
# With guards off the dispatch tail is byte-identical to the unguarded
# facility (asserted by tests/test_guards.py).

LADDER = ("pallas", "xla", "ref")

# Exception classes a broken lowering legitimately raises (narrow on
# purpose: programming errors like AttributeError must surface, not
# demote).  InjectedFault is the fault-harness stand-in for all of them.
LOWERING_ERRORS = (ValueError, TypeError, NotImplementedError,
                   ArithmeticError, jax.errors.JaxRuntimeError)

_QUARANTINE: dict[tuple, str] = {}     # guard key -> demoted start rung
GUARD_EVENTS: list[dict] = []          # demotion log (tests/CI assert)
_guard_log = logging.getLogger("repro.facility.guards")


def guard_key(op_class: str, op: "Op") -> tuple:
    """Quarantine granularity: one entry per (op-class, ger, spec, operand
    shapes) — the same granularity the autotune cache keys a kernel config
    by, so "this kernel config is poisoned" maps one-to-one."""
    return (op_class, op.ger.value, op.spec, tuple(jnp.shape(op.x)),
            tuple(jnp.shape(op.y)))


def quarantine_state() -> dict:
    return dict(_QUARANTINE)


def clear_guard_state() -> None:
    _QUARANTINE.clear()
    GUARD_EVENTS.clear()
    _abft.clear_verdicts()


def _output_finite(out) -> bool:
    """The NaN/Inf detector.  Tracers (a contract call inside someone
    else's jit) cannot be value-inspected — the exception ladder still
    protects them, value poisoning is caught at the caller's sync point
    (e.g. the serving loop's per-step logits check)."""
    if isinstance(out, jax.core.Tracer):
        return True
    dt = out.dtype
    if not jnp.issubdtype(dt, jnp.inexact):
        return True
    if jnp.issubdtype(dt, jnp.complexfloating):
        return bool(jnp.isfinite(jnp.real(out)).all()
                    & jnp.isfinite(jnp.imag(out)).all())
    return bool(jnp.isfinite(out).all())


def _record_demotion(key, frm, to, reason, op_class, spec):
    ev = {"op_class": op_class, "spec": spec, "from": frm, "to": to,
          "reason": reason, "key": key}
    GUARD_EVENTS.append(ev)
    _guard_log.warning("guard: %s %r demoted %s -> %s (%s)",
                       op_class, spec, frm, to, reason)


def _apply_data_fault(fault, out):
    """Apply the data-shaped fault kinds to a lowering output.  ``flip``
    skips tracers: a trace-time flip would bake permanent corruption into
    the compiled function (the ``nan`` kind covers trace-time poisoning)."""
    if fault is None:
        return out
    if fault.kind == _faults.NAN:
        return _faults.poison(out)
    if fault.kind == _faults.FLIP \
            and not isinstance(out, jax.core.Tracer):
        return _faults.flip(out, fault.seed)
    return out


def _guarded_dispatch(op: "Op", op_class: str, backend: str, ger: Ger,
                      fused: bool, abft_on: bool = False, wrap=None):
    """Walk the ladder from ``backend`` (or its quarantined demotion)
    until a rung returns a clean output.

    Demotion rules:
      * a rung that *raises* (LOWERING_ERRORS / InjectedFault) is
        quarantined immediately — the failure is structural, retrying it
        per call buys nothing;
      * a rung whose output is non-finite is demoted *pending*: the
        quarantine commits only if a later rung produces finite output
        (otherwise the NaN is input-borne and no rung is at fault);
      * the final rung's non-finite output is returned as-is, without
        quarantine — ref is ground truth, garbage-in stays garbage-out;
      * with ABFT on (``FacilityConfig.abft``, core/abft.py) a rung whose
        output fails checksum verification is retried ONCE on the same
        rung (transient SDC clears), then demoted *pending* like the
        non-finite case; the final rung's mismatch is returned as-is
        with an unrecovered verdict on ``abft.VERDICTS``.
    """
    key = guard_key(op_class, op)
    start = _QUARANTINE.get(key, backend)
    if start not in LADDER:
        start = backend
    attempts = [r for r in LADDER[LADDER.index(start):]
                if lookup(r, op_class, ger, fused) is not None]
    if not attempts:
        raise NotImplementedError(
            f"no lowering registered on any ladder rung for "
            f"({op_class!r}, {ger}, fused={fused})")
    aplan = None
    if abft_on:
        conv_dw = (op_class == "conv"
                   and _CONV_SPECS.get(op.spec, (0, False))[1])
        aplan = _abft.plan_for(op, op_class,
                               expanded=expansion_for(ger) is not None,
                               conv_depthwise=conv_dw)

    def attempt(fn, sub):
        """One guarded execution: inject, run (checksum-instrumented when
        a verification plan is active), apply data-shaped faults.
        Returns (out, raw, cap): ``out`` is the caller-visible output,
        ``raw`` the array verification checks (augmented checksum channel
        intact), ``cap`` the Pallas kernel-sidecar capture."""
        fault = _faults.maybe_inject(_faults.CONTRACT_DISPATCH)
        runner = wrap(fn) if wrap is not None else fn
        cap = None
        with _dispatch_scope(op_class, sub.backend):
            if aplan is not None and aplan.augments:
                raw = runner(aplan.augment(sub))
            elif aplan is not None:
                with _abft.capture() as cap:
                    raw = runner(sub)
            else:
                raw = runner(sub)
        raw = _apply_data_fault(fault, raw)
        out = aplan.strip(raw) if aplan is not None and aplan.augments \
            else raw
        return out, raw, cap

    last_exc = None
    pending_nonfinite = False
    pending_mismatch = False
    for i, rung in enumerate(attempts):
        fn = lookup(rung, op_class, ger, fused)
        sub = op if rung == op.backend \
            else dataclasses.replace(op, backend=rung)
        nxt = attempts[i + 1] if i + 1 < len(attempts) else None
        try:
            out, raw, cap = attempt(fn, sub)
        except (_faults.InjectedFault,) + LOWERING_ERRORS as e:
            last_exc = e
            if nxt is None:
                raise
            _record_demotion(key, rung, nxt, f"{type(e).__name__}: {e}",
                             op_class, op.spec)
            _QUARANTINE[key] = nxt
            continue
        if not _output_finite(out):
            if nxt is None:
                # ref itself is non-finite: input-borne NaN, nobody's fault
                DISPATCH_COUNTS[(rung, op_class, ger.value)] += 1
                return out
            pending_nonfinite = True
            _record_demotion(key, rung, nxt, "non-finite output",
                             op_class, op.spec)
            continue
        if aplan is not None and not isinstance(out, jax.core.Tracer):
            ok, detail = aplan.check(raw, cap)
            if not ok:
                # Retry the SAME rung once: transient SDC (a one-shot
                # upset) clears; the retry re-consults the fault plan, so
                # max_fires-bounded injections clear exactly like the
                # hardware fault they stand in for.
                retried = None
                try:
                    retried = attempt(fn, sub)
                except (_faults.InjectedFault,) + LOWERING_ERRORS as e:
                    last_exc = e
                if retried is not None:
                    out2, raw2, cap2 = retried
                    if _output_finite(out2) \
                            and aplan.check(raw2, cap2)[0]:
                        _abft.record_verdict(
                            key=key, op_class=op_class, spec=op.spec,
                            rung=rung, recovered=True, how="retry",
                            detail=detail)
                        if rung != backend and (pending_nonfinite
                                                or pending_mismatch):
                            _QUARANTINE[key] = rung
                        DISPATCH_COUNTS[(rung, op_class,
                                         ger.value)] += 1
                        return out2
                if nxt is None:
                    # ground truth disagrees with its own checksums:
                    # return it, but tell the serving loop (it discards
                    # the step and requeues the slots).
                    _abft.record_verdict(
                        key=key, op_class=op_class, spec=op.spec,
                        rung=rung, recovered=False, how="exhausted",
                        detail=detail)
                    DISPATCH_COUNTS[(rung, op_class, ger.value)] += 1
                    return retried[0] if retried is not None else out
                pending_mismatch = True
                _record_demotion(key, rung, nxt, "checksum-mismatch",
                                 op_class, op.spec)
                continue
        if rung != backend and (pending_nonfinite or pending_mismatch):
            # data-borne demotions commit only on a clean lower rung
            _QUARANTINE[key] = rung
        if pending_mismatch:
            _abft.record_verdict(
                key=key, op_class=op_class, spec=op.spec, rung=rung,
                recovered=True, how="demote", detail=None)
        DISPATCH_COUNTS[(rung, op_class, ger.value)] += 1
        return out
    raise last_exc  # pragma: no cover — loop always returns or raises


# ----------------------------------------------------------------------
# Shard-aware dispatch: the mesh-native lowering path (DESIGN.md
# section 11).  When a mesh binding resolves (Plan.mesh or the ambient
# parallel.api rules), the pallas gemm/conv/attn lowerings run PER SHARD
# under one shard_map: output-disjoint labels (batch, M, N, heads, Sq)
# map onto mesh axes, every shard keeps the FULL contraction extent, and
# the block plan is resolved once at the global shape so each shard runs
# exactly the k-loop the single-device dispatch would — sharded output is
# bitwise-identical to single-device output (tests/test_sharding.py).
# The guarded ladder and ABFT wrap the shard_map from outside: demotion
# and checksum verdicts stay whole-dispatch decisions, with kernel-
# sidecar capture masked inside the trace (abft.suppress) so the passive
# global checksums carry verification.
# ----------------------------------------------------------------------

_SHARD_OPERANDS = ("x", "y", "z", "acc", "bias", "residual", "valid",
                   "w")


def _shard_rules(plan: Plan):
    """Resolve ``Plan.mesh`` to the active ShardingRules, or None when
    this dispatch stays single-device (no binding, ``mesh=False``, or a
    rules object with no mesh behind it)."""
    from repro.parallel import api as _par
    b = plan.mesh
    if b is False:
        return None
    if b is None:
        r = _par.current()
        return r if (r.enabled and r.mesh is not None) else None
    if isinstance(b, _par.ShardingRules):
        return b if b.mesh is not None else None
    return _par.default_rules(b)


def _ax_flat(ax) -> tuple:
    return ax if isinstance(ax, tuple) else (ax,)


@dataclasses.dataclass(frozen=True)
class _ShardPlan:
    """How one dispatch maps onto the mesh: per-operand PartitionSpecs in
    ``_SHARD_OPERANDS`` order, the output spec, the globally-resolved
    block override, and — for causal/window sequence-parallel attn — the
    mesh axes whose flattened index selects the static per-shard
    ``q_offset`` branch."""

    mesh: object
    in_specs: tuple
    out_spec: object
    block: tuple | None = None
    seq_axes: tuple = ()
    seq_parts: int = 1
    seq_local: int = 0


def _plan_gemm_shards(op: Op, rules) -> _ShardPlan | None:
    """Bind gemm labels to mesh axes: batch labels ride the data axes
    (any packed operand vetoes — batch labels live inside the tile
    stream), M rows take the data axes otherwise, N columns take the TP
    axis when the y side is natural.  Contraction labels are never
    sharded: every shard reduces the full K, which is what makes the
    sharded output bitwise-equal to the single-device one."""
    p = op.parsed
    sizes = _sizes(p, op.x, op.y)
    x_packed = _packing.is_packed(op.x)
    y_packed = _packing.is_packed(op.y)
    dp = rules.rules.get("batch")
    tp = rules.rules.get("mlp") or rules.rules.get("heads")
    assign: dict = {}
    used: list = []

    def bind(labels, ax, veto) -> bool:
        if ax is None or veto or not labels:
            return False
        e = rules.axis_extent(ax)
        if e <= 1 or any(a in used for a in _ax_flat(ax)):
            return False
        for d in labels:
            if sizes[d] % e == 0:
                assign[d] = ax
                used.extend(_ax_flat(ax))
                return True
        return False

    if not bind(p.batch, dp, x_packed or y_packed):
        bind(p.x_free, dp, x_packed)
    # bias is flat over the normalized N: its contiguous shard chunks
    # line up with output columns only when the OUTERMOST y_free label
    # is the sharded one.
    n_labels = p.y_free[:1] if op.bias is not None else p.y_free
    bind(n_labels, tp, y_packed)
    if not assign:
        return None

    if x_packed or y_packed or op.block is not None:
        # A pack's layout block (or the caller's explicit block) already
        # drives every shard identically.
        blk = op.block
    else:
        # Resolve at the GLOBAL shape: bitwise equality needs every
        # shard to run the single-device k-loop; bm/bn only group
        # independent output tiles (masked fringe absorbs bm > m_local).
        b, m, n, k = (_prod(sizes[d] for d in p.batch),
                      _prod(sizes[d] for d in p.x_free),
                      _prod(sizes[d] for d in p.y_free),
                      _prod(sizes[d] for d in p.contract))
        pack = 2 if op.pol.packed_int4 else 1
        blk = resolve_block(op.ger, m, n, k * pack, None,
                            op.epilogue.key, b=b if p.batch else 1)
        if blk is None:
            from repro.core import tiling as _tiling
            tcfg = _tiling.choose_blocks(m, n, k * pack, rep_kind(op.ger))
            blk = (tcfg.bm, tcfg.bn, tcfg.bk)

    def spec_for(labels, arr):
        if arr is None or _packing.is_packed(arr):
            return _P()
        return _P(*[assign.get(d) for d in labels])

    out_spec = _P(*[assign.get(d) for d in p.out_labels])
    bias_spec = _P(assign.get(p.y_free[0])) if op.bias is not None \
        else _P()
    return _ShardPlan(
        mesh=rules.mesh,
        in_specs=(spec_for(p.x_labels, op.x), spec_for(p.y_labels, op.y),
                  _P(), spec_for(p.out_labels, op.acc), bias_spec,
                  spec_for(p.out_labels, op.residual), _P()),
        out_spec=out_spec, block=blk)


def _plan_conv_shards(op: Op, rules) -> _ShardPlan | None:
    """Conv shards the image batch N over the data axes; filters and bias
    stay resident (replicated).  The filter-block resolution is
    N-independent, so per-shard lowering re-derives the global plan."""
    dp = rules.rules.get("batch")
    e = rules.axis_extent(dp)
    n = op.x.shape[0]
    if e <= 1 or n % e:
        return None
    img = _P(dp, *([None] * (op.x.ndim - 1)))
    rep = _P()
    return _ShardPlan(
        mesh=rules.mesh,
        in_specs=(img, rep, rep, rep, rep,
                  img if op.residual is not None else rep, rep),
        out_spec=img)


def _plan_attn_shards(op: Op, rules) -> _ShardPlan | None:
    """Attn shards B over the data axes and heads over TP — but only when
    BOTH q heads and kv heads divide (each shard keeps the full GQA
    group ratio, so the kernel's head-group-broadcast index maps are
    untouched); otherwise Sq goes sequence-parallel over the seq rules
    entry, with K/V resident.  Causal/window sequence shards record the
    mesh axes so dispatch can select each shard's static q_offset."""
    b, sq, h, d = op.x.shape
    kvh = op.y.shape[2]
    sk = op.y.shape[1]
    dp = rules.rules.get("batch")
    hp = rules.rules.get("heads")
    sqp = rules.rules.get("seq")
    q = [None, None, None, None]
    kv = [None, None, None, None]
    used: list = []
    seq_axes: tuple = ()
    seq_parts, seq_local = 1, 0

    def free(ax) -> bool:
        return (ax is not None and rules.axis_extent(ax) > 1
                and not any(a in used for a in _ax_flat(ax)))

    if free(dp) and b % rules.axis_extent(dp) == 0:
        q[0] = kv[0] = dp
        used.extend(_ax_flat(dp))
    if free(hp) and h % rules.axis_extent(hp) == 0 \
            and kvh % rules.axis_extent(hp) == 0:
        q[2] = hp
        kv[2] = hp
        used.extend(_ax_flat(hp))
    elif free(sqp) and sq % rules.axis_extent(sqp) == 0:
        e = rules.axis_extent(sqp)
        q[1] = sqp
        used.extend(_ax_flat(sqp))
        if op.causal or op.window is not None:
            seq_axes, seq_parts, seq_local = _ax_flat(sqp), e, sq // e
    if all(a is None for a in q):
        return None

    # The global (bq, bk) plan; a sequence shard takes the largest
    # divisor of its local Sq not above the global bq that the TPU tiling
    # takes (the kernel wants dividing query blocks; bk is untouched — it
    # shapes the KV stream every shard walks identically).  No such
    # divisor: the dispatch stays single-device.
    bq, bk = _attn_blocks(op, b * h, sq, sk, d)
    if q[1] is not None:
        loc = sq // rules.axis_extent(sqp)
        bq = next((c for c in range(min(bq, loc), 0, -1)
                   if loc % c == 0 and (c % 8 == 0 or c == loc)), None)
        if bq is None:
            return None
    valid_spec = _P()
    if (op.valid is not None and q[0] is not None
            and getattr(op.valid, "ndim", 0) == 2
            and op.valid.shape[0] == b):
        valid_spec = _P(dp, None)
    return _ShardPlan(
        mesh=rules.mesh,
        in_specs=(_P(*q), _P(*kv), _P(*kv), _P(), _P(),
                  _P(*q) if op.residual is not None else _P(), valid_spec),
        out_spec=_P(*q), block=(bq, bk),
        seq_axes=seq_axes, seq_parts=seq_parts, seq_local=seq_local)


def _plan_ssd_shards(op: Op, rules) -> _ShardPlan | None:
    """SSD shards the sequences over the data axes and heads over the
    ssm-heads axis, where a shard's heads still tile the kernel; every
    (sequence, chunk, head) is independent, so each shard's kernel
    computes exactly the single-device values.  None where no axis
    divides: the kernel then runs on the whole operands."""
    s, h = op.x.shape[0], op.z.shape[3]
    dp = rules.rules.get("batch")
    hp = rules.rules.get("ssm_heads")
    sa = (dp if dp is not None and rules.axis_extent(dp) > 1
          and s % rules.axis_extent(dp) == 0 else None)
    ha = (hp if hp is not None and rules.axis_extent(hp) > 1
          and h % rules.axis_extent(hp) == 0
          and not set(_ax_flat(hp)) & set(_ax_flat(sa or ()))
          and _ssd_fits(op, heads=h // rules.axis_extent(hp)) else None)
    if sa is None and ha is None:
        return None
    cb = _P(sa, None, None, None)
    xy = _P(sa, None, None, ha, None)
    return _ShardPlan(
        mesh=rules.mesh,
        in_specs=(cb, cb, xy, _P(), _P(), _P(), _P(),
                  _P(sa, None, ha, None)),
        out_spec=xy)


def _shard_plan(op: Op, op_class: str, rules) -> _ShardPlan | None:
    if op_class == "gemm":
        return _plan_gemm_shards(op, rules)
    if op_class == "conv":
        return _plan_conv_shards(op, rules)
    if op_class == "attn":
        return _plan_attn_shards(op, rules)
    if op_class == "ssd":
        return _plan_ssd_shards(op, rules)
    return None


def _shard_wrap(sp: _ShardPlan):
    """``fn -> per-shard fn``: the one shard_map of the mesh-native path.

    The body replaces the Op's array operands with their local shards and
    pins the globally-resolved block.  ABFT kernel-sidecar capture is
    masked inside the trace (abft.suppress — deposits of shard_map
    tracers must not escape it); verification falls back to the passive
    global checksums.  Causal/window sequence-parallel attn selects its
    static per-shard ``q_offset`` with a lax.switch over the flattened
    mesh-axis index: ``seq_parts`` statically-specialized branches, each
    with exactly its shard's causal grid bounds."""

    def wrap(fn):
        def run(sub: "Op"):
            _faults.maybe_inject(_faults.COLLECTIVE)
            keys, vals, specs = [], [], []
            for name, spec in zip(_SHARD_OPERANDS, sp.in_specs):
                v = getattr(sub, name)
                if v is None:
                    continue
                keys.append(name)
                vals.append(v)
                specs.append(spec)
            blk = sp.block if sp.block is not None else sub.block

            def body(*args):
                inner = dataclasses.replace(
                    sub, block=blk, **dict(zip(keys, args)))
                with _abft.suppress():
                    if sp.seq_parts > 1:
                        idx = lax.axis_index(sp.seq_axes[0])
                        for a in sp.seq_axes[1:]:
                            idx = idx * sp.mesh.shape[a] + lax.axis_index(a)
                        branches = [
                            functools.partial(
                                lambda o: fn(dataclasses.replace(
                                    inner, q_offset=o)),
                                sub.q_offset + i * sp.seq_local)
                            for i in range(sp.seq_parts)]
                        return lax.switch(idx, branches)
                    return fn(inner)

            return jax.shard_map(
                body, mesh=sp.mesh, in_specs=tuple(specs),
                out_specs=sp.out_spec, check_vma=False)(*vals)
        return run
    return wrap


# ----------------------------------------------------------------------
# Packed-operand admission: which operands may stay in their prepacked
# tile layout for this dispatch (core/packing.py owns the layouts; this
# layer only reads descriptor metadata and routes ineligible operands
# through the sanctioned packing demotion helpers)
# ----------------------------------------------------------------------

def _packed_gemm_compatible(parsed, v, side: str) -> bool:
    """A packed GEMM operand is admissible when the spec's normalization
    of that operand is exactly the relayout its pack already paid: single
    contract label, single free label on the packed side, at most one
    batch label, and a label order matching the layout's orientation."""
    lay = v.layout
    if getattr(lay, "tile", None) != "gemm" or lay.side != side:
        return False
    p = parsed
    if p is None or len(p.contract) != 1 or len(p.batch) > 1:
        return False
    free = p.x_free if side == "x" else p.y_free
    if len(free) != 1 or lay.batched != bool(p.batch):
        return False
    labels = p.x_labels if side == "x" else p.y_labels
    if side == "x":
        natural = p.batch + free + p.contract
        flipped = p.batch + p.contract + free
    else:
        natural = p.batch + p.contract + free
        flipped = p.batch + free + p.contract
    return labels == (flipped if lay.transposed else natural)


def _admit_packed(op_class: str, backend: str, ger: Ger, pol, parsed,
                  spec: str, x, y, masks):
    """Demote packed operands that cannot ride this dispatch packed.

    The packed fast path is the single-pass Pallas gemm/conv kernel;
    everything else — xla/ref backends, masked/saturating/complex/attn/
    einsum classes, expansion chains, int4 nibble kinds, incompatible
    spec orientations — demotes here, exactly once, through the
    sanctioned ``packing.demote_value``."""
    pallas_ok = (backend == "pallas" and not pol.packed_int4
                 and expansion_for(ger) is None)
    if op_class == "gemm" and pallas_ok and masks is None:
        if _packing.is_packed(x) and _packing.is_packed(y):
            # one packed operand per dispatch: keep the weight-side y
            x = _packing.demote_value(x, "both-operands-packed")
        if _packing.is_packed(x) and not _packed_gemm_compatible(
                parsed, x, "x"):
            x = _packing.demote_value(x, "spec-orientation")
        if _packing.is_packed(y) and not _packed_gemm_compatible(
                parsed, y, "y"):
            y = _packing.demote_value(y, "spec-orientation")
        return x, y
    if op_class == "conv" and pallas_ok:
        if _packing.is_packed(x):
            x = _packing.demote_value(x, "conv-image-operand")
        if _packing.is_packed(y):
            nd, depthwise = _CONV_SPECS[spec]
            lay = y.layout
            if (depthwise or getattr(lay, "tile", None) != "conv"
                    or lay.nd != nd):
                y = _packing.demote_value(y, "conv-layout-mismatch")
        return x, y
    return (_packing.demote_value(x, op_class),
            _packing.demote_value(y, op_class))


# ----------------------------------------------------------------------
# The driver
# ----------------------------------------------------------------------

def execute(spec: str, x, y, z=None, w=None, *, cfg,
            plan: Plan | None = None,
            acc=None, bias=None, residual=None,
            dequant: Dequant | None = None, masks=None):
    """Resolve ``plan`` against ``cfg``, pick a lowering, run it.

    This is the body of ``facility.contract`` — kept here so the facility
    module stays the thin architected surface.  ``masks`` = the pm*
    prefixed-form predicates ``(xmask, ymask, pmask)`` on the normalized
    M/N/K axes (each entry optional) — routes to the ``gemm.masked``
    op-class, where the Pallas lowering applies them to the streamed
    panels in-kernel instead of pre-masking operands in HBM.  ``z`` is the
    value operand of the canonical ``ATTN`` spec (a three-operand
    builtin); for attn, ``masks`` is the 1-tuple ``(valid,)`` KV-slot
    predicate.  The canonical ``SSD`` spec takes four operands: C, B, x as
    ``z`` and the cumulative log-decays as ``w``.
    """
    from repro.kernels import epilogue as _epilogue

    plan = plan or Plan()
    ger = plan.ger or cfg.ger
    pol = precision.policy(ger)
    if isinstance(plan.out_dtype, str) and plan.out_dtype == ACC:
        out_dtype = pol.acc_dtype
    else:
        out_dtype = plan.out_dtype or cfg.out_dtype
    # Unset FacilityConfig fields follow the platform (the one place they
    # resolve): compiled Pallas kernels on a TPU, XLA elsewhere, and Pallas
    # interpret mode only on the CPU.  Plan fields override both.
    platform = jax.default_backend()
    use_pallas = (platform == "tpu" if cfg.use_pallas is None
                  else cfg.use_pallas)
    backend = plan.backend or ("pallas" if use_pallas else "xla")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; have {BACKENDS}")
    interpret = plan.interpret
    if interpret is None:
        interpret = (platform == "cpu" if cfg.interpret is None
                     else cfg.interpret)

    ep = plan.epilogue
    if ep is None:
        ep = _epilogue.make(bias=bias, residual=residual)
    ep.validate(pol.acc_dtype, bias=bias, residual=residual)

    spec = spec.replace(" ", "")
    conv_info = _CONV_SPECS.get(spec)
    stride: tuple[int, ...] = ()
    parsed = None
    valid = None
    if z is not None and spec not in (ATTN, SSD):
        raise ValueError(
            f"a third operand is attn-spec vocabulary (facility.ATTN) "
            f"or ssd-spec vocabulary (facility.SSD), not {spec!r}")
    if w is not None and spec != SSD:
        raise ValueError(
            f"a fourth operand is ssd-spec vocabulary (facility.SSD), "
            f"not {spec!r}")
    if spec == SSD:
        op_class = "ssd"
        if z is None or w is None:
            raise ValueError(
                f"the ssd spec {spec!r} is a four-operand contraction: "
                f"contract(facility.SSD, C, B, x, cum)")
        s, nc, l, n = jnp.shape(x)
        if (jnp.ndim(z) != 5 or jnp.shape(y) != (s, nc, l, n)
                or jnp.shape(z)[:3] != (s, nc, l)
                or jnp.shape(w) != (s, nc, jnp.shape(z)[3], l)):
            raise ValueError(
                f"ssd wants C, B (S, NC, L, N), x (S, NC, L, H, P) and cum "
                f"(S, NC, H, L); got {jnp.shape(x)}, {jnp.shape(y)}, "
                f"{jnp.shape(z)}, {jnp.shape(w)}")
        if (acc is not None or dequant is not None or masks is not None
                or plan.saturating
                or plan.neg_product or plan.neg_acc or plan.alpha != 1.0
                or plan.beta != 1.0 or not ep.is_identity
                or plan.block is not None):
            raise ValueError(
                "ssd contractions take no accumulator seed, dequant, "
                "masks, epilogue, block, saturating or accumulate forms — "
                "only the ger family and the out dtype")
    elif spec == ATTN:
        op_class = "attn"
        if z is None:
            raise ValueError(
                f"the attn spec {spec!r} is a three-operand contraction: "
                f"contract(facility.ATTN, q, k, v, ...)")
        if jnp.ndim(x) != 4 or jnp.ndim(y) != 4 or jnp.shape(y) != \
                jnp.shape(z):
            raise ValueError(
                f"attn wants q (B, Sq, H, D) and k == v shapes "
                f"(B, Sk, KVH, D); got {jnp.shape(x)} x {jnp.shape(y)} x "
                f"{jnp.shape(z)}")
        b, sq, h, d = jnp.shape(x)
        bk_, sk, kvh, dk_ = jnp.shape(y)
        if bk_ != b or dk_ != d or h % kvh:
            raise ValueError(
                f"attn batch/head/depth mismatch: q {jnp.shape(x)} vs "
                f"k/v {jnp.shape(y)} (H must be a multiple of KVH)")
        if ger not in _ATTN_GERS:
            raise ValueError(
                f"attn lowers float families with f32 accumulators only "
                f"({[g.value for g in _ATTN_GERS]}), not {ger.value}")
        if (acc is not None or dequant is not None or plan.saturating
                or plan.neg_product or plan.neg_acc
                or plan.alpha != 1.0 or plan.beta != 1.0):
            raise ValueError(
                "attn contractions take no accumulator seed, dequant, "
                "saturating, or alpha/beta/neg accumulate forms — only a "
                "fused epilogue and the causal/window/q_offset/valid "
                "predicates")
        if plan.block is not None and len(plan.block) != 2:
            raise ValueError(
                f"attn blocks are (bq, bk); got {plan.block!r}")
        if plan.window is not None and plan.window < 1:
            raise ValueError(f"window must be >= 1, got {plan.window!r}")
        if masks is not None:
            if len(masks) != 1:
                raise ValueError(
                    "attn masks is the 1-tuple (valid,) — the (B, Sk) "
                    f"filled-KV-slot predicate — got {len(masks)} entries")
            valid = masks[0]
            if valid is not None:
                vshape = jnp.shape(valid)
                if vshape not in ((sk,), (1, sk), (b, sk)):
                    raise ValueError(
                        f"attn valid mask has shape {vshape}; want "
                        f"({sk},) or ({b}, {sk})")
            masks = None
    elif conv_info is not None:
        nd, _ = conv_info
        op_class = "conv"
        s = plan.stride
        stride = (s,) * nd if isinstance(s, int) else tuple(s)
        if len(stride) != nd or any(st < 1 for st in stride):
            raise ValueError(f"conv spec {spec!r} wants {nd} stride "
                             f"value(s) >= 1, got {plan.stride!r}")
        if (acc is not None or dequant is not None or plan.saturating
                or plan.neg_product or plan.neg_acc
                or plan.alpha != 1.0 or plan.beta != 1.0):
            raise ValueError(
                "conv contractions take no accumulator seed, dequant, "
                "saturating, or alpha/beta/neg accumulate forms — only a "
                "fused epilogue")
    elif jnp.iscomplexobj(x) or jnp.iscomplexobj(y):
        op_class = "complex"
        parsed = parse_spec(spec, jnp.ndim(x), jnp.ndim(y))
        if parsed is None or parsed.out_perm is not None:
            raise ValueError(
                f"complex contraction {spec!r} must normalize to a "
                f"(batched) GEMM in natural output order")
        if dequant is not None or plan.saturating or not ep.is_identity:
            raise ValueError(
                "complex contractions take accumulate forms only — no "
                "fused epilogue, dequant, or saturating updates")
    else:
        parsed = parse_spec(spec, jnp.ndim(x), jnp.ndim(y))
        if parsed is not None and _ellipsis_broadcasts(parsed, x, y):
            parsed = None
        op_class = "gemm.saturating" if plan.saturating else (
            "gemm" if parsed is not None else "einsum")
    if masks is not None:
        if len(masks) != 3:
            raise ValueError(
                f"masks wants the 3-tuple (xmask, ymask, pmask) — entries "
                f"may be None — got {len(masks)} entries")
        if op_class != "gemm":
            raise ValueError(
                f"masks (pm* prefixed forms) require a gemm-class "
                f"contraction, not {op_class!r} ({spec!r})")
        if not parsed.is_natural_gemm:
            raise ValueError(
                f"masked contraction {spec!r} must already be in the "
                f"normalized (batch..., M, K) x (batch..., K, N) layout "
                f"so the (M,), (N,), (K,) predicates name unique axes")
        if dequant is not None:
            raise ValueError("masks and dequant are exclusive")
        if pol.packed_int4:
            raise ValueError(
                "packed-int4 masked forms lower through the ref.pm_ger "
                "oracle (ops.mma_pm_dot keeps that path)")
        sizes = _sizes(parsed, x, y)
        want = {0: sizes[parsed.x_free[0]], 1: sizes[parsed.y_free[0]],
                2: sizes[parsed.contract[0]]}
        for i, mask in enumerate(masks):
            if mask is not None and jnp.shape(mask) != (want[i],):
                raise ValueError(
                    f"mask {i} has shape {jnp.shape(mask)}; want "
                    f"({want[i]},) for spec {spec!r}")
        op_class = "gemm.masked"
    if op_class != "conv" and (plan.stride != 1 or plan.padding != "valid"):
        raise ValueError(
            f"stride/padding apply to the conv specs only, not {spec!r}")
    if op_class != "attn" and (plan.causal or plan.window is not None
                               or plan.q_offset or plan.q_chunk):
        raise ValueError(
            f"causal/window/q_offset/q_chunk apply to the attn spec only, "
            f"not {spec!r}")
    if dequant is not None and not ep.is_identity:
        raise ValueError("dequant and a fused epilogue are exclusive")
    if (parsed is not None and parsed.out_perm is not None
            and (acc is not None or not ep.is_identity)):
        raise ValueError(
            f"spec {spec!r} permutes the natural output order; accumulator "
            f"inputs and fused epilogues require the natural "
            f"(batch..., m..., n...) output")
    if plan.saturating and (not ep.is_identity or plan.neg_product
                            or plan.neg_acc or plan.alpha != 1.0
                            or plan.beta != 1.0 or dequant is not None):
        raise ValueError(
            "saturating forms take an accumulator seed only — no fused "
            "epilogue, dequant, or alpha/beta/neg accumulate forms "
            "(xvi16ger2s-class instructions have no such variants)")

    if (op_class == "conv" and backend == "pallas"
            and pol.acc_dtype != jnp.float32):
        # The conv kernels accumulate in f32 only: route non-f32 families
        # to the shardable XLA lowering BEFORE counting, so
        # DISPATCH_COUNTS names the backend that actually ran
        # (gemm.saturating precedent).  Depthwise no longer reroutes: it
        # runs the resident-accumulator VPU kernel (mma_conv).
        backend = "xla"
    if (backend == "pallas" and not interpret
            and rep_kind(ger) not in _COMPILED_GERS):
        # Dtype rule: the TPU compiler refuses the kernels' f64, f16 and
        # integer families (v5e), so compiled dispatches of those take the
        # XLA lowering; interpret mode runs every family.
        backend = "xla"

    fn = lookup(backend, op_class, ger, not ep.is_identity)
    if fn is None and backend == "pallas":
        # e.g. saturating forms (no MXU analogue) or general einsum specs:
        # fall back to the shardable XLA lowering.
        backend = "xla"
        fn = lookup(backend, op_class, ger, not ep.is_identity)
    if fn is None:
        raise NotImplementedError(
            f"no lowering registered for ({backend!r}, {op_class!r}, "
            f"{ger}, fused={not ep.is_identity})")

    x, y = _admit_packed(op_class, backend, ger, pol, parsed, spec,
                         x, y, masks)
    # acc/bias/residual/z are never packed operands; unwrap defensively so
    # a mis-routed descriptor degrades to natural layout instead of
    # crashing a lowering.
    z = _packing.demote_value(z, "attn-value") if _packing.is_packed(z) \
        else z
    acc = _packing.demote_value(acc, "acc-seed") if _packing.is_packed(acc) \
        else acc

    lowering_out_dtype = None if dequant is not None else out_dtype
    op = Op(x=x, y=y, acc=acc, bias=bias, residual=residual, parsed=parsed,
            spec=spec, ger=ger, pol=pol, out_dtype=lowering_out_dtype,
            epilogue=ep, block=plan.block, interpret=interpret,
            neg_product=plan.neg_product, neg_acc=plan.neg_acc,
            alpha=plan.alpha, beta=plan.beta, backend=backend,
            stride=stride, padding=plan.padding, masks=masks,
            z=z, valid=valid, causal=plan.causal, window=plan.window,
            q_offset=plan.q_offset, q_chunk=plan.q_chunk, w=w)
    if backend == "pallas" and (
            (op_class == "attn" and not interpret and not _attn_tiles_fit(op))
            or (op_class == "ssd" and not _ssd_fits(op))):
        # Shape rules: attn block plans the TPU tiling refuses (see
        # _attn_tiles_fit), and ssd shapes the kernel does not tile, take
        # the XLA lowering, before counting.
        backend = "xla"
        fn = lookup(backend, op_class, ger, not ep.is_identity)
        op = dataclasses.replace(op, backend=backend)
        SHAPE_RULE_FALLBACKS[(op_class, ger.value)] += 1
    wrap = None
    if backend == "pallas" and op_class in ("gemm", "conv", "attn", "ssd"):
        srules = _shard_rules(plan)
        if srules is not None:
            sp = _shard_plan(op, op_class, srules)
            if sp is not None:
                wrap = _shard_wrap(sp)
    if getattr(cfg, "guards", False):
        out = _guarded_dispatch(op, op_class, backend, ger,
                                not ep.is_identity,
                                abft_on=getattr(cfg, "abft", False),
                                wrap=wrap)
    else:
        # The unguarded fast path: with no fault plan installed this is
        # ONE contextvar read away from `fn(op)` — bitwise-identical
        # output (tests/test_guards.py::test_guards_off_bitwise_unchanged).
        DISPATCH_COUNTS[(backend, op_class, ger.value)] += 1
        fault = _faults.maybe_inject(_faults.CONTRACT_DISPATCH)
        with _dispatch_scope(op_class, backend):
            out = wrap(fn)(op) if wrap is not None else fn(op)
        out = _apply_data_fault(fault, out)
    if dequant is not None:
        out = dequant.apply(out)
        out = out.astype(out_dtype) if out_dtype is not None else out
    return out


def deprecated_shim(old: str, replacement: str):
    """Emit the facility-migration DeprecationWarning for a legacy entry
    point.  stacklevel=3 attributes the warning to the shim's *caller*, so
    the tier-1 filter (tests/conftest.py) escalates in-repo callers to
    errors while external/test callers only see the warning."""
    warnings.warn(
        f"{old} is deprecated; use facility.contract — e.g. {replacement}",
        DeprecationWarning, stacklevel=3)
