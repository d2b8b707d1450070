"""The invariant catalog: every rule ``repro.analysis`` enforces.

Each rule names a *contract* an earlier PR introduced and ``scripts/ci.sh``
used to "enforce" with a grep block.  Greps string-match source, so they
miss aliased imports (``from jax.numpy import dot``), method-call forms
(``x.dot(y)``), the ``@`` operator, and everything semantic; the AST rules
here resolve imports first and match *meaning*, and the jaxpr rules
(``jaxpr_check``) go one level further and inspect the traced program.

Registering a new rule (the workflow a future contract-introducing PR
follows — DESIGN.md section 10):

  1. Add a :class:`Rule` entry to :data:`RULES` (id, what it protects,
     which PR introduced the contract).
  2. Implement the check in ``astcheck.Checker`` (AST) or
     ``jaxpr_check`` (traced invariants) and emit findings with the
     rule id.
  3. Add a known-bad fixture to ``tests/test_analysis.py`` proving the
     rule fires, and keep the clean-tree assertion green.

Suppression: a finding is silenced by ``# repro: allow(<rule-id>)`` on the
flagged line or the line directly above it (comma-separate several ids).
Suppressions are for sites where the contract is *intentionally* crossed —
deprecated shims, architected dtype decodes — and the comment should say
why.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    summary: str          # what the rule protects
    contract_pr: str      # which PR introduced the contract it guards


RULES: dict[str, Rule] = {r.id: r for r in [
    Rule("facility-purity",
         "facility.contract is the only sanctioned route to matrix "
         "contractions: any spelling of dot/einsum/matmul/tensordot/vdot "
         "(module call through any alias, from-import, x.dot(y) method "
         "call, or the @ operator) is confined to the facility's own "
         "lowering layer and the architected oracles",
         "PR 2"),
    Rule("lax-purity",
         "raw lax.dot_general / lax.conv_general_dilated belong to the "
         "lowering layer and the kernels only — models and everything "
         "above route contraction and conv work through "
         "facility.contract's op-classes",
         "PR 3"),
    Rule("grid-owns-batch",
         "batched contractions fold the batch axis into the Pallas grid; "
         "kernel dispatch in core/lowering.py never wraps a kernel in "
         "jax.vmap / vectorize (one pallas_call per contraction)",
         "PR 4"),
    Rule("attn-op-class",
         "attention and the SSD chunk scan are registry op-classes: "
         "models dispatch through facility.contract(facility.ATTN, ...) "
         "and facility.contract(facility.SSD, ...) and never import "
         "kernels.mma_attention or kernels.mma_ssd directly",
         "PR 5"),
    Rule("pack-once",
         "layout changes are paid once at pack time (core/packing.py): "
         "no raw unpack*/pack_* in the lowering dispatch path and no "
         "per-call operand transpose/swapaxes/moveaxis inside the "
         "GEMM/conv kernels",
         "PR 7"),
    Rule("layer-stratification",
         "the model-to-kernel spine is a strict layer DAG "
         "(models -> facility -> lowering -> kernels): no upward imports "
         "and no layer-skipping imports within the mapped strata",
         "PR 8"),
    Rule("deprecated-shim",
         "the deprecated pre-contract entry points (fdot, mma_dot, "
         "flash_attention, ...) are for external callers only; in-repo "
         "code outside the defining module calls facility.contract",
         "PR 2"),
    Rule("mutable-default-arg",
         "no mutable default arguments (lists/dicts/sets or constructor "
         "calls evaluated once at def time) — the cfg=ElasticConfig() "
         "class of bug PR 6 fixed once",
         "PR 6"),
    Rule("fault-point-literal",
         "string literals handed to faults.fire / faults.maybe_inject "
         "name registered injection points (members of faults.POINTS) — "
         "a typo'd point validates nowhere and silently never fires",
         "PR 9"),
    Rule("collective-purity",
         "raw collectives (shard_map, with_sharding_constraint, "
         "lax.ppermute, lax.all_to_all) are the mesh-native dispatch "
         "surface's own vocabulary — parallel/api, core/lowering, and "
         "runtime/pipeline only; models annotate with parallel.api.shard "
         "and contracts shard through facility.contract's mesh binding",
         "PR 10"),
    Rule("overbroad-except",
         "no bare `except:` / `except Exception:` / `except "
         "BaseException:` — failure handling catches the narrow "
         "LOWERING_ERRORS set (or narrower) so programming errors "
         "surface instead of demoting",
         "PR 6"),
    # ---- jaxpr-level rules (jaxpr_check.py) --------------------------
    Rule("jaxpr-acc-dtype",
         "accumulator-dtype discipline: every dot_general a registered "
         "lowering traces to carries the ger policy's accumulator dtype "
         "as preferred_element_type (or already computes in it)",
         "PR 2"),
    Rule("jaxpr-zero-relayout",
         "a PackedOperand input reaches its pallas_call untouched: no "
         "transpose/gather equations between the packed panels and the "
         "kernel launch",
         "PR 7"),
    Rule("jaxpr-no-premask",
         "masked forms stream their predicates into the kernel; no "
         "select_n equation feeds a pallas_call operand (operands are "
         "never pre-masked in HBM)",
         "PR 4"),
    Rule("jaxpr-vmem-budget",
         "every autotune candidate block config's BlockSpec-implied VMEM "
         "residency (accumulator scratch + double-buffered panels + "
         "output tile) fits the budget before anything is compiled",
         "PR 1"),
]}


# ----------------------------------------------------------------------
# Rule configuration (the data the checks consume)
# ----------------------------------------------------------------------

# facility-purity: contraction spellings at the jnp/numpy level, and the
# repo modules sanctioned to use them (the facility's own lowering layer
# plus the architected oracles).  Method-call forms and the ``@`` operator
# are matched structurally in astcheck.
CONTRACTION_FNS = frozenset({"dot", "einsum", "matmul", "tensordot",
                             "vdot"})
CONTRACTION_MODULES = ("jax.numpy", "numpy")
PURITY_SANCTIONED = frozenset({
    "repro.core.facility",
    "repro.core.lowering",
    "repro.core.abft",          # checksum oracles (reference sums)
    "repro.kernels.ref",
})

# lax-purity: one layer down — additionally sanctioned in the kernels.
LAX_CONTRACTION_FNS = frozenset({"dot", "dot_general",
                                 "conv_general_dilated"})
LAX_SANCTIONED_PREFIXES = ("repro.core.lowering", "repro.kernels")

# grid-owns-batch: modules whose kernel dispatch must never vmap.
GRID_OWNS_BATCH_MODULES = frozenset({"repro.core.lowering"})
VMAP_NAMES = frozenset({"jax.vmap", "jax.numpy.vectorize",
                        "numpy.vectorize"})

# attn-op-class: modules forbidden to import the op-class kernel modules,
# each mapped to the facility spec that dispatches it.
ATTN_FORBIDDEN_PREFIX = "repro.models"
OP_CLASS_KERNEL_MODULES = {"repro.kernels.mma_attention": "facility.ATTN",
                           "repro.kernels.mma_ssd": "facility.SSD"}

# pack-once: the dispatch hot path (lowering) must not unpack/pack or
# swapaxes operands per call; the GEMM/conv kernels must not transpose
# operands at all (layout is paid once, at pack time).
PACK_ONCE_LOWERING = frozenset({"repro.core.lowering"})
PACK_ONCE_KERNELS = frozenset({"repro.kernels.mma_gemm",
                               "repro.kernels.mma_conv"})
RELAYOUT_FNS = frozenset({"transpose", "swapaxes", "moveaxis"})

# layer-stratification: the model-to-kernel spine.  Longest-prefix match;
# modules not mapped (configs, launch, runtime, optim, roofline, the
# core substrate precision/tiling/packing/autotune/quant, ...) sit outside
# the DAG and are unconstrained.  ops and blas3 live under kernels/ for
# legacy API reasons but are facility *clients* (deprecated shims / thin
# plans over contract), so they map to the client stratum.
STRATA: dict[str, int] = {
    "repro.models": 3,
    "repro.kernels.ops": 3,        # deprecated shims over contract
    "repro.kernels.blas3": 3,      # thin plans over contract
    "repro.core.facility": 2,
    "repro.core.lowering": 1,
    "repro.kernels": 0,
}
STRATUM_NAMES = {3: "clients/models", 2: "facility", 1: "lowering",
                 0: "kernels"}

# deprecated-shim: defining module -> shim names.  Calling (or importing)
# one of these outside its defining module is a finding.
DEPRECATED_SHIMS: dict[str, frozenset] = {
    "repro.core.facility": frozenset({"fdot", "fdot_fused", "feinsum"}),
    "repro.kernels.ops": frozenset({"mma_dot", "mma_dot_fused",
                                    "mma_conv2d", "mma_pm_dot"}),
    "repro.kernels.mma_attention": frozenset({"flash_attention"}),
}

# collective-purity: the raw collective spellings (resolved through the
# alias table, so `from jax import shard_map` and `lax.ppermute` both
# match) and the three modules that ARE the mesh-native dispatch surface.
COLLECTIVE_FNS = frozenset({
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.lax.with_sharding_constraint",
    "jax.lax.ppermute",
    "jax.lax.all_to_all",
})
COLLECTIVE_SANCTIONED = frozenset({
    "repro.parallel.api",
    "repro.core.lowering",
    "repro.runtime.pipeline",
})

# mutable-default-arg: call-expression defaults that are immutable and
# therefore safe to evaluate once at def time.
IMMUTABLE_DEFAULT_CTORS = frozenset({"tuple", "frozenset", "object"})

# overbroad-except: exception names that catch too much.
OVERBROAD_EXCEPTIONS = frozenset({"Exception", "BaseException"})

# fault-point-literal: the injection hooks and the registered points.
# POINTS is imported from the registry itself so the rule can never drift
# from the runtime (a point added there is instantly legal here).
from repro.runtime import faults as _faults  # noqa: E402  (config import)

FAULT_MODULE = "repro.runtime.faults"
FAULT_HOOKS = frozenset({"fire", "maybe_inject"})
FAULT_POINTS = frozenset(_faults.POINTS)


def stratum_of(module: str) -> int | None:
    """Longest-prefix stratum lookup; None = outside the mapped DAG."""
    best, rank = -1, None
    for prefix, r in STRATA.items():
        if module == prefix or module.startswith(prefix + "."):
            if len(prefix) > best:
                best, rank = len(prefix), r
    return rank
