"""Jaxpr contract auditor: traces registered lowerings and checks the
semantic invariants no source lint can see.

The AST pass proves callers *route through* ``facility.contract``; this
pass proves the registered lowerings *keep the facility's promises* once
traced.  For every (op-class, ger, backend) cell of the audit matrix it
builds a small representative contract, traces it with ``jax.make_jaxpr``
(Pallas in interpret mode — the kernel jaxpr rides in the ``pallas_call``
eqn params, nothing executes), and audits the equations:

- ``jaxpr-acc-dtype``: every ``dot_general`` carries the ger policy's
  accumulator dtype as ``preferred_element_type`` (or already computes in
  it — the conv op-class's XLA lowering accumulates into an f32 output).
- ``jaxpr-zero-relayout``: a :class:`PackedOperand`'s panels flow from
  the trace input to the ``pallas_call`` with no transpose/gather/rev on
  the way — the layout was paid once, at pack time.
- ``jaxpr-no-premask``: no ``select_n`` result feeds a ``pallas_call``
  operand — predicates stream into the kernel; HBM operands are never
  pre-masked.
- ``jaxpr-vmem-budget``: every autotune candidate's full BlockSpec
  residency (working set + out tile) fits physical VMEM before anything
  is compiled, and the SSD kernel's shape rule, VMEM residency included,
  admits every registered SSM architecture's chunk.

Taint flow maps positionally through ``jit`` boundaries (``contract``
jits internally) and stops at ``pallas_call``: in-kernel ``select_n`` on
the VMEM-resident panels is exactly the architected masking, so the
kernel body is the sink, not part of the searched graph.  Backends whose
lowering is host-side numpy (the ref saturating oracle) do not trace;
those cells are reported as skips, not findings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.astcheck import Finding
from repro.core import autotune, facility, lowering, packing, precision
from repro.core import tiling
from repro.core.precision import Ger

RELAYOUT_PRIMS = frozenset({"transpose", "gather", "rev"})
MASK_PRIMS = frozenset({"select_n"})

# Representative gers per op-class: one cell per accumulator family the
# class supports (f32 acc, int32 acc, the 3xBF16 expansion, packed int4).
AUDIT_GERS = {
    "gemm": (Ger.BF16GER2, Ger.F32GER, Ger.I8GER4, Ger.F32GER_3XBF16),
    "gemm.masked": (Ger.F32GER, Ger.I8GER4),
    "gemm.saturating": (Ger.I16GER2,),
    "conv": (Ger.F32GER,),
    "attn": (Ger.BF16GER2,),
    "ssd": (Ger.BF16GER2,),
}


def _is_var(v) -> bool:
    return not hasattr(v, "val")


def _sub_jaxprs(eqn):
    """Every Jaxpr hiding in an eqn's params (pallas_call kernel, scan
    body, jit computation, ...)."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else [v]):
            if hasattr(sub, "jaxpr"):
                sub = sub.jaxpr
            if hasattr(sub, "eqns"):
                yield sub


def iter_eqns(jaxpr):
    """All equations, recursing into every sub-jaxpr (kernels included)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


# ----------------------------------------------------------------------
# Invariant checks (pure jaxpr -> findings; the tests drive these with
# deliberately broken traces)
# ----------------------------------------------------------------------

def check_acc_dtype(jaxpr, acc_dtype, where: str) -> list[Finding]:
    """Every contraction eqn must accumulate in ``acc_dtype``."""
    acc = jnp.dtype(acc_dtype)
    out = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in ("dot_general", "conv_general_dilated"):
            continue
        pref = eqn.params.get("preferred_element_type")
        out_dtype = eqn.outvars[0].aval.dtype
        if (pref is None or jnp.dtype(pref) != acc) \
                and jnp.dtype(out_dtype) != acc:
            out.append(Finding(
                "jaxpr-acc-dtype", where, 0,
                f"{name} accumulates in "
                f"{pref if pref is not None else out_dtype}, policy says "
                f"{acc.name} (preferred_element_type missing or wrong)"))
    return out


def _flow(jaxpr, taint: set, *, source_prims: frozenset,
          flag_prims: frozenset, flag_at_sink: bool,
          hits: list) -> set:
    """Propagate taint through a jaxpr; returns tainted outvars.

    ``pallas_call`` is the sink: tainted operands reaching it are a hit
    iff ``flag_at_sink`` (the premask check), and its kernel body is
    never entered.  ``jit`` recurses with positional invar mapping
    (``contract`` jits internally); other sub-jaxpr eqns (scan, cond)
    conservatively taint all outputs when any input is tainted.
    """
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        tainted_in = any(_is_var(v) and v in taint for v in eqn.invars)
        if name == "pallas_call":
            if tainted_in and flag_at_sink:
                hits.append(name)
            continue
        if name == "jit":
            sub = eqn.params["jaxpr"].jaxpr
            sub_taint = {sv for v, sv in zip(eqn.invars, sub.invars)
                         if _is_var(v) and v in taint}
            out_taint = _flow(sub, sub_taint, source_prims=source_prims,
                              flag_prims=flag_prims,
                              flag_at_sink=flag_at_sink, hits=hits)
            for ov, sov in zip(eqn.outvars, sub.outvars):
                if _is_var(sov) and sov in out_taint:
                    taint.add(ov)
            continue
        if name in source_prims:
            taint.update(eqn.outvars)
            continue
        if tainted_in:
            if name in flag_prims:
                hits.append(name)
            taint.update(eqn.outvars)
    return {v for v in jaxpr.outvars if _is_var(v) and v in taint}


def check_zero_relayout(closed, packed_argnums, where: str
                        ) -> list[Finding]:
    """No transpose/gather/rev between packed invars and the kernel."""
    jaxpr = closed.jaxpr
    taint = {v for i, v in enumerate(jaxpr.invars) if i in packed_argnums}
    hits: list = []
    _flow(jaxpr, taint, source_prims=frozenset(),
          flag_prims=RELAYOUT_PRIMS, flag_at_sink=False, hits=hits)
    return [Finding("jaxpr-zero-relayout", where, 0,
                    f"`{h}` applied to a PackedOperand's panels between "
                    "the trace input and the pallas_call — layout must "
                    "be paid once, at pack time") for h in hits]


def check_no_premask(closed, where: str) -> list[Finding]:
    """No select_n result may feed a pallas_call operand."""
    hits: list = []
    _flow(closed.jaxpr, set(), source_prims=MASK_PRIMS,
          flag_prims=frozenset(), flag_at_sink=True, hits=hits)
    return [Finding("jaxpr-no-premask", where, 0,
                    "a select_n (pre-masked operand) feeds a pallas_call "
                    "— predicates must stream into the kernel instead")
            for _ in hits]


def check_vmem_candidates(cfgs, pol, where: str,
                          limit: int = tiling.VMEM_BYTES
                          ) -> list[Finding]:
    """Every candidate's BlockSpec-implied residency fits VMEM."""
    out = []
    for cfg in cfgs:
        used = cfg.residency_bytes(pol)
        if used > limit:
            out.append(Finding(
                "jaxpr-vmem-budget", where, 0,
                f"candidate {cfg} implies {used} B VMEM residency > "
                f"{limit} B — must be rejected before compilation"))
    return out


def check_ssd_vmem(where: str) -> list[Finding]:
    """The SSD kernel's shape rule (``lowering.ssd_shape_fits``: tiles,
    lanes and VMEM residency) holds, with bf16 C, B, x and y, at the
    chunk, state, heads per B/C group and head width of every registered
    SSM architecture: where it fails, dispatch would leave the
    architecture on the XLA chain."""
    from repro import configs
    bf16 = jnp.bfloat16
    out = []
    for name in configs.ARCHS:
        cfg = configs.get(name)
        if not cfg.ssm_state:
            continue
        heads = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
        h = heads // cfg.ssm_ngroups
        if not lowering.ssd_shape_fits(
                cfg.ssm_chunk, cfg.ssm_state, h, cfg.ssm_headdim,
                ger=Ger.BF16GER2, c_dtype=bf16, x_dtype=bf16,
                out_dtype=bf16):
            out.append(Finding(
                "jaxpr-vmem-budget", where, 0,
                f"{name}: the SSD kernel does not take chunk "
                f"{cfg.ssm_chunk}, state {cfg.ssm_state}, {h} heads of "
                f"{cfg.ssm_headdim} within {tiling.VMEM_BUDGET} B VMEM"))
    return out


# ----------------------------------------------------------------------
# The audit driver: build the matrix from the registry, trace each cell
# ----------------------------------------------------------------------

def _operands(op_class, ger, rng):
    """Small representative operands per op-class (trace-only sizes)."""
    f32 = jnp.float32
    if op_class == "attn":
        q = jnp.asarray(rng.normal(size=(1, 16, 2, 16)), f32)
        return (q, jnp.asarray(rng.normal(size=(1, 16, 2, 16)), f32),
                jnp.asarray(rng.normal(size=(1, 16, 2, 16)), f32))
    if op_class == "conv":
        return (jnp.asarray(rng.normal(size=(1, 8, 8, 4)), f32),
                jnp.asarray(rng.normal(size=(3, 3, 4, 8)), f32))
    if op_class == "ssd":
        # one 128-row chunk (the kernel's row tile) of 8 heads of 16
        cum = jnp.cumsum(-jnp.asarray(rng.random((1, 1, 8, 128)), f32), -1)
        return (jnp.asarray(rng.normal(size=(1, 1, 128, 16)), f32),
                jnp.asarray(rng.normal(size=(1, 1, 128, 16)), f32),
                jnp.asarray(rng.normal(size=(1, 1, 128, 8, 16)), f32), cum)
    x = jnp.asarray(rng.normal(size=(16, 64)), f32)
    y = jnp.asarray(rng.normal(size=(64, 32)), f32)
    return (x, y)


def _trace_cell(backend, op_class, ger, cfg, rng):
    """Returns the cell's ClosedJaxpr (raises if untraceable)."""
    args = _operands(op_class, ger, rng)
    if op_class == "attn":
        plan = lowering.Plan(ger=ger, backend=backend, causal=True)
        fn = lambda q, k, v: facility.contract(
            facility.ATTN, q, k, v, plan=plan)
    elif op_class == "conv":
        plan = lowering.Plan(ger=ger, backend=backend,
                             out_dtype=jnp.float32)
        fn = lambda a, b: facility.contract(
            facility.CONV2D, a, b, plan=plan)
    elif op_class == "ssd":
        plan = lowering.Plan(ger=ger, backend=backend)
        fn = lambda c, b, x, cum: facility.contract(
            facility.SSD, c, b, x, cum, plan=plan)
    elif op_class == "gemm.masked":
        plan = lowering.Plan(ger=ger, backend=backend,
                             out_dtype=precision.policy(ger).acc_dtype)
        m, k, n = args[0].shape[0], args[0].shape[1], args[1].shape[1]
        masks = (jnp.asarray(rng.random(m) > 0.3),
                 jnp.asarray(rng.random(n) > 0.3),
                 jnp.asarray(rng.random(k) > 0.3))
        base = args
        args = base + masks
        fn = lambda a, b, m1, m2, m3: facility.contract(
            "mk,kn->mn", a, b, masks=(m1, m2, m3), plan=plan)
    elif op_class == "gemm.saturating":
        plan = lowering.Plan(ger=ger, backend=backend, saturating=True,
                             out_dtype=lowering.ACC)
        args = tuple(a.astype(jnp.int16) for a in args)
        fn = lambda a, b: facility.contract("mk,kn->mn", a, b, plan=plan)
    else:
        plan = lowering.Plan(ger=ger, backend=backend)
        fn = lambda a, b: facility.contract("mk,kn->mn", a, b, plan=plan)
    with facility.configure(cfg):
        return jax.make_jaxpr(fn)(*args)


def audit_registry(verbose: bool = False):
    """Audit every traceable (op-class, ger, backend) registry cell.

    Returns (findings, audited, skipped): ``audited`` is the list of
    cell names checked, ``skipped`` the (cell, reason) pairs whose
    lowering does not trace (host-side numpy oracles).
    """
    rng = np.random.default_rng(0)
    cfg = facility.FacilityConfig(use_pallas=True, interpret=True)
    findings: list[Finding] = []
    audited: list[str] = []
    skipped: list[tuple] = []

    cells = sorted({(b, oc) for (b, oc, _, _) in lowering._REGISTRY
                    if oc in AUDIT_GERS})
    for backend, op_class in cells:
        for ger in AUDIT_GERS[op_class]:
            where = f"<jaxpr:{backend}/{op_class}/{ger.name}>"
            try:
                closed = _trace_cell(backend, op_class, ger, cfg, rng)
            except Exception as e:  # repro: allow(overbroad-except)
                # Untraceable cell (e.g. the ref saturating oracle is
                # host numpy) — reported as a skip, never silently.
                skipped.append((where, f"{type(e).__name__}: {e}"))
                continue
            audited.append(where)
            pol = precision.policy(ger)
            findings.extend(
                check_acc_dtype(closed.jaxpr, pol.acc_dtype, where))
            if backend == "pallas" and op_class == "gemm.masked":
                findings.extend(check_no_premask(closed, where))

    # zero-relayout: the packed-operand fast path (pallas gemm).
    for ger in (Ger.F32GER, Ger.BF16GER2):
        where = f"<jaxpr:pallas/gemm.packed/{ger.name}>"
        rngl = np.random.default_rng(1)
        x = jnp.asarray(rngl.normal(size=(16, 64)), jnp.float32)
        w = jnp.asarray(rngl.normal(size=(64, 32)), jnp.float32)
        lay = packing.gemm_layout(ger, 16, 32, 64)
        po = packing.pack_gemm(w, lay)
        plan = lowering.Plan(ger=ger, backend="pallas",
                             out_dtype=jnp.float32)
        try:
            with facility.configure(cfg):
                closed = jax.make_jaxpr(
                    lambda a, b: facility.contract(
                        "mk,kn->mn", a, b, plan=plan))(x, po)
        except Exception as e:  # repro: allow(overbroad-except)
            skipped.append((where, f"{type(e).__name__}: {e}"))
            continue
        audited.append(where)
        n_x = len(jax.tree_util.tree_leaves(x))
        packed = set(range(n_x, len(closed.jaxpr.invars)))
        findings.extend(check_zero_relayout(closed, packed, where))
        findings.extend(
            check_acc_dtype(closed.jaxpr,
                            precision.policy(ger).acc_dtype, where))

    # static VMEM-footprint audit over the autotune candidate space.
    for ger in (Ger.F64GER, Ger.F32GER, Ger.BF16GER2, Ger.I8GER4):
        pol = precision.policy(ger)
        where = f"<jaxpr:vmem/{ger.name}>"
        audited.append(where)
        for mnk in ((128, 128, 128), (512, 512, 512),
                    (2048, 2048, 2048), (8192, 8192, 8192)):
            findings.extend(check_vmem_candidates(
                autotune.candidate_blocks(*mnk, ger), pol, where))

    # the SSD kernel's residency at each registered SSM architecture.
    where = "<jaxpr:vmem/ssd>"
    audited.append(where)
    findings.extend(check_ssd_vmem(where))

    if verbose:
        for w in audited:
            print(f"audited {w}")
        for w, why in skipped:
            print(f"skipped {w}: {why}")
    return findings, audited, skipped
