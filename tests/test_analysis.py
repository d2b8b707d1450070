"""The invariant checker checks itself: every AST rule has a known-bad
fixture that must be flagged, the real tree must be clean, suppressions
must be honored, and every jaxpr invariant has a broken-trace case that
must fail."""

from __future__ import annotations

import json
import pathlib
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import astcheck, check_paths, check_source
from repro.analysis import jaxpr_check, rules
from repro.analysis.__main__ import main as analysis_main
from repro.core import autotune, facility, lowering, packing, precision
from repro.core import tiling
from repro.core.precision import Ger

REPO = pathlib.Path(__file__).resolve().parent.parent
MODELS = "src/repro/models/fixture.py"
LOWERING = "src/repro/core/lowering.py"
KERNEL = "src/repro/kernels/mma_gemm.py"


def rule_ids(src: str, path: str = MODELS) -> set:
    return {f.rule for f in check_source(textwrap.dedent(src), path)}


# ----------------------------------------------------------------------
# AST rules: one known-bad fixture per rule (and a sanctioned twin)
# ----------------------------------------------------------------------

def test_purity_module_alias():
    src = """
        import jax.numpy as qnp
        def f(a, b):
            return qnp.dot(a, b)
    """
    assert "facility-purity" in rule_ids(src)
    # the same spelling inside a sanctioned oracle is fine
    assert "facility-purity" not in rule_ids(src, "src/repro/kernels/ref.py")


def test_purity_from_import_alias():
    src = """
        from jax.numpy import dot as d
        def f(a, b):
            return d(a, b)
    """
    ids = [f for f in check_source(textwrap.dedent(src), MODELS)
           if f.rule == "facility-purity"]
    assert len(ids) == 2  # the import itself and the aliased call


def test_purity_method_call_and_matmul_operator():
    assert "facility-purity" in rule_ids("""
        def f(x, y):
            return x.dot(y)
    """)
    assert "facility-purity" in rule_ids("""
        def f(x, y):
            return x @ y
    """)
    assert "facility-purity" in rule_ids("""
        import numpy as np
        def f(x, y):
            return np.einsum("ij,jk->ik", x, y)
    """)


def test_lax_purity():
    src = """
        from jax import lax
        def f(a, b, d):
            return lax.dot_general(a, b, d)
    """
    assert "lax-purity" in rule_ids(src)
    # one layer down the same call is the lowering's job
    assert "lax-purity" not in rule_ids(src, KERNEL)
    assert "lax-purity" not in rule_ids(src, LOWERING)


def test_grid_owns_batch():
    src = """
        import jax
        def dispatch(f, xs):
            return jax.vmap(f)(xs)
    """
    assert "grid-owns-batch" in rule_ids(src, LOWERING)
    assert "grid-owns-batch" not in rule_ids(src, MODELS)


def test_attn_op_class():
    src = "from repro.kernels import mma_attention\n"
    assert "attn-op-class" in rule_ids(src, MODELS)
    assert "attn-op-class" not in rule_ids(src, "src/repro/launch/x.py")


def test_pack_once():
    assert "pack-once" in rule_ids("""
        def dispatch(po):
            return po.unpack()
    """, LOWERING)
    assert "pack-once" in rule_ids("""
        def dispatch(w, lay):
            from repro.core import packing
            return packing.pack_gemm(w, lay)
    """, LOWERING)
    assert "pack-once" in rule_ids("""
        def kernel(x_ref):
            import jax.numpy as jnp
            return jnp.transpose(x_ref[...])
    """, KERNEL)
    assert "pack-once" in rule_ids("""
        def kernel(x):
            return x.swapaxes(0, 1)
    """, KERNEL)
    # jnp.transpose in the lowering layer is output assembly, not a
    # per-call operand relayout — only swapaxes/pack/unpack are banned.
    assert "pack-once" not in rule_ids("""
        def assemble(out):
            import jax.numpy as jnp
            return jnp.transpose(out, (0, 2, 1))
    """, LOWERING)


def test_layer_stratification():
    # layer-skip: models reaching two strata down into the kernels
    assert "layer-stratification" in rule_ids(
        "from repro.kernels import epilogue\n", MODELS)
    assert "layer-stratification" in rule_ids(
        "from repro.core import lowering\n", MODELS)
    # upward: a kernel importing the facility above it
    assert "layer-stratification" in rule_ids(
        "from repro.core import facility\n", KERNEL)
    # adjacent layers are the architecture
    assert "layer-stratification" not in rule_ids(
        "from repro.core import lowering\n", "src/repro/core/facility.py")
    assert "layer-stratification" not in rule_ids(
        "from repro.core import facility\n", MODELS)
    # unmapped substrate is outside the DAG
    assert "layer-stratification" not in rule_ids(
        "from repro.core import precision\n", KERNEL)


def test_deprecated_shim():
    src = """
        from repro.core import facility
        def f(x, y):
            return facility.fdot(x, y)
    """
    assert "deprecated-shim" in rule_ids(src)
    assert "deprecated-shim" in rule_ids(
        "from repro.kernels.ops import mma_dot\n", MODELS)
    # tests may exercise the shims
    assert "deprecated-shim" not in rule_ids(src, "tests/test_fixture.py")
    # the defining module may reference its own shims
    assert "deprecated-shim" not in rule_ids(src, "src/repro/core/facility.py")


def test_mutable_default_arg():
    assert "mutable-default-arg" in rule_ids("""
        def f(a, xs=[]):
            return xs
    """)
    assert "mutable-default-arg" in rule_ids("""
        def f(cfg=ElasticConfig()):
            return cfg
    """)
    assert "mutable-default-arg" not in rule_ids("""
        def f(a, xs=(), t=tuple(), n=None, k=3):
            return xs
    """)


def test_overbroad_except():
    assert "overbroad-except" in rule_ids("""
        def f():
            try:
                g()
            except:
                pass
    """)
    assert "overbroad-except" in rule_ids("""
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert "overbroad-except" not in rule_ids("""
        def f():
            try:
                g()
            except (ValueError, TypeError):
                pass
    """)


def test_fault_point_literal():
    # a typo'd point never validates anywhere and silently never fires
    assert "fault-point-literal" in rule_ids("""
        from repro.runtime import faults as _faults
        def f():
            return _faults.fire("contract.dispatchh")
    """)
    assert "fault-point-literal" in rule_ids("""
        from repro.runtime.faults import maybe_inject
        def f():
            return maybe_inject(point="autotune.lod")
    """)
    # a registered literal and a named constant are both fine
    assert "fault-point-literal" not in rule_ids("""
        from repro.runtime import faults as _faults
        def f():
            _faults.fire("autotune.load")
            return _faults.fire(_faults.CONTRACT_DISPATCH)
    """)
    # unrelated fire() functions are not the registry's hook
    assert "fault-point-literal" not in rule_ids("""
        def f(event):
            return event.fire("whatever")
    """)


def test_collective_purity():
    # raw collectives outside the mesh-native dispatch surface: every
    # spelling (module attr chain, lax alias, from-import) is a finding
    assert "collective-purity" in rule_ids("""
        from jax import shard_map
        def f(fn, mesh, x):
            return shard_map(fn, mesh=mesh)(x)
    """)
    assert "collective-purity" in rule_ids("""
        from jax import lax
        def ring(x, pairs):
            return lax.ppermute(x, 'stage', pairs)
    """)
    assert "collective-purity" in rule_ids("""
        import jax
        def exchange(x):
            x = jax.lax.all_to_all(x, 'experts', 0, 1, tiled=True)
            return jax.lax.with_sharding_constraint(x, None)
    """)
    # the three sanctioned modules own the primitives
    for path in ("src/repro/parallel/api.py",
                 "src/repro/core/lowering.py",
                 "src/repro/runtime/pipeline.py"):
        assert "collective-purity" not in rule_ids("""
            from jax import shard_map
            from jax import lax
            def f(fn, mesh, x):
                return shard_map(fn, mesh=mesh)(lax.ppermute(x, 'a', []))
        """, path)
    # parallel.api.shard (the sanctioned annotation) is not a collective
    assert "collective-purity" not in rule_ids("""
        from repro.parallel.api import shard
        def f(x):
            return shard(x, "batch", None)
    """)


def test_suppression_honored():
    flagged = """
        def f(x, y):
            return x @ y
    """
    same_line = """
        def f(x, y):
            return x @ y  # repro: allow(facility-purity)
    """
    line_above = """
        def f(x, y):
            # repro: allow(facility-purity)
            return x @ y
    """
    wrong_rule = """
        def f(x, y):
            return x @ y  # repro: allow(pack-once)
    """
    assert "facility-purity" in rule_ids(flagged)
    assert rule_ids(same_line) == set()
    assert rule_ids(line_above) == set()
    assert "facility-purity" in rule_ids(wrong_rule)


def test_every_ast_rule_has_catalog_entry():
    ast_rules = {"facility-purity", "lax-purity", "grid-owns-batch",
                 "attn-op-class", "pack-once", "layer-stratification",
                 "deprecated-shim", "mutable-default-arg",
                 "overbroad-except", "fault-point-literal",
                 "collective-purity"}
    for rid in ast_rules:
        assert rid in rules.RULES, rid
        assert rules.RULES[rid].contract_pr.startswith("PR")


def test_clean_tree():
    """The checker's whole point: exit 0 on the fixed tree."""
    findings = check_paths([str(REPO / "src")])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_flags_and_json_report(tmp_path):
    bad = tmp_path / "repro" / "models" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(x, y):\n    return x @ y\n")
    report = tmp_path / "report.json"
    rc = analysis_main([str(tmp_path), "--json", str(report)])
    assert rc == 1
    blob = json.loads(report.read_text())
    assert blob["count"] == 1
    assert blob["rules"] == ["facility-purity"]
    assert blob["findings"][0]["line"] == 2
    assert analysis_main(["--list-rules"]) == 0


# ----------------------------------------------------------------------
# Jaxpr invariants: each one verified to fail with the invariant broken
# ----------------------------------------------------------------------

_PALLAS = facility.FacilityConfig(use_pallas=True, interpret=True)
rng = np.random.default_rng(0)


def _gemm_args():
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    return x, y


def test_jaxpr_acc_dtype_broken():
    x, y = _gemm_args()
    # a bf16 dot_general with no preferred_element_type accumulates in
    # bf16 — exactly what the discipline forbids
    bad = jax.make_jaxpr(
        lambda a, b: jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ()))))(x, y)
    found = jaxpr_check.check_acc_dtype(bad.jaxpr, jnp.float32, "<t>")
    assert found and found[0].rule == "jaxpr-acc-dtype"


def test_jaxpr_acc_dtype_clean():
    x, y = _gemm_args()
    plan = lowering.Plan(ger=Ger.BF16GER2, backend="pallas")
    with facility.configure(_PALLAS):
        good = jax.make_jaxpr(lambda a, b: facility.contract(
            "mk,kn->mn", a, b, plan=plan))(x, y)
    assert jaxpr_check.check_acc_dtype(good.jaxpr, jnp.float32, "<t>") == []


def test_jaxpr_zero_relayout_broken():
    x, y = _gemm_args()
    plan = lowering.Plan(ger=Ger.F32GER, backend="pallas",
                         out_dtype=jnp.float32)

    def relayouted(a, b):
        b = jnp.transpose(jnp.transpose(b))   # round-trip relayout
        return facility.contract("mk,kn->mn", a, b, plan=plan)

    with facility.configure(_PALLAS):
        bad = jax.make_jaxpr(relayouted)(x, y)
    found = jaxpr_check.check_zero_relayout(bad, {1}, "<t>")
    assert found and found[0].rule == "jaxpr-zero-relayout"


def test_jaxpr_zero_relayout_clean_packed_path():
    x, y = _gemm_args()
    lay = packing.gemm_layout(Ger.F32GER, 16, 32, 64)
    po = packing.pack_gemm(y, lay)
    plan = lowering.Plan(ger=Ger.F32GER, backend="pallas",
                         out_dtype=jnp.float32)
    with facility.configure(_PALLAS):
        good = jax.make_jaxpr(lambda a, b: facility.contract(
            "mk,kn->mn", a, b, plan=plan))(x, po)
    packed = set(range(1, len(good.jaxpr.invars)))
    assert jaxpr_check.check_zero_relayout(good, packed, "<t>") == []


def test_jaxpr_no_premask_broken():
    x, y = _gemm_args()
    xm = jnp.asarray(rng.random(16) > 0.3)
    plan = lowering.Plan(ger=Ger.F32GER, backend="pallas",
                         out_dtype=jnp.float32)

    def premasked(a, b, m):
        a = jnp.where(m[:, None], a, 0.0)     # pre-masking in HBM
        return facility.contract("mk,kn->mn", a, b, plan=plan)

    with facility.configure(_PALLAS):
        bad = jax.make_jaxpr(premasked)(x, y, xm)
    found = jaxpr_check.check_no_premask(bad, "<t>")
    assert found and found[0].rule == "jaxpr-no-premask"


def test_jaxpr_no_premask_clean_streamed_masks():
    x, y = _gemm_args()
    masks = (jnp.asarray(rng.random(16) > 0.3),
             jnp.asarray(rng.random(32) > 0.3),
             jnp.asarray(rng.random(64) > 0.3))
    plan = lowering.Plan(ger=Ger.F32GER, backend="pallas",
                         out_dtype=jnp.float32)
    with facility.configure(_PALLAS):
        good = jax.make_jaxpr(lambda a, b, m1, m2, m3: facility.contract(
            "mk,kn->mn", a, b, masks=(m1, m2, m3), plan=plan))(
                x, y, *masks)
    assert jaxpr_check.check_no_premask(good, "<t>") == []


def test_jaxpr_vmem_budget():
    pol = precision.policy(Ger.F64GER)
    fat = tiling.BlockConfig(1024, 1024, 1024)
    assert fat.residency_bytes(pol) > tiling.VMEM_BYTES
    found = jaxpr_check.check_vmem_candidates([fat], pol, "<t>")
    assert found and found[0].rule == "jaxpr-vmem-budget"
    # the real candidate generator never emits such a config
    for mnk in ((512, 512, 512), (8192, 8192, 8192)):
        cfgs = autotune.candidate_blocks(*mnk, Ger.F64GER)
        assert jaxpr_check.check_vmem_candidates(cfgs, pol, "<t>") == []
    # residency = working set + the out BlockSpec tile
    cfg = tiling.BlockConfig(128, 128, 256)
    assert cfg.residency_bytes(pol) == (cfg.vmem_bytes(pol)
                                        + 128 * 128 * pol.acc_bytes)


def test_jaxpr_registry_audit_clean():
    """The shipped registry passes the full audit; the one skip is the
    host-numpy ref saturating oracle (untraceable by design)."""
    findings, audited, skipped = jaxpr_check.audit_registry()
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(audited) >= 20
    assert all("ref/gemm.saturating" in w for w, _ in skipped), skipped
