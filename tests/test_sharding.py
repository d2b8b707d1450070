"""Sharded-vs-single-device bitwise equivalence of the mesh-native
contract path (DESIGN.md section 11): on a forced 8-device host mesh,
every pallas op-class lowers per-shard under shard_map with the full
contraction extent resident, so the sharded output must equal the
single-device output BITWISE — not approximately.  The fault probe on the
``collective`` point proves the shard_map path actually engaged (a
silently-degraded dispatch would pass the equality check trivially)."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(py_src: str, n_dev: int = 8, timeout=1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"      # a forced host mesh, never a chip
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py_src)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


_PRELUDE = """
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import facility, packing
    from repro.core.lowering import Plan
    from repro.parallel import api as par
    from repro.runtime import faults

    rng = np.random.default_rng(0)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    rules = par.default_rules(mesh)
    PAL = Plan(backend="pallas")

    def check(name, fn, want_collective=True):
        single = fn()
        probe = faults.FaultPlan([faults.FaultSpec(
            faults.COLLECTIVE, kind=faults.LATENCY, latency_s=0.0,
            every=1, max_fires=None)])
        with par.use_rules(rules), faults.install(probe):
            sharded = fn()
        assert jnp.array_equal(single, sharded), (
            name, float(jnp.abs(single - sharded).max()))
        fired = len(probe.fired(faults.COLLECTIVE))
        assert (fired > 0) == want_collective, (name, fired)
        print(name, "ok")

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
"""


def test_gemm_and_einsum_bitwise_under_mesh():
    _run(_PRELUDE + """
    x, y = arr(64, 48), arr(48, 96)
    check("gemm2d", lambda: facility.contract("mk,kn->mn", x, y, plan=PAL))
    xb, yb = arr(4, 32, 48), arr(4, 48, 64)
    check("bgemm", lambda: facility.contract("bmk,bkn->bmn", xb, yb,
                                             plan=PAL))
    bias, res = arr(96), arr(64, 96)
    check("gemm_fused", lambda: facility.contract(
        "mk,kn->mn", x, y, bias=bias, residual=res, plan=PAL))
    # general einsum specs (here: a sum-reduced free label, not
    # GEMM-shaped) fall back to the shardable XLA lowering: no shard_map
    # of our own, XLA SPMD owns the partitioning
    xe, ye = arr(8, 16), arr(16, 8)
    check("einsum", lambda: facility.contract("ab,bc->c", xe, ye,
                                              plan=PAL),
          want_collective=False)
    # an indivisible shape degrades to single-device, never wrong answers
    xo, yo = arr(7, 48), arr(48, 13)
    check("gemm_indivisible", lambda: facility.contract(
        "mk,kn->mn", xo, yo, plan=PAL), want_collective=False)
    print("OK")
    """)


def test_packed_operand_bitwise_under_mesh():
    _run(_PRELUDE + """
    x, y = arr(64, 48), arr(48, 96)
    lay = packing.GemmLayout(kind=facility.Ger.BF16GER2,
                             block=(32, 32, 16), side="y",
                             rows=48, cols=96, transposed=False)
    yp = packing.pack_gemm(y, lay)
    # packed y: N sharding is vetoed (tile stream), M shards over data;
    # the pack's layout block drives every shard identically
    check("gemm_packed_y", lambda: facility.contract(
        "mk,kn->mn", x, yp, plan=PAL))
    print("OK")
    """)


def test_conv_and_attn_bitwise_under_mesh():
    _run(_PRELUDE + """
    img, filt = arr(8, 40, 6), arr(5, 6, 12)
    check("conv1d", lambda: facility.contract(facility.CONV1D, img, filt,
                                              plan=PAL))
    q, k, v = arr(4, 64, 8, 16), arr(4, 64, 8, 16), arr(4, 64, 8, 16)
    check("attn", lambda: facility.contract(facility.ATTN, q, k, v,
                                            plan=PAL))
    check("attn_causal", lambda: facility.contract(
        facility.ATTN, q, k, v, plan=Plan(backend="pallas", causal=True)))
    # GQA with 6 heads / 2 kv heads: head sharding over the 4-way model
    # axis would break the group ratio, so Sq goes sequence-parallel and
    # the causal per-shard q_offset branches must still line up
    q2, k2, v2 = arr(2, 64, 6, 16), arr(2, 64, 2, 16), arr(2, 64, 2, 16)
    check("attn_gqa_seqshard", lambda: facility.contract(
        facility.ATTN, q2, k2, v2,
        plan=Plan(backend="pallas", causal=True)))
    valid = jnp.asarray(rng.random((4, 64)) > 0.3)
    check("attn_valid", lambda: facility.contract(
        facility.ATTN, q, k, v, masks=(valid,), plan=PAL))
    print("OK")
    """)


def test_mesh_of_one_and_explicit_binding():
    _run(_PRELUDE + """
    x, y = arr(64, 48), arr(48, 96)
    want = facility.contract("mk,kn->mn", x, y, plan=PAL)

    # mesh of 1: the plan binds but nothing shards — plain dispatch
    m1 = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    with par.use_rules(par.default_rules(m1)):
        got = facility.contract("mk,kn->mn", x, y, plan=PAL)
    assert jnp.array_equal(want, got)

    # Plan(mesh=...) binds explicitly, no ambient rules needed
    got = facility.contract("mk,kn->mn", x, y,
                            plan=Plan(backend="pallas", mesh=mesh))
    assert jnp.array_equal(want, got)

    # Plan(mesh=False) opts out even under active ambient rules
    probe = faults.FaultPlan([faults.FaultSpec(
        faults.COLLECTIVE, kind=faults.LATENCY, latency_s=0.0,
        every=1, max_fires=None)])
    with par.use_rules(rules), faults.install(probe):
        got = facility.contract("mk,kn->mn", x, y,
                                plan=Plan(backend="pallas", mesh=False))
    assert jnp.array_equal(want, got)
    assert not probe.fired(faults.COLLECTIVE)
    print("OK")
    """)


def test_guarded_abft_dispatch_under_mesh():
    _run(_PRELUDE + """
    x, y = arr(64, 48), arr(48, 96)
    q, k, v = arr(4, 64, 8, 16), arr(4, 64, 8, 16), arr(4, 64, 8, 16)
    with facility.configure(facility.FacilityConfig(
            use_pallas=True, guards=True, abft=True)):
        s0 = facility.contract("mk,kn->mn", x, y)
        a0 = facility.contract(facility.ATTN, q, k, v)
        with par.use_rules(rules):
            s1 = facility.contract("mk,kn->mn", x, y)
            a1 = facility.contract(facility.ATTN, q, k, v)
    assert jnp.array_equal(s0, s1)
    assert jnp.array_equal(a0, a1)
    print("OK")
    """)


def test_moe_exchange_matches_gather_reference():
    _run(_PRELUDE + """
    from repro.configs import get
    from repro.configs.base import reduced
    from repro.models import moe as MOE

    cfg = reduced(get("mixtral-8x22b"))
    p = MOE.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model),
                          jnp.float32)
    o_ref, a_ref = MOE.apply_moe(p, x, cfg)
    try:
        MOE.EXCHANGE_DISPATCH = True
        with par.use_rules(rules):
            o_ex, a_ex = MOE.apply_moe(p, x, cfg)
        o_deg, _ = MOE.apply_moe(p, x, cfg)   # no mesh: plain-fn path
    finally:
        MOE.EXCHANGE_DISPATCH = False
    assert jnp.array_equal(o_ref, o_ex), float(
        jnp.abs(o_ref - o_ex).max())
    assert jnp.array_equal(o_ref, o_deg)
    assert abs(float(a_ref - a_ex)) < 1e-6
    print("OK")
    """)


def test_pipeline_chunked_matches_fused():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.runtime import pipeline as PP
    from repro.runtime import faults

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("stage",))
    params, stage_fn, ref = PP.make_pipelined_mlp(
        jax.random.key(0), 4, 32, 64)
    x = jax.random.normal(jax.random.key(1), (16, 32))
    fused = PP.pipeline_apply(stage_fn, params, x, mesh=mesh,
                              microbatches=16)
    ticks = []
    probe = faults.FaultPlan([faults.FaultSpec(
        faults.COLLECTIVE, kind=faults.LATENCY, latency_s=0.0,
        every=1, max_fires=None)])
    with faults.install(probe):
        chunked = PP.pipeline_apply(
            stage_fn, params, x, mesh=mesh, microbatches=16,
            on_chunk=lambda d, t: ticks.append((d, t)))
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(fused),
                               rtol=1e-6, atol=1e-6)
    assert ticks == [(4, 16), (8, 16), (12, 16), (16, 16)], ticks
    assert len(probe.fired(faults.COLLECTIVE)) == 4
    print("OK")
    """)


def test_ssd_bitwise_under_mesh():
    _run(_PRELUDE + """
    def ssd(s, h, p):
        c, b = arr(s, 2, 128, 16), arr(s, 2, 128, 16)
        x = arr(s, 2, 128, h, p)
        cum = jnp.cumsum(-jnp.abs(arr(s, 2, h, 128)) * 0.1, -1)
        return lambda: facility.contract(facility.SSD, c, b, x, cum,
                                         plan=PAL)
    # sequences over data, heads over model: 8 heads of 16 a shard
    check("ssd", ssd(4, 32, 16))
    # 2 heads a shard would not tile the kernel's lanes: sequences only
    check("ssd_seq_only", ssd(4, 8, 16))
    # nothing divides: the kernel runs on the whole operands
    check("ssd_indivisible", ssd(3, 6, 64), want_collective=False)
    print("OK")
    """)
