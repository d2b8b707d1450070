"""The ``ssd`` op-class: the SSD chunk scan's intra-chunk branch.

The Pallas kernel (interpret mode) against the XLA lowering, through the
model's own scan, at reduced heads and length: one and two B/C groups,
one and sixteen chunks, states of 64 and 128; shapes the kernel does not
tile fall back to the XLA lowering; and which lowering the model's
prefill and the trainer's step reach.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.base import reduced
from repro.core import facility, lowering
from repro.core.facility import Plan
from repro.core.precision import Ger
from repro.kernels import mma_ssd
from repro.models import mamba2 as M2
from repro.models import model as M
from repro.train import steps as S

# test_properties.py's chunked-vs-single-chunk tolerance
TOL = dict(rtol=2e-4, atol=2e-4)


def _f32(use_pallas):
    return facility.FacilityConfig(ger=Ger.F32GER, out_dtype=jnp.float32,
                                   use_pallas=use_pallas, interpret=True)


def _scan_inputs(seed, *, l, h, p, n, groups):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (1, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, l, h)) - 2.0)
    A = -jnp.linspace(0.5, 4.0, h)
    B = jax.random.normal(ks[2], (1, l, groups * n))
    C = jax.random.normal(ks[3], (1, l, groups * n))
    D = jax.random.normal(ks[4], (h,))
    return x, dt, A, B, C, D


def _ssd_counts(before):
    return {k[0]: v - before.get(k, 0)
            for k, v in lowering.DISPATCH_COUNTS.items()
            if k[1] == "ssd" and v != before.get(k, 0)}


def _both(args, chunk, groups):
    """ssd_grouped's (y, final state) on each lowering, and the ssd
    dispatches the Pallas-configured run counted."""
    out = {}
    for use_pallas in (False, True):
        before = dict(lowering.DISPATCH_COUNTS)
        with facility.configure(_f32(use_pallas)):
            out[use_pallas] = M2.ssd_grouped(*args, chunk, groups)
        counts = _ssd_counts(before)
    return out[False], out[True], counts


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("nc", [1, 16])
@pytest.mark.parametrize("n", [64, 128])
def test_kernel_matches_xla(groups, nc, n):
    """y and the final state (the prefill -> decode handoff) of the scan
    with the kernel equal the XLA chain's, at 128-token chunks of 8 heads
    of 64 per group."""
    chunk = 128
    args = _scan_inputs(nc * n + groups, l=nc * chunk, h=8 * groups, p=64,
                        n=n, groups=groups)
    ruled = sum(lowering.SHAPE_RULE_FALLBACKS.values())
    (y0, s0), (y1, s1), counts = _both(args, chunk, groups)
    assert counts == {"pallas": 1}
    assert sum(lowering.SHAPE_RULE_FALLBACKS.values()) == ruled
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), **TOL)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), **TOL)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [16, 64])
def test_short_chunks_fall_back(groups, chunk):
    """Chunks under the kernel's 128-row tile take the XLA lowering under
    a Pallas config: the same values, dispatched as ``ssd`` on xla and
    recorded as the shape rule's choice."""
    args = _scan_inputs(chunk, l=2 * chunk, h=4 * groups, p=32, n=16,
                        groups=groups)
    key = ("ssd", Ger.F32GER.value)
    ruled = lowering.SHAPE_RULE_FALLBACKS[key]
    (y0, s0), (y1, s1), counts = _both(args, chunk, groups)
    assert counts == {"xla": 1}
    assert lowering.SHAPE_RULE_FALLBACKS[key] == ruled + 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), **TOL)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), **TOL)


def test_contract_bf16_matches_xla():
    """At the serving policy (bf16 operands, f32 decays and accumulation,
    bf16 out) the kernel's intra-chunk outputs equal the XLA chain's to
    one bf16 rounding of the output."""
    s, nc, l, n, h, p = 2, 2, 256, 64, 16, 64
    ks = jax.random.split(jax.random.key(7), 4)
    c, b = (jax.random.normal(k, (s, nc, l, n)).astype(jnp.bfloat16)
            for k in ks[:2])
    x = jax.random.normal(ks[2], (s, nc, l, h, p)).astype(jnp.bfloat16)
    cum = jnp.cumsum(-0.05 * jax.random.uniform(ks[3], (s, nc, h, l)), -1)
    got = {}
    for backend in ("xla", "pallas"):
        with facility.configure(facility.FacilityConfig(interpret=True)):
            got[backend] = np.asarray(facility.contract(
                facility.SSD, c, b, x, cum,
                plan=Plan(backend=backend)), np.float32)
    np.testing.assert_allclose(got["pallas"], got["xla"], rtol=2 ** -7,
                               atol=2 ** -7)


def test_ref_lowering_matches_xla():
    s, nc, l, n, h, p = 1, 2, 16, 8, 2, 4
    ks = jax.random.split(jax.random.key(5), 4)
    c, b = (jax.random.normal(k, (s, nc, l, n)) for k in ks[:2])
    x = jax.random.normal(ks[2], (s, nc, l, h, p))
    cum = jnp.cumsum(-jax.random.uniform(ks[3], (s, nc, h, l)), -1)
    got = {}
    for backend in ("xla", "ref"):
        with facility.configure(_f32(False)):
            got[backend] = facility.contract(
                facility.SSD, c, b, x, cum,
                plan=Plan(backend=backend))
    np.testing.assert_allclose(np.asarray(got["ref"]),
                               np.asarray(got["xla"]), **TOL)


def test_ssd_spec_is_validated():
    c = jnp.zeros((1, 1, 16, 8))
    x = jnp.zeros((1, 1, 16, 2, 4))
    cum = jnp.zeros((1, 1, 2, 16))
    with pytest.raises(ValueError, match="four-operand"):
        facility.contract(facility.SSD, c, c, x)
    with pytest.raises(ValueError, match="ssd wants"):
        facility.contract(facility.SSD, c, c, x, jnp.zeros((1, 1, 3, 16)))
    with pytest.raises(ValueError, match="no accumulator seed"):
        facility.contract(facility.SSD, c, c, x, cum,
                          plan=Plan(block=(8, 8, 8)))
    with pytest.raises(ValueError, match="no accumulator seed"):
        facility.contract(facility.SSD, c, c, x, cum, masks=(cum,))
    with pytest.raises(ValueError, match="fourth operand"):
        facility.contract("mk,kn->mn", c[0, 0], c[0, 0].T, None, cum)


def test_kernel_refuses_untiled_heads():
    c = jnp.zeros((1, 1, 128, 16))
    with pytest.raises(ValueError, match="ssd lanes"):
        mma_ssd.mma_ssd(c, c, jnp.zeros((1, 1, 128, 3, 48)),
                        jnp.zeros((1, 1, 3, 128)), c_dtype=jnp.float32,
                        b_dtype=jnp.float32, att_dtype=jnp.float32,
                        prod_dtype=jnp.float32, x_dtype=jnp.float32,
                        out_dtype=jnp.float32, interpret=True)


def _prefill_counts(arch, use_pallas):
    """The ssd dispatches of tracing a reduced ``arch``'s prefill at two
    128-token chunks."""
    cfg = dataclasses.replace(reduced(get(arch)), ssm_chunk=128)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 256), jnp.int32)}
    before = dict(lowering.DISPATCH_COUNTS)
    with facility.configure(facility.FacilityConfig(use_pallas=use_pallas,
                                                    interpret=True)):
        jax.eval_shape(S.make_prefill_step(cfg), params, batch)
    return _ssd_counts(before)


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-7b"])
def test_prefill_reaches_the_kernel(arch):
    """The prefill's Mamba layers (one trace of each layer scan's body)
    dispatch ``ssd`` on pallas under a Pallas config, and on xla under an
    XLA one."""
    pallas = _prefill_counts(arch, True)
    assert set(pallas) == {"pallas"} and pallas["pallas"] >= 1
    assert _prefill_counts(arch, False) == {"xla": pallas["pallas"]}


def test_training_never_reaches_the_kernel():
    """``launch.train.build``'s step traces every contract on XLA, under a
    Pallas config too: the kernel has no backward pass."""
    from repro.data import pipeline
    from repro.launch import train
    cfg = dataclasses.replace(reduced(get("mamba2-130m")), ssm_chunk=128)
    make_state, make_step, _ = train.build(cfg, total_steps=2)
    state = jax.eval_shape(make_state)
    batch = {k: jnp.asarray(v) for k, v in pipeline.synthetic_batch(
        cfg, batch=1, seq=256, step=0).items()}
    before = dict(lowering.DISPATCH_COUNTS)
    with facility.configure(facility.FacilityConfig(use_pallas=True,
                                                    interpret=True)):
        jax.eval_shape(make_step(), state, batch)
    counts = _ssd_counts(before)
    assert set(counts) == {"xla"} and counts["xla"] >= 1
