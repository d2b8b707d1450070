"""Compile the main path's kernels for a described TPU v5e, at real widths.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, and asserts the compiled program holds the Pallas
kernel (``tpu_custom_call``).  This catches what interpret mode cannot —
block shapes the TPU tiling refuses, primitives Mosaic has no lowering for.

The topology is described inside a module-scoped fixture (never at import,
in a ``skipif`` or in ``conftest.py``): only the worker that runs this
file loads the TPU compiler.  The persistent compilation cache is off
around the compiles — an entry written here cannot be read back without a
chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get as get_arch
from repro.core import facility
from repro.core.facility import Epilogue, Plan
from repro.core.precision import Ger
from repro.models import model as M
from repro.train import steps as S


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# The facility as a TPU process resolves it (this process sees the CPU).
COMPILED = facility.FacilityConfig(use_pallas=True, interpret=False)


def _custom_calls(fn, *args) -> int:
    with facility.configure(COMPILED):
        text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bf16_gemm(one_chip):
    x = _spec(one_chip, (2048, 768))
    w = _spec(one_chip, (768, 3072))
    assert _custom_calls(lambda a, b: facility.contract(
        "mk,kn->mn", a, b, plan=Plan(ger=Ger.BF16GER2)), x, w) == 1


def test_gemm_fused_gelu(one_chip):
    x = _spec(one_chip, (2048, 768))
    w = _spec(one_chip, (768, 3072))
    bias = _spec(one_chip, (3072,), jnp.float32)
    ep = Epilogue(bias=True, activation="gelu")
    assert _custom_calls(lambda a, b, c: facility.contract(
        "mk,kn->mn", a, b, bias=c, plan=Plan(ger=Ger.BF16GER2, epilogue=ep)),
        x, w, bias) == 1


def test_batched_ssd_gemm(one_chip):
    """mamba2-130m's chunk-state contraction at 8 x 2048 tokens (256-token
    chunks, state 128, 24 heads of 64)."""
    b = _spec(one_chip, (8, 8, 256, 128))
    x = _spec(one_chip, (8, 8, 256, 24, 64))
    assert _custom_calls(lambda p, q: facility.contract(
        "bcln,bclhp->bchnp", p, q, plan=Plan(out_dtype=jnp.float32)),
        b, x) >= 1


@pytest.mark.parametrize("shape", [(2, 16, 256, 64, 56, 64),
                                   (64, 1, 256, 128, 24, 64)],
                         ids=["zamba2-7b", "mamba2-130m"])
def test_ssd_intra_chunk_kernel(one_chip, shape):
    """The SSD chunk scan's intra-chunk branch as one kernel: one layer of
    a zamba2-7b 4096-token prompt (two B/C groups as sequences, 16 chunks
    of 256, 56 heads of 64 a group, state 64), and mamba2-130m's prefill
    of 64 prompts of 256 (24 heads, state 128)."""
    s, nc, l, n, h, p = shape
    c, b = _spec(one_chip, (s, nc, l, n)), _spec(one_chip, (s, nc, l, n))
    x = _spec(one_chip, (s, nc, l, h, p))
    cum = _spec(one_chip, (s, nc, h, l), jnp.float32)
    assert _custom_calls(lambda c_, b_, x_, m: facility.contract(
        facility.SSD, c_, b_, x_, m), c, b, x, cum) == 1


def test_mamba2_depthwise_conv(one_chip):
    """The causal width-4 depthwise conv over mamba2-130m's 1792 conv
    channels, on the f32 accumulator path with bias + silu fused."""
    x = _spec(one_chip, (8, 2048, 1792))
    w = _spec(one_chip, (4, 1792), jnp.float32)
    bias = _spec(one_chip, (1792,), jnp.float32)
    assert _custom_calls(lambda a, k, c: facility.contract(
        facility.CONV1D_DEPTHWISE, a, k, bias=c,
        plan=Plan(ger=Ger.F32GER, padding="causal", out_dtype=jnp.bfloat16,
                  epilogue=Epilogue(bias=True, activation="silu"))),
        x, w, bias) == 1


def _attn(one_chip, q, kv, *, valid=None, **plan):
    args = [_spec(one_chip, q), _spec(one_chip, kv), _spec(one_chip, kv)]
    if valid is not None:
        args.append(_spec(one_chip, valid, jnp.bool_))
        return _custom_calls(lambda a, b, c, m: facility.contract(
            facility.ATTN, a, b, c, masks=(m,), plan=Plan(**plan)), *args)
    return _custom_calls(lambda a, b, c: facility.contract(
        facility.ATTN, a, b, c, plan=Plan(**plan)), *args)


def test_flash_attention_causal_gqa(one_chip):
    assert _attn(one_chip, (1, 2048, 32, 128), (1, 2048, 8, 128),
                 causal=True) == 1


def test_flash_attention_window(one_chip):
    assert _attn(one_chip, (1, 8192, 32, 120), (1, 8192, 8, 120),
                 causal=True, window=4096) == 1


def test_flash_attention_valid_batched(one_chip):
    assert _attn(one_chip, (8, 256, 32, 128), (8, 2048, 2, 128),
                 valid=(8, 2048)) == 1


def test_mamba2_decode_step(one_chip):
    """The whole mamba2-130m decode step: 8 slots, 24 layers."""
    cfg = get_arch("mamba2-130m")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.key(0))))
    cache = jax.tree.map(spec, jax.eval_shape(
        lambda: M.init_cache(cfg, batch=8, seq_len=2048)))
    tokens = _spec(one_chip, (8, 1), jnp.int32)
    assert _custom_calls(S.make_serve_step(cfg), params, cache,
                         tokens) >= 4
