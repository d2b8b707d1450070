"""Each plain reference against the program's model at the reduced size
of each configuration the cells run (mamba2 with its head tied to the
embedding, the dense model with its own head).

The model computes with bfloat16 operands and the reference in float32,
so their logits differ by bfloat16 rounding: about 1% of the logit scale
at this size.  The fp8 control differs several times more.
"""
import _paths  # noqa: F401

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import jobs, spec
from references import common, llama, mamba2
from repro.models import model as M

CASES = [("mamba2-130m", mamba2), ("deepseek-7b", llama)]
CONFIGS = {"mamba2-130m": "mamba2-130m", "deepseek-7b": "deepseek-7b-pp4"}


def _setup(arch, ref):
    with open(spec.BENCH / "configs" / f"{CONFIGS[arch]}.json") as f:
        model = jobs.make_model(json.load(f), rehearse=True)
    assert model.ref is ref
    cfg = model.arch
    w = jobs.make_weights(model, 2 ** 40 + 7)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 64)), jnp.int32)
    return cfg, model.cfg, w, toks


def _last(ref, w, toks, cd, mm):
    if ref is mamba2:
        return ref.logits(w, toks, cd, mm)[:, -1]
    return ref.last_logits(w, toks, cd, mm)


@pytest.mark.parametrize("arch,ref", CASES, ids=[a for a, _ in CASES])
def test_reference_matches_model(arch, ref):
    cfg, cd, w, toks = _setup(arch, ref)
    model, _, _ = M.forward(w, {"tokens": toks}, cfg)
    want = _last(ref, w, toks, cd, common.mm_highest)
    scale = float(jnp.abs(want).max())
    err = float(jnp.abs(model[:, -1] - want).max()) / scale
    low = float(jnp.abs(_last(ref, w, toks, cd, common.mm_fp8) - want
                        ).max()) / scale
    assert err < 0.03, err
    assert low > 3 * err, (low, err)


def test_mamba2_reference_every_position():
    cfg, cd, w, toks = _setup("mamba2-130m", mamba2)
    model, _, _ = M.forward(w, {"tokens": toks}, cfg)
    want = mamba2.logits(w, toks, cd, common.mm_highest)
    assert float(jnp.abs(model - want).max()) < 0.03 * float(
        jnp.abs(want).max())


def test_weights_in_the_programs_layout():
    for arch, ref in CASES:
        cfg, _, w, _ = _setup(arch, ref)
        want = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
        assert jax.tree.structure(w) == jax.tree.structure(want)


def test_seed_keys_differ_beyond_32_bits():
    a = jax.random.key_data(common.key_from_seed(5))
    b = jax.random.key_data(common.key_from_seed(5 + 2 ** 32))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
