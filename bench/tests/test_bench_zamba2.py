"""The hybrid model (zamba2) against its plain reference, at the reduced
size of the benchmark's configuration: two shared blocks called A, B, A,
each call with its own adapter and linear, over Mamba2 layers with two
B/C groups.

The model computes with bfloat16 operands and activations and the
reference in float32.  At this size their logits differ by up to about 9%
of the largest logit over every position (5% at the last; three seeds):
the residual stream is rounded to bfloat16 at each of the seven layers
and three calls, and the long-memory heads (A = 1 .. heads, as published)
carry that rounding along the sequence.  The fp8 control differs six times
more.  Computed in float32 throughout, the model matches the reference to
a few millionths.
"""
import _paths  # noqa: F401

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import jobs, spec
from references import common, zamba2
from repro.configs import get
from repro.configs.base import reduced
from repro.models import mamba2 as M2
from repro.models import model as M
from repro.train import steps as S

SEED = 2 ** 40 + 7


def _setup(seed=SEED, config="zamba2-7b-pp4"):
    with open(spec.BENCH / "configs" / f"{config}.json") as f:
        model = jobs.make_model(json.load(f), rehearse=True)
    assert model.ref is zamba2
    return model.arch, model.cfg, jobs.make_weights(model, seed)


def _tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def test_the_reduced_configuration_keeps_the_hybrids_shape():
    cfg, _, w = _setup()
    assert cfg.shared_calls == (1, 3, 5) and cfg.num_mem_blocks == 2
    assert cfg.ssm_ngroups == 2 and cfg.head_dim * cfg.num_heads == (
        2 * cfg.d_model)
    assert w["calls"]["linear"].shape[0] == 3
    assert w["shared"]["attn"]["wo"].shape[1:] == (
        cfg.num_heads * cfg.head_dim, cfg.d_model)


def test_forward_matches_the_reference():
    cfg, cd, w = _setup()
    toks = _tokens(cfg, (2, 64))
    got, _, _ = jax.jit(lambda w, t: M.forward(w, {"tokens": t}, cfg))(
        w, toks)
    ref = jax.jit(lambda w, t, mm: zamba2.logits(w, t, cd, mm),
                  static_argnums=2)
    want = ref(w, toks, common.mm_highest)
    err = _rel(got, want)
    assert err < 0.1, err
    assert _rel(ref(w, toks, common.mm_fp8), want) > 4 * err
    last = jax.jit(lambda w, t: zamba2.last_logits(
        w, t, cd, common.mm_highest))(w, toks)
    np.testing.assert_allclose(np.asarray(last), np.asarray(want[:, -1]),
                               rtol=1e-5, atol=1e-6)


def test_prefill_then_decode_equals_the_forward():
    """Prefill 48 tokens, hand the caches to the decode cache, decode 8
    more through it at batch 1: each step's logits are the full forward's
    at that position.  The decode runs the SSM as its recurrence and
    attention over each call's ring where the forward runs the chunked
    scan and the flash kernel, so bfloat16 rounds them differently: within
    5% of the largest logit (up to 2.8% read over three seeds)."""
    cfg, _, w = _setup()
    p, n = 48, 8
    toks = _tokens(cfg, (1, p + 16), seed=1)
    full, _, _ = jax.jit(lambda w, t: M.forward(w, {"tokens": t}, cfg))(
        w, toks)
    _, pre = jax.jit(S.make_prefill_step(cfg))(w, {"tokens": toks[:, :p]})
    k, v = pre["kv"]
    assert k.shape[0] == len(cfg.shared_calls)     # one K/V for each call
    cache = M.init_cache(cfg, batch=1, seq_len=p + n)
    cache.update(ssm=pre["ssm"], conv=pre["conv"].astype(cache["conv"].dtype),
                 k=cache["k"].at[:, :, :p].set(k),
                 v=cache["v"].at[:, :, :p].set(v),
                 pos=cache["pos"].at[:p].set(jnp.arange(p)),
                 cur=jnp.asarray(p, jnp.int32))
    step = jax.jit(lambda w, c, t: M.decode_step(w, c, t, cfg))
    for s in range(n):
        logits, cache = step(w, cache, toks[:, p + s:p + s + 1])
        assert _rel(logits[0, 0], full[0, p + s]) < 0.05, s
    # each call wrote its own ring: the calls' keys differ
    assert not np.array_equal(np.asarray(cache["k"][0, :, p]),
                              np.asarray(cache["k"][2, :, p]))


def test_calls_of_one_block_share_it_and_keep_their_own_adapters():
    """Calls 0 and 2 run block A with the same weights, call 1 block B;
    perturbing call 0's adapter changes what call 0 gives and nothing
    else."""
    cfg, _, w = _setup()
    (a0, c0), (b1, _), (a2, c2) = [M._call_params(w, cfg, j)
                                   for j in range(3)]
    for x, y in zip(jax.tree.leaves(a0), jax.tree.leaves(a2)):
        assert x is y or np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a0["mlp"]["w1"]),
                              np.asarray(b1["mlp"]["w1"]))
    assert not np.array_equal(np.asarray(c0["linear"]),
                              np.asarray(c2["linear"]))

    h = jax.random.normal(jax.random.key(1), (1, 16, cfg.d_model),
                          jnp.bfloat16)
    emb0 = jax.random.normal(jax.random.key(2), h.shape, jnp.bfloat16)

    @jax.jit
    def outs(w):
        cos_sin = M._cos_sin_for(cfg, jnp.arange(16)[None])
        got = []
        for j in range(3):
            sp, cp = M._call_params(w, cfg, j)
            a, _ = M._shared_attn(sp, h, emb0, cfg, cos_sin=cos_sin)
            got.append(M._shared_mlp(sp, cp, a, cfg))
        return got

    before = outs(w)
    w2 = jax.tree.map(lambda a: a, w)
    w2["calls"] = dict(w["calls"], adapter_in=w["calls"]["adapter_in"].at[
        0].multiply(3.0))
    after = outs(w2)
    assert not np.array_equal(np.asarray(before[0]), np.asarray(after[0]))
    for j in (1, 2):
        np.testing.assert_array_equal(np.asarray(before[j]),
                                      np.asarray(after[j]))


def test_one_group_runs_the_ungrouped_path():
    """With one B/C group the grouped scan and norm add no operation: the
    mamba2-130m prefill step lowers to the same program as with the
    ungrouped scan and norm put back in their place."""
    cfg = reduced(get("mamba2-130m"))
    assert cfg.ssm_ngroups == 1
    params = M.init_params(cfg, jax.random.key(0))
    toks = {"tokens": _tokens(cfg, (2, 32))}

    def lowered():
        return jax.jit(S.make_prefill_step(cfg)).lower(params,
                                                       toks).as_text()

    grouped = lowered()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(M2, "ssd_grouped", lambda x, dt, A, B, C, D, chunk, g:
                   M2.ssd_chunked(x, dt, A, B, C, D, chunk,
                                  return_state=True))
        mp.setattr(M2, "_group_rms", lambda g, groups, eps: g * jax.lax.rsqrt(
            (g * g).mean(-1, keepdims=True) + eps))
        plain = lowered()
    assert grouped == plain


def test_grouped_scan_is_each_group_on_its_own():
    """Two groups: the heads of group g read only group g's B and C, as
    two ungrouped scans would."""
    b, l, h, p, n = 1, 32, 4, 8, 4
    ks = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.arange(1.0, h + 1)
    B, C = (jax.random.normal(k, (b, l, 2 * n)) for k in ks[2:4])
    D = jax.random.normal(ks[4], (h,))
    y, final = M2.ssd_grouped(x, dt, A, B, C, D, 16, 2)
    for g in range(2):
        hs, ns = slice(2 * g, 2 * g + 2), slice(n * g, n * g + n)
        yg, fg = M2.ssd_chunked(x[:, :, hs], dt[:, :, hs], A[hs],
                                B[..., ns], C[..., ns], D[hs], 16,
                                return_state=True)
        np.testing.assert_allclose(np.asarray(y[:, :, hs]), np.asarray(yg),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(final[:, hs]), np.asarray(fg),
                                   rtol=1e-5, atol=1e-5)


def test_zamba2_1p2b_builds_and_matches_the_reference():
    """The smaller model of the family: one shared block, one group."""
    cfg = reduced(get("zamba2-1.2b"))
    assert cfg.num_mem_blocks == 1 and cfg.ssm_ngroups == 1
    cd = dataclasses.asdict(cfg)
    w = jax.jit(lambda k: zamba2.init_weights(cd, k))(
        common.key_from_seed(9))
    want_tree = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    assert jax.tree.structure(w) == jax.tree.structure(want_tree)
    toks = _tokens(cfg, (1, 32))
    got, _, _ = jax.jit(lambda w, t: M.forward(w, {"tokens": t}, cfg))(
        w, toks)
    want = jax.jit(lambda w, t: zamba2.logits(w, t, cd, common.mm_highest))(
        w, toks)
    assert _rel(got, want) < 0.1
    cache = M.init_cache(cfg, batch=2, seq_len=16)
    logits, cache = jax.jit(lambda w, c, t: M.decode_step(w, c, t, cfg))(
        w, cache, jnp.zeros((2, 1), jnp.int32))
    assert bool(jnp.isfinite(logits).all()) and int(cache["cur"]) == 1
