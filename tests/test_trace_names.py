"""The program's own names in a trace: the facility's dispatch scope, the
model's block and cost-site scopes, the trainer loop's host spans, and the
compile counter.  Compiled on the CPU: a scope reaches the compiled HLO as
the ``op_name`` of every op traced under it."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.base import reduced
from repro.core import facility, lowering
from repro.launch import compile_cache
from repro.models import model as M
from repro.optim import adamw
from repro.runtime.elastic import ElasticConfig, ElasticTrainer
from repro.train import steps as S


def _op_names(compiled_text: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _scopes(names) -> set[str]:
    """Every name-stack component, unwrapped: the stack writes a scope
    entered under a transformation as e.g. ``transpose(jvp(loss))``."""
    return {re.sub(r"^(?:[\w-]+\()+", "", part).rstrip(")")
            for n in names for part in n.split("/")}


def _compiled(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("guards", [False, True], ids=["unguarded",
                                                       "guarded"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla",
                                                           "pallas"])
def test_contract_scope_names_the_backend_that_ran(use_pallas, guards):
    cfg = dataclasses.replace(facility.current(), use_pallas=use_pallas,
                              guards=guards)
    x = jnp.ones((16, 32), jnp.bfloat16)
    y = jnp.ones((32, 24), jnp.bfloat16)
    before = dict(lowering.DISPATCH_COUNTS)
    with facility.configure(cfg):
        text = _compiled(lambda a, b: facility.contract(facility.DOT, a, b),
                         x, y)
    ran = [k for k, v in lowering.DISPATCH_COUNTS.items()
           if v != before.get(k, 0)]
    assert len(ran) == 1
    backend, op_class, _ = ran[0]
    assert backend == ("pallas" if use_pallas else "xla")
    scope = f"contract.{op_class}.{backend}"
    names = _op_names(text)
    assert any(f"/{scope}/" in n for n in names), names
    assert not any("contract." in n and f"/{scope}/" not in n
                   for n in names)


def test_contract_scope_reaches_the_backward_pass():
    w = jnp.ones((32, 24), jnp.float32)
    x = jnp.ones((16, 32), jnp.float32)
    names = _op_names(_compiled(jax.grad(
        lambda w, x: facility.contract(facility.DOT, x, w).sum()), w, x))
    # the name stack wraps the scope: transpose(jvp(contract.gemm.xla))
    assert any("transpose(" in n and "contract.gemm.xla" in n
               for n in names), names


def test_mamba2_decode_step_carries_state_and_head_scopes():
    cfg = reduced(get("mamba2-130m"))
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=2, seq_len=16)
    tok = jnp.zeros((2, 1), jnp.int32)
    scopes = _scopes(_op_names(_compiled(S.make_serve_step(cfg), params,
                                         cache, tok)))
    assert {"embed", "head", "block.ssm", "block.norm", "ssm.state",
            "ssm.conv", "weights.cast", "contract.gemm.xla"} <= scopes


def test_dense_prefill_step_carries_block_and_head_scopes():
    cfg = reduced(get("deepseek-7b"))
    params = M.init_params(cfg, jax.random.key(0))
    toks = jnp.zeros((1, 32), jnp.int32)
    scopes = _scopes(_op_names(_compiled(S.make_prefill_step(cfg), params,
                                         {"tokens": toks})))
    assert {"embed", "head", "block.attn", "block.mlp", "block.norm",
            "contract.attn.xla", "contract.gemm.xla"} <= scopes


def test_dense_decode_step_scopes_the_ring_insert():
    cfg = reduced(get("deepseek-7b"))
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=2, seq_len=16)
    tok = jnp.zeros((2, 1), jnp.int32)
    scopes = _scopes(_op_names(_compiled(S.make_serve_step(cfg), params,
                                         cache, tok)))
    assert {"block.attn", "kv.write", "weights.cast"} <= scopes


def test_train_step_carries_loss_and_optimizer_scopes():
    cfg = reduced(get("mamba2-130m"))
    opt = adamw.AdamWConfig()
    state = S.init_train_state(cfg, jax.random.key(0), opt)
    toks = jnp.zeros((2, 32), jnp.int32)
    names = _op_names(_compiled(S.make_train_step(cfg, opt), state,
                                {"tokens": toks, "labels": toks}))
    assert {"loss", "optim.adamw", "block.ssm", "ssm.state",
            "contract.gemm.xla"} <= _scopes(names)
    # the backward pass keeps the forward's names under transpose(...)
    assert any("transpose(" in n and "block.ssm" in n for n in names)


class _Held:
    """A checkpointer that keeps what it is handed."""

    def latest_step(self):
        return None

    def save_async(self, step, state):
        self.state = state

    save = save_async

    def wait(self):
        pass


def test_trainer_spans_once_per_step(tmp_path):
    step_fn = jax.jit(lambda s, b: (s + b, {"loss": (s + b).sum()}))
    s0 = jnp.zeros((4,), jnp.float32)
    step_fn(s0, s0)                     # compiled before the trace

    def batches(start):
        step = start
        while True:
            yield step, jnp.full((4,), float(step))
            step += 1

    trainer = ElasticTrainer(make_step=lambda: step_fn,
                             make_state=lambda: s0, batches=batches,
                             checkpointer=_Held(),
                             cfg=ElasticConfig(ckpt_every=1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.run(2)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(e.start_ns, e.end_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.trainer.")]
    steps = sorted(s for s in spans if s[2] == "repro.trainer.step")
    assert len(steps) == 3              # two steps, then the feed's end

    def inside(step, name):
        lo, hi, _ = step
        return sum(1 for s, e, n in spans
                   if n == "repro.trainer." + name and lo <= s and e <= hi)
    for step in steps[:2]:
        for name in ("batch", "dispatch", "wait", "log", "checkpoint"):
            assert inside(step, name) == 1, name
    assert [inside(steps[2], n) for n in ("batch", "dispatch")] == [1, 0]
    # the end-of-run save
    assert sum(1 for s in spans if s[2] == "repro.trainer.checkpoint") == 3


def test_compile_counter_counts_a_fresh_jit_once():
    compile_cache.count_compiles()
    compile_cache.count_compiles()      # registers once
    fn = jax.jit(lambda x: x * 3.25 + np.float32(0.5))
    x = jnp.arange(7, dtype=jnp.float32)
    before = compile_cache.COMPILE_COUNTS["backend_compiles"]
    fn(x).block_until_ready()
    mid = compile_cache.COMPILE_COUNTS["backend_compiles"]
    fn(x).block_until_ready()
    after = compile_cache.COMPILE_COUNTS["backend_compiles"]
    assert (mid - before, after - mid) == (1, 0)
    assert compile_cache.COMPILE_COUNTS["backend_compile_s"] > 0

