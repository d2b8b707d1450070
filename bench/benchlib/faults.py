"""Faults planted under the timed path, to show that a run the check
should refuse comes out not correct.  Only the tests and
``bench/readings.py`` plant them; ``bench/run.py`` never does.

Each fault replaces one program function for the extent of a ``with``:

``token``        the decode step's tokens are each moved to the next id,
                 where the step produces them;
``answer``       a scored answer's token 0 is raised above its best logit;
``half_batch``   the loss is taken over the first half of the batch only
                 (training), or the second half of a generated batch
                 repeats the first half's tokens (generation);
``stale_state``  the training step hands back the state it was given;
``exchange``     on a mesh, each chip keeps only its own block of a
                 sharded gemm's activation rows and takes it for every
                 block: the gather between chips before the gemm is
                 left out.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp
from jax import lax

from repro.core import lowering, packing
from repro.models import model as M
from repro.train import steps as S


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _token(orig):
    def make_serve_step(cfg, **kw):
        step = orig(cfg, **kw)

        def serve_step(params, cache, tokens, key=None):
            nxt, logits, cache = step(params, cache, tokens, key)
            return (nxt + 1) % cfg.vocab_size, logits, cache
        return serve_step
    return make_serve_step


def _half_rows(orig):
    def make_serve_step(cfg, **kw):
        step = orig(cfg, **kw)

        def serve_step(params, cache, tokens, key=None):
            nxt, logits, cache = step(params, cache, tokens, key)
            half = nxt.shape[0] // 2
            return jnp.concatenate([nxt[:half], nxt[:nxt.shape[0] - half]]
                                   ), logits, cache
        return serve_step
    return make_serve_step


def _answer(orig):
    def make_prefill_step(cfg):
        step = orig(cfg)

        def prefill_step(params, batch):
            logits, caches = step(params, batch)
            return logits.at[:, 0].set(logits.max(-1) + 1.0), caches
        return prefill_step
    return make_prefill_step


def _half_loss(orig):
    def loss_fn(params, batch, cfg):
        half = batch["tokens"].shape[0] // 2
        return orig(params, {k: v[:half] for k, v in batch.items()}, cfg)
    return loss_fn


def _stale(orig):
    def make_train_step(cfg, opt_cfg, **kw):
        step = orig(cfg, opt_cfg, **kw)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return train_step
    return make_train_step


def _own_rows_only(orig):
    def _shard_wrap(sp):
        wrap = orig(sp)
        parts = dict(sp.mesh.shape).get("model", 1)

        def own_rows(fn):
            def run(op):
                p = op.parsed
                if p is None or parts == 1 or packing.is_packed(op.x):
                    return fn(op)
                rows = [i for i, d in enumerate(p.x_labels)
                        if d in p.x_free and op.x.shape[i] % parts == 0]
                if not rows:
                    return fn(op)
                axis = max(rows, key=lambda i: op.x.shape[i])
                size = op.x.shape[axis] // parts
                mine = lax.dynamic_slice_in_dim(
                    op.x, lax.axis_index("model") * size, size, axis)
                return fn(dataclasses.replace(
                    op, x=jnp.concatenate([mine] * parts, axis)))
            return wrap(run)
        return own_rows
    return _shard_wrap


def plant(name: str, kind: str):
    """A context that plants fault ``name`` under a job of ``kind``."""
    table = {
        ("token", "generate"): (S, "make_serve_step", _token),
        ("half_batch", "generate"): (S, "make_serve_step", _half_rows),
        ("answer", "score"): (S, "make_prefill_step", _answer),
        ("exchange", "score"): (lowering, "_shard_wrap", _own_rows_only),
        ("half_batch", "train"): (M, "loss_fn", _half_loss),
        ("stale_state", "train"): (S, "make_train_step", _stale),
    }
    return _patched(*table[(name, kind)])


FAULTS = {"generate": ("token", "half_batch"), "score": ("answer",),
          "train": ("stale_state", "half_batch")}


def of(cell) -> tuple:
    """The faults a cell (``spec.Cell``) can have: its job's, and on more
    than one chip the exchange between them."""
    return FAULTS[cell.traffic["job"]] + (
        ("exchange",) if cell.workload["chips"] > 1 else ())
