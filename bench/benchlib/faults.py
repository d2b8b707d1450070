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
``stale_state``  the training step hands back the state it was given.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

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


def plant(name: str, kind: str):
    """A context that plants fault ``name`` under a job of ``kind``."""
    table = {
        ("token", "generate"): (S, "make_serve_step", _token),
        ("half_batch", "generate"): (S, "make_serve_step", _half_rows),
        ("answer", "score"): (S, "make_prefill_step", _answer),
        ("half_batch", "train"): (M, "loss_fn", _half_loss),
        ("stale_state", "train"): (S, "make_train_step", _stale),
    }
    return _patched(*table[(name, kind)])


FAULTS = {"generate": ("token", "half_batch"), "score": ("answer",),
          "train": ("stale_state", "half_batch")}
