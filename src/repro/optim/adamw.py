"""AdamW with global-norm clipping; optimizer state inherits the parameter
sharding (FSDP/ZeRO-3: m/v live on the same (data, model) shards as the
weights, so per-chip optimizer memory is params_bytes * 2 / n_chips)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: Callable[[jnp.ndarray], jnp.ndarray] | float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    # decay only matrices (>=2D); norms/biases/embeddings excluded by rank
    decay_min_ndim: int = 2


def init_state(params) -> dict[str, Any]:
    zeros = lambda p: jnp.zeros_like(p)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def apply_updates(params, grads, state, cfg: AdamWConfig):
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    with jax.named_scope("optim.adamw"):
        step = state["step"] + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
        lr = cfg.lr(step) if callable(cfg.lr) else jnp.asarray(cfg.lr)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m1 = cfg.b1 * m + (1 - cfg.b1) * g
            v1 = cfg.b2 * v + (1 - cfg.b2) * g * g
            mhat = m1 / (1 - cfg.b1 ** step.astype(jnp.float32))
            vhat = v1 / (1 - cfg.b2 ** step.astype(jnp.float32))
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if p.ndim >= cfg.decay_min_ndim:
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            new = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
            return new, m1, v1

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state["m"])
        flat_v = tdef.flatten_up_to(state["v"])
        out = [upd(p, g, m, v) for p, g, m, v
               in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return new_p, {"step": step, "m": new_m, "v": new_v}, {
            "grad_norm": gnorm, "lr": lr}
