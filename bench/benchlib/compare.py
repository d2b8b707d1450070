"""The comparisons that decide ``correct``, and the reference's optimizer.

Served tokens are judged by the reference: the gap by which a served
token's reference logit lies below the reference's best logit at that
position (0 where the program chose the reference's own best).  A scored
answer (a whole logit vector) is judged by its largest error against the
reference's logits, over the reference's largest logit.  A training
run is judged by its first steps' losses, the norm of each leaf of its
first (clipped) gradient, and the norm of each leaf's change after the
compared steps, each against the reference's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from references import common


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _served_gaps(logits_fn, weights, toks, served, cfg_items, control):
    cfg = dict(cfg_items)
    ref = logits_fn(weights, toks, cfg, common.mm_highest)
    best = ref.max(-1)
    pick = lambda t: jnp.take_along_axis(ref, t[..., None], -1)[..., 0]  # noqa: E731
    gaps = {"program": (best - pick(served)).max()}
    if control:
        low = logits_fn(weights, toks, cfg, common.mm_fp8)
        gaps["control"] = (best - pick(low.argmax(-1))).max()
    return gaps


def served_gaps(logits_fn, weights, toks, served, cfg: dict, name: str,
                control: bool) -> dict:
    """``logits_fn(weights, toks, cfg, mm)`` gives the logits that chose
    ``served`` (same leading shape).  Returns {name: widest gap}, and with
    ``control`` the control's under "control"."""
    got = _served_gaps(logits_fn, weights, toks, served,
                       tuple(sorted(cfg.items())), control)
    out = {name: float(got["program"])}
    if control:
        out["control"] = {name: float(got["control"])}
    return out


def logit_error(ref: np.ndarray, got: np.ndarray) -> float:
    """A scored answer's largest logit error over the reference's largest
    logit (both (vocab,))."""
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def _leaf_gap(prog, ref, keep=None) -> float:
    """Worst leaf: |norm_p - norm_r| over the larger of norm_r and the
    median leaf's norm_r."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    med = float(np.median(ref))
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, med)))


# A leaf whose first reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: it is left out of the
# update's comparison.
STILL = 1e-3


def train_numbers(prog: dict, ref: dict) -> dict:
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    keep = ref["grad"] >= STILL * np.median(ref["grad"])
    return {"loss_gap": float(np.max(np.abs(loss_p - loss_r)
                                     / np.abs(loss_r))),
            "grad_gap": _leaf_gap(prog["grad"], ref["grad"]),
            "update_gap": _leaf_gap(prog["update"], ref["update"], keep)}


def lr_at(step: int, o: dict) -> float:
    """Warm-up then cosine, as the configuration's optimizer states."""
    peak, warm, total = o["lr"], o["warmup_steps"], o["total_steps"]
    if step < warm:
        return peak * step / warm
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (o["final_frac"] + (1 - o["final_frac"]) * 0.5
                   * (1 + math.cos(math.pi * prog)))


def adamw_step(w, grads, m, v, step: int, o: dict):
    """One AdamW step with global-norm clipping and decoupled weight
    decay on matrices.  Returns (w, m, v, the clipped gradient)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    if m is None:
        m = jax.tree.map(jnp.zeros_like, g)
        v = jax.tree.map(jnp.zeros_like, g)
    b1, b2, lr = o["b1"], o["b2"], lr_at(step, o)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

    def upd(p, mi, vi):
        delta = (mi / (1 - b1 ** step)) / (jnp.sqrt(vi / (1 - b2 ** step))
                                           + o["eps"])
        if p.ndim >= 2:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta
    return jax.tree.map(upd, w, m, v), m, v, g
