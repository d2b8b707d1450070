"""What the plain references share: their contraction, in two precisions,
and the seeded weights they and the program are both given.

A reference computes every contraction through ``mm(spec, a, b)`` and
everything else in float32.  ``highest`` is the reference itself: float32
operands at full precision.  ``fp8`` is its control: each operand rounded
to float8_e4m3fn under one scale per tensor (its largest magnitude maps
to 448), accumulated in float32 -- the precision below the bfloat16
operands the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def _to_fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm_highest(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def mm_fp8(spec, a, b):
    return mm_highest(spec, _to_fp8(a), _to_fp8(b))


PRECISIONS = {"highest": mm_highest, "fp8": mm_fp8}


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def normal(key, name, shape, std):
    """A float32 normal leaf, its key folded from the leaf's name so that
    each leaf is independent of the others' shapes."""
    k = jax.random.fold_in(key, sum(ord(c) * 131 ** i
                                    for i, c in enumerate(name)) % 2 ** 31)
    return jax.random.normal(k, shape, jnp.float32) * std


def embedding(key, cfg):
    """The token embedding, and the head beside it unless the
    configuration ties the head to the embedding."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    out = {"tok": normal(key, "tok", (v, d), 0.02)}
    if not cfg["tie_embeddings"]:
        out["unembed"] = normal(key, "unembed", (d, v), d ** -0.5)
    return out


def head(params, cfg):
    """The head's (d_model, vocab) weight: the embedding's transpose when
    the configuration ties them."""
    e = params["embed"]
    return e["tok"].T if cfg["tie_embeddings"] else e["unembed"]


def key_from_seed(seed: int):
    """A PRNG key from a seed of any size.  ``jax.random.key`` keeps only
    the low 32 bits of a larger seed, so two seeds 2**32 apart would give
    one key; the seed is hashed to two 32-bit words first."""
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
