"""Plain float32 reference of the Mamba-2 language model (arXiv:2405.21060).

Each layer: RMSNorm; one input projection to [z, x, B, C, dt];
dt = softplus(dt + dt_bias); a causal depthwise convolution of width W
over [x, B, C] with bias and SiLU; the SSD mixer with one B/C group; the
D skip; the gated RMSNorm of y * silu(z); the output projection; the
residual.  Then the final RMSNorm and the head, tied to the embedding
where the configuration says so, as the published model does.

The mixer is the SSM's own definition, in its quadratic (matrix) form:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s

with the exponent's segment sums taken by a masked cumulative sum, so no
difference of two large running sums loses precision.  No chunking, no
cache, no kernels.  ``init_weights`` lays the weights out as the program
takes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from references.common import embedding, head, normal, rmsnorm


def dims(cfg):
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    heads = d_in // cfg["ssm_headdim"]
    return d_in, heads, d_in + 2 * cfg["ssm_state"]


def init_weights(cfg, key):
    d, L = cfg["d_model"], cfg["num_layers"]
    n, w = cfg["ssm_state"], cfg["ssm_conv_width"]
    d_in, h, conv_dim = dims(cfg)
    proj = 2 * d_in + 2 * n + h
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32))
    return {
        "embed": embedding(key, cfg),
        "layers": {
            "norm": {"scale": jnp.ones((L, d), jnp.float32)},
            "mamba": {
                "in_proj": normal(key, "in_proj", (L, d, proj), d ** -0.5),
                "conv_w": normal(key, "conv_w", (L, w, conv_dim), 0.1),
                "conv_b": jnp.zeros((L, conv_dim), jnp.float32),
                "A_log": jnp.broadcast_to(a_log, (L, h)),
                "D": jnp.ones((L, h), jnp.float32),
                "dt_bias": jnp.zeros((L, h), jnp.float32),
                "norm_scale": jnp.ones((L, d_in), jnp.float32),
                "out_proj": normal(key, "out_proj", (L, d_in, d),
                                   d_in ** -0.5),
            }},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


def _segsum(a):
    """a (..., T) -> (..., T, T): out[t, s] = sum a[s+1..t] for s <= t,
    -inf above the diagonal."""
    t = a.shape[-1]
    rows = jnp.broadcast_to(a[..., :, None], a.shape + (t,))
    rows = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), rows, 0.0)
    seg = jnp.cumsum(rows, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def _layer(lp, h, cfg, mm):
    d_in, heads, _ = dims(cfg)
    n, p, w = cfg["ssm_state"], cfg["ssm_headdim"], cfg["ssm_conv_width"]
    eps = cfg["norm_eps"]
    b, t, _ = h.shape
    m = lp["mamba"]
    u = rmsnorm(h, lp["norm"]["scale"], eps)
    proj = mm("btd,de->bte", u, m["in_proj"])
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])                    # (b, t, h)
    a = -jnp.exp(m["A_log"])                                   # (h,)
    padded = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + t] * m["conv_w"][k] for k in range(w))
    xbc = jax.nn.silu(conv + m["conv_b"])
    x, bm, cm = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    x = x.reshape(b, t, heads, p)
    decay = jnp.exp(_segsum(jnp.moveaxis(dt * a, -1, -2)))     # (b,h,t,s)
    cb = mm("btn,bsn->bts", cm, bm)
    y = mm("bhts,bshp->bthp", cb[:, None] * decay, x * dt[..., None])
    y = y + x * m["D"][:, None]
    y = y.reshape(b, t, d_in) * jax.nn.silu(z)
    y = rmsnorm(y, m["norm_scale"], eps)
    return h + mm("bte,ed->btd", y, m["out_proj"])


def hidden(params, tokens, cfg, mm, remat=False):
    """Final-norm hidden states (b, t, d) of a token batch."""
    body = lambda c, lp: (_layer(lp, c, cfg, mm), None)  # noqa: E731
    if remat:
        body = jax.checkpoint(body)
    h = params["embed"]["tok"][tokens]
    h, _ = jax.lax.scan(body, h, params["layers"])
    return rmsnorm(h, params["final_norm"]["scale"], cfg["norm_eps"])


def logits(params, tokens, cfg, mm):
    """Logits at every position, (b, t, vocab)."""
    return mm("btd,dv->btv", hidden(params, tokens, cfg, mm),
              head(params, cfg))


def nll_sum(params, tokens, labels, cfg, mm):
    """Summed next-token negative log-likelihood of a batch."""
    lg = mm("btd,dv->btv", hidden(params, tokens, cfg, mm, remat=True),
            head(params, cfg))
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).sum()
