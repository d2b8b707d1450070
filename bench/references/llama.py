"""Plain float32 reference of a llama-style dense decoder (the DeepSeek LLM
architecture, arXiv:2401.02954).

Each layer: RMSNorm; multi-head attention with grouped KV heads, rotary
embeddings in the rotate-half convention (theta ``rope_theta``), a causal
softmax scaled by 1/sqrt(head_dim); the output projection and residual;
RMSNorm; the SwiGLU MLP silu(x W1) * (x W3) W2 and residual.  Then the
final RMSNorm and the head (tied to the embedding where the
configuration says so).  No cache, no kernels; attention runs
one head group at a time so that the scores of a long prompt fit.
``init_weights`` lays the weights out as the program takes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from references.common import embedding, head, normal, rmsnorm


def init_weights(cfg, key):
    d, L, f = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    ones = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    return {
        "embed": embedding(key, cfg),
        "layers": {
            "attn_norm": {"scale": ones(L, d)},
            "attn": {"wq": normal(key, "wq", (L, d, q), d ** -0.5),
                     "wk": normal(key, "wk", (L, d, kv), d ** -0.5),
                     "wv": normal(key, "wv", (L, d, kv), d ** -0.5),
                     "wo": normal(key, "wo", (L, q, d), q ** -0.5)},
            "mlp_norm": {"scale": ones(L, d)},
            "mlp": {"w1": normal(key, "w1", (L, d, f), d ** -0.5),
                    "w2": normal(key, "w2", (L, f, d), f ** -0.5),
                    "w3": normal(key, "w3", (L, d, f), d ** -0.5)}},
        "final_norm": {"scale": ones(d)},
    }


def _rope(x, theta):
    """x (b, t, h, hd), positions 0..t-1."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm):
    """Causal attention, one KV head (and its query group) at a time."""
    b, t, h, hd = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, t, kvh, h // kvh, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one(_, qkv):
        qg, kg, vg = qkv                      # (b,t,g,hd), (b,t,hd) x2
        s = mm("btgd,bsd->bgts", qg, kg) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return None, mm("bgts,bsd->btgd", p, vg)

    _, out = jax.lax.scan(one, None, (jnp.moveaxis(q, 2, 0),
                                      jnp.moveaxis(k, 2, 0),
                                      jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, h * hd)


def _layer(lp, h, cfg, mm):
    b, t, _ = h.shape
    hd, eps = cfg["head_dim"], cfg["norm_eps"]
    a = lp["attn"]
    u = rmsnorm(h, lp["attn_norm"]["scale"], eps)
    q = mm("btd,de->bte", u, a["wq"]).reshape(b, t, -1, hd)
    k = mm("btd,de->bte", u, a["wk"]).reshape(b, t, -1, hd)
    v = mm("btd,de->bte", u, a["wv"]).reshape(b, t, -1, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    h = h + mm("bte,ed->btd", _attention(q, k, v, mm), a["wo"])
    u = rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    m = lp["mlp"]
    g = jax.nn.silu(mm("btd,df->btf", u, m["w1"])) * mm("btd,df->btf", u,
                                                         m["w3"])
    return h + mm("btf,fd->btd", g, m["w2"])


def last_logits(params, tokens, cfg, mm):
    """Next-token logits after the last position, (b, vocab)."""
    h = params["embed"]["tok"][tokens]
    h, _ = jax.lax.scan(lambda c, lp: (_layer(lp, c, cfg, mm), None), h,
                        params["layers"])
    h = rmsnorm(h[:, -1], params["final_norm"]["scale"], cfg["norm_eps"])
    return mm("bd,dv->bv", h, head(params, cfg))
