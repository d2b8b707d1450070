"""Plain float32 reference of the Zamba2 hybrid language model (Zyphra's
Zamba2-7B, arXiv:2411.15242), as transformers' ``modeling_zamba2``
defines it.

The stage holds layers 0 .. num_layers-1 of the published stack.  Each is
a Mamba2 layer; before the Mamba of each layer in ``hybrid_layer_ids``,
call j of the stage runs shared block j mod ``num_mem_blocks``:

    x = RMSNorm_in(concat(h, emb0))            over 2 d; emb0 the embedding
    a = o(Attn(q(x), k(x), v(x)))              rotary over the whole head,
                                               causal softmax scaled by
                                               (head_dim / 2) ** -0.5
    a = RMSNorm_ff(a)
    m = W_down(gelu(W_gate a + Bg_j A_j a) * (W_up a + Bu_j A_j a))
    t = L_j m                                  (gelu: the exact erf form)
    h = h + Mamba_i(RMSNorm(h + t))            (the residual is h)

and every other layer is h = h + Mamba_i(RMSNorm(h)).  Mamba_i: one input
projection to [z, x, B, C, dt] with ``ssm_ngroups`` groups of B and C;
dt = softplus(dt + dt_bias); a causal depthwise convolution of width W
with bias and SiLU over [x, B, C]; the SSM run token by token,

    s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t,    y_t = C_t . s_t + D x_t,

head k reading group k * G / H; the gated RMSNorm of y * silu(z) over each
group's d_inner / G channels; the output projection.  Then the final
RMSNorm and the head.  No chunks, no cache, no kernels; attention runs
one block of queries at a time so that the scores of a long prompt fit.

Departures from the published model, each on purpose:

* ``tie_embeddings``: the head is the embedding's transpose, as published
  (``Zamba2ForCausalLM`` ties ``lm_head.weight``).
* transformers' pure-PyTorch fallback clamps dt below at
  ``time_step_min``; the published CUDA kernels, which run the model,
  apply no limit (``time_step_limit`` is null), and neither does this
  reference or the program.
* the published fused ``gate_up_proj`` and the adapters' second matrices
  are held as separate gate and up halves, as the program takes them.
* weights are random from a seed, in the program's layout
  (``init_weights``); dt_bias and A follow the published initialisation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from references.common import embedding, head, normal, rmsnorm


def dims(cfg):
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    heads = d_in // cfg["ssm_headdim"]
    bc = cfg["ssm_ngroups"] * cfg["ssm_state"]
    return d_in, heads, bc


def calls(cfg):
    """The hybrid layers this stage holds."""
    return [i for i in cfg["hybrid_layer_ids"] if i < cfg["num_layers"]]


def init_weights(cfg, key):
    d, L, f = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    n_blocks, n_calls = cfg["num_mem_blocks"], len(calls(cfg))
    r, w = cfg["adapter_rank"], cfg["ssm_conv_width"]
    d_in, h, bc = dims(cfg)
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg[
        "head_dim"]
    ones = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    # published: dt log-uniform in [1e-3, 0.1] through softplus's inverse;
    # A = 1 .. heads
    dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 7), (L, h))
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "embed": embedding(key, cfg),
        "layers": {
            "norm": {"scale": ones(L, d)},
            "mamba": {
                "in_proj": normal(key, "in_proj",
                                  (L, d, 2 * d_in + 2 * bc + h), d ** -0.5),
                "conv_w": normal(key, "conv_w", (L, w, d_in + 2 * bc), 0.1),
                "conv_b": jnp.zeros((L, d_in + 2 * bc), jnp.float32),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, h + 1, dtype=jnp.float32)), (L, h)),
                "D": ones(L, h),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_scale": ones(L, d_in),
                "out_proj": normal(key, "out_proj", (L, d_in, d),
                                   d_in ** -0.5),
            }},
        "final_norm": {"scale": ones(d)},
        "shared": {
            "attn_norm": {"scale": ones(n_blocks, 2 * d)},
            "attn": {
                "wq": normal(key, "wq", (n_blocks, 2 * d, q), (2 * d) ** -0.5),
                "wk": normal(key, "wk", (n_blocks, 2 * d, kv),
                             (2 * d) ** -0.5),
                "wv": normal(key, "wv", (n_blocks, 2 * d, kv),
                             (2 * d) ** -0.5),
                "wo": normal(key, "wo", (n_blocks, q, d), q ** -0.5)},
            "mlp_norm": {"scale": ones(n_blocks, d)},
            "mlp": {"w1": normal(key, "w1", (n_blocks, d, f), d ** -0.5),
                    "w2": normal(key, "w2", (n_blocks, f, d), f ** -0.5),
                    "w3": normal(key, "w3", (n_blocks, d, f), d ** -0.5)}},
        "calls": {
            "adapter_in": normal(key, "adapter_in", (n_calls, d, r),
                                 d ** -0.5),
            "adapter_gate": normal(key, "adapter_gate", (n_calls, r, f),
                                   r ** -0.5),
            "adapter_up": normal(key, "adapter_up", (n_calls, r, f),
                                 r ** -0.5),
            "linear": normal(key, "linear", (n_calls, d, d), d ** -0.5)},
    }


def _ssm(x, dt, a, bm, cm, groups, mm):
    """The recurrence, one token at a time.  x (b,t,h,p); dt (b,t,h);
    a (h,); bm, cm (b,t,G*n).  Returns y (b,t,h,p) without the D skip."""
    b, t, h, p = x.shape
    k = h // groups
    split = lambda v: jnp.moveaxis(v.reshape(b, t, groups, -1), 1, 0)  # noqa: E731
    xs = jnp.moveaxis(x.reshape(b, t, groups, k, p), 1, 0)
    dts = split(dt)                                       # (t, b, G, k)
    decay = a.reshape(groups, k)

    def step(s, inp):                                     # s (b,G,k,n,p)
        xt, dtt, bt, ct = inp
        s = (s * jnp.exp(dtt * decay)[..., None, None]
             + mm("bgn,bgkp->bgknp", bt, xt * dtt[..., None]))
        return s, mm("bgn,bgknp->bgkp", ct, s)

    s0 = jnp.zeros((b, groups, k, bm.shape[-1] // groups, p), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xs, dts, split(bm), split(cm)))
    return jnp.moveaxis(y, 0, 1).reshape(b, t, h, p)


def _mamba(lp, h, cfg, mm, t=None):
    d_in, heads, bc = dims(cfg)
    p, w, eps = cfg["ssm_headdim"], cfg["ssm_conv_width"], cfg["norm_eps"]
    groups = cfg["ssm_ngroups"]
    b, n_tok, _ = h.shape
    m = lp["mamba"]
    u = rmsnorm(h if t is None else h + t, lp["norm"]["scale"], eps)
    proj = mm("btd,de->bte", u, m["in_proj"])
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * bc], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])                # (b, t, h)
    padded = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + n_tok] * m["conv_w"][k] for k in range(w))
    xbc = jax.nn.silu(conv + m["conv_b"])
    x, bm, cm = jnp.split(xbc, [d_in, d_in + bc], axis=-1)
    x = x.reshape(b, n_tok, heads, p)
    y = _ssm(x, dt, -jnp.exp(m["A_log"]), bm, cm, groups, mm)
    y = (y + x * m["D"][:, None]).reshape(b, n_tok, d_in) * jax.nn.silu(z)
    y = rmsnorm(y.reshape(b, n_tok, groups, -1), 1.0, eps).reshape(
        b, n_tok, d_in) * m["norm_scale"]
    return h + mm("bte,ed->btd", y, m["out_proj"])


def _rope(x, theta):
    """x (b, t, h, hd), positions 0..t-1, rotate-half over the whole
    head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mm, block=512):
    """Causal attention over blocks of ``block`` queries (a divisor of
    the length), scaled by (head_dim / 2) ** -0.5.  q (b,t,h,hd); k, v
    (b,t,kvh,hd)."""
    b, t, h, hd = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    block = math.gcd(t, block)
    keys = jnp.arange(t)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = mm("bqhd,bkhd->bhqk", qb, k) * (hd / 2) ** -0.5
        rows = i * block + jnp.arange(block)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(t // block))         # (nb,b,blk,h,hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * hd)


def _call(sp, cp, h, emb0, cfg, mm):
    """One call of a shared block: t, which its hybrid layer adds to the
    input of its Mamba."""
    b, n_tok, _ = h.shape
    hd, eps = cfg["head_dim"], cfg["norm_eps"]
    at = sp["attn"]
    x = rmsnorm(jnp.concatenate([h, emb0], -1), sp["attn_norm"]["scale"], eps)
    q = mm("btd,de->bte", x, at["wq"]).reshape(b, n_tok, -1, hd)
    k = mm("btd,de->bte", x, at["wk"]).reshape(b, n_tok, -1, hd)
    v = mm("btd,de->bte", x, at["wv"]).reshape(b, n_tok, -1, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    a = mm("bte,ed->btd", _attention(q, k, v, mm), at["wo"])
    a = rmsnorm(a, sp["mlp_norm"]["scale"], eps)
    lo = mm("btd,dr->btr", a, cp["adapter_in"])
    ml = sp["mlp"]
    g = mm("btd,df->btf", a, ml["w1"]) + mm("btr,rf->btf", lo,
                                            cp["adapter_gate"])
    u = mm("btd,df->btf", a, ml["w3"]) + mm("btr,rf->btf", lo,
                                            cp["adapter_up"])
    m = mm("btf,fd->btd", jax.nn.gelu(g, approximate=False) * u, ml["w2"])
    return mm("btd,de->bte", m, cp["linear"])


def hidden(params, tokens, cfg, mm):
    """Final-norm hidden states (b, t, d) of a token batch."""
    pick = lambda tree, i: jax.tree.map(lambda w: w[i], tree)  # noqa: E731
    layers = params["layers"]

    def run(h, lo, hi):
        if hi <= lo:
            return h
        seg = jax.tree.map(lambda w: w[lo:hi], layers)
        h, _ = jax.lax.scan(lambda c, lp: (_mamba(lp, c, cfg, mm), None), h,
                            seg)
        return h

    emb0 = params["embed"]["tok"][tokens]
    held = calls(cfg)
    bounds = held + [cfg["num_layers"]]
    h = run(emb0, 0, bounds[0])
    for j, i in enumerate(held):
        t = _call(pick(params["shared"], j % cfg["num_mem_blocks"]),
                  pick(params["calls"], j), h, emb0, cfg, mm)
        h = run(_mamba(pick(layers, i), h, cfg, mm, t), i + 1, bounds[j + 1])
    return rmsnorm(h, params["final_norm"]["scale"], cfg["norm_eps"])


def logits(params, tokens, cfg, mm):
    """Logits at every position, (b, t, vocab)."""
    return mm("btd,dv->btv", hidden(params, tokens, cfg, mm),
              head(params, cfg))


def last_logits(params, tokens, cfg, mm):
    """Next-token logits after the last position, (b, vocab)."""
    return mm("bd,dv->bv", hidden(params, tokens, cfg, mm)[:, -1],
              head(params, cfg))
