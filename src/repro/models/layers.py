"""Transformer building blocks, all matrix math routed via the MMA facility.

Pure-functional: params are nested dicts of jnp arrays; every function takes
(params, inputs) and returns outputs.  Sharding is expressed with logical
axis annotations (repro.parallel.api.shard) so the same code runs on one
CPU device and on the 512-chip production mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import facility
from repro.core.facility import DOT, Epilogue, Plan
from repro.parallel.api import shard

# ----------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------

def _dense_init(key, shape, in_axis=0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * (fan_in ** -0.5)


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------

def init_norm(cfg, d=None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_axes(cfg, d=None):
    p = {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        p["bias"] = ("embed",)
    return p


def apply_norm(p, x, cfg):
    with jax.named_scope("block.norm"):
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = xf.mean(-1, keepdims=True)
            var = ((xf - mu) ** 2).mean(-1, keepdims=True)
            out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
            out = out * p["scale"] + p["bias"]
        else:
            var = (xf * xf).mean(-1, keepdims=True)
            out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
        return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Rotary embeddings (standard + qwen2-vl M-RoPE)
# ----------------------------------------------------------------------

def _inv_freq(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))

def rope_cos_sin(positions, head_dim, theta):
    """positions (..., S) -> cos/sin (..., S, head_dim//2)."""
    inv = _inv_freq(head_dim, theta)
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions3, head_dim, theta, sections):
    """M-RoPE: positions3 (3, B, S); sections partition head_dim//2 into
    temporal/height/width frequency bands (paper arXiv:2409.12191)."""
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = _inv_freq(head_dim, theta)
    ang = positions3[..., None].astype(jnp.float32) * inv  # (3, B, S, hd/2)
    parts, start = [], 0
    for i, s in enumerate(sections):
        parts.append(ang[i, ..., start:start + s])
        start += s
    ang = jnp.concatenate(parts, axis=-1)                  # (B, S, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B, S, H, D); cos/sin (B, S, D//2) -> rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Attention (GQA, optional sliding window, optional cross-attention)
# ----------------------------------------------------------------------

def init_attention(key, cfg, d_model=None, d_out=None):
    """Projections from ``d_model`` (default the model's width) to the
    heads, and from the heads to ``d_out`` (default ``d_model``)."""
    d = d_model or cfg.d_model
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], (d, h * hd)),
        "wk": _dense_init(ks[1], (d, kv * hd)),
        "wv": _dense_init(ks[2], (d, kv * hd)),
        "wo": _dense_init(ks[3], (h * hd, d_out or d)),
    }


def attention_axes(cfg):
    return {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
            "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


# Max query rows whose attention scores are live at once.  The q-chunk
# scan bounds score memory to (B,H,chunk,Sk) but re-reads K/V per chunk;
# dryrun --qchunk overrides (0 = unchunked) for the §Perf trade study.
Q_CHUNK = 1024


def _attend(q, k, v, q_pos, kv_pos, *, causal, window, valid):
    """One query block against full K/V.  q (B,C,H,D); q_pos (1|B, C).

    Thin policy wrapper over ``facility.attend_chunk`` — the ONE chunked-
    attention implementation, shared with the xla attn lowering, so the
    ring-buffer decode path keeps the facility's conventions (notably:
    fully-masked rows yield exact zeros, never a uniform-softmax mean(V))."""
    from repro.core import precision
    cfg = facility.current()
    pol = precision.policy(cfg.ger)
    out = facility.attend_chunk(
        q.astype(pol.x_dtype), k.astype(pol.x_dtype), v.astype(pol.y_dtype),
        q_pos=q_pos, kv_pos=kv_pos, causal=causal, window=window,
        valid=valid)
    return out.astype(cfg.out_dtype)


def sdpa(q, k, v, *, causal, window=None, q_offset=0, kv_positions=None,
         valid=None, q_chunk: int = 0):
    """Scaled dot-product attention via the facility.

    q (B,Sq,H,D); k,v (B,Sk,KVH,D) — KV heads are broadcast over their
    GQA group (H % KVH == 0).  ``q_offset``: absolute position of q[0]
    (decode).  ``kv_positions`` (B,Sk) absolute positions for ring-buffer
    caches; ``valid`` (B,Sk) marks filled cache slots.

    Prefill and training (dense positions, static ``q_offset``) dispatch
    through the registry's ``attn`` op-class —
    ``facility.contract(facility.ATTN, q, k, v, plan=Plan(causal=...,
    window=..., q_offset=...))`` — so the Pallas backend runs the
    causal-bounded flash kernel and the xla backend the shardable chunked
    two-dot lowering (which bounds live scores to (B, H, chunk, Sk),
    ragged tails included).  The ring-buffer decode path (arbitrary
    ``kv_positions`` / traced offsets) keeps the explicit chunked scan
    below, which since the attn-op-class PR also handles a ragged tail
    chunk instead of silently falling back to unchunked attention.
    """
    sq, sk = q.shape[1], k.shape[1]
    if kv_positions is None and isinstance(q_offset, (int, np.integer)):
        plan = Plan(causal=causal, window=window, q_offset=int(q_offset),
                    q_chunk=q_chunk or Q_CHUNK)
        return facility.contract(
            facility.ATTN, q, k, v, plan=plan,
            masks=(valid,) if valid is not None else None)

    # Ring-buffer / traced-offset decode path: positions are data, so the
    # structural grid bounds cannot apply — mask in the score tile.
    h, nkv = q.shape[2], k.shape[2]
    k = _repeat_kv(k, h // nkv)
    v = _repeat_kv(v, h // nkv)
    if kv_positions is None:
        kv_pos = jnp.arange(sk)[None, :]                  # (1, Sk)
    else:
        kv_pos = kv_positions                             # (B, Sk)
    q_pos_full = (jnp.arange(sq) + q_offset)[None, :]     # (1, Sq)

    q_chunk = q_chunk or Q_CHUNK
    if q_chunk <= 0 or sq <= q_chunk:
        return _attend(q, k, v, q_pos_full, kv_pos, causal=causal,
                       window=window, valid=valid)

    b, _, h, d = q.shape
    nc, tail = divmod(sq, q_chunk)
    main = nc * q_chunk
    qc = q[:, :main].reshape(b, nc, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    pc = q_pos_full[:, :main].reshape(1, nc, q_chunk).transpose(1, 0, 2)

    def body(_, xs):
        qb, pb = xs
        return None, _attend(qb, k, v, pb, kv_pos, causal=causal,
                             window=window, valid=valid)

    _, out = jax.lax.scan(body, None, (qc, pc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, main, h, d)
    if tail:  # ragged tail chunk: keep the memory bound for any Sq
        out_tail = _attend(q[:, main:], k, v, q_pos_full[:, main:], kv_pos,
                           causal=causal, window=window, valid=valid)
        out = jnp.concatenate([out, out_tail], axis=1)
    return out


def apply_attention(p, x, cfg, *, cos_sin=None, kv=None, causal=None,
                    window=None, q_offset=0, kv_positions=None, valid=None,
                    cross_x=None, residual=None, q_scale=1.0):
    """Full attention block: projections + RoPE + SDPA + output proj.

    cross_x: keys/values come from the encoder stream (whisper decoder).
    ``residual`` is fused into the output projection's deprime store
    (epilogue-carrying contract Plan), saving the separate elementwise
    read-add pass.  ``q_scale`` multiplies the queries in their
    projection's deprime (its alpha), so the softmax scale is
    ``q_scale / sqrt(head_dim)``.
    Returns (out, (k, v)) so callers can build KV caches.
    """
    b, s, d = x.shape
    h, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = facility.contract(DOT, x, p["wq"], plan=Plan(alpha=q_scale)
                          ).reshape(b, s, h, hd)
    src = cross_x if cross_x is not None else x
    if kv is None:
        k = facility.contract(DOT, src, p["wk"]).reshape(
            b, src.shape[1], nkv, hd)
        v = facility.contract(DOT, src, p["wv"]).reshape(
            b, src.shape[1], nkv, hd)
    else:
        k, v = kv
    if cos_sin is not None:
        qcos, qsin, kcos, ksin = cos_sin
        q = apply_rope(q, qcos, qsin)
        if kv is None:                  # fresh keys need rotating
            k = apply_rope(k, kcos, ksin)
    q = shard(q, "batch", None, "heads", None)
    # decode caches shard the KV sequence (flash-decode); fresh keys in
    # training shard heads instead — 'model' can only appear once.
    k = shard(k, "batch", "seq_kv" if kv is not None else None,
              None if kv is not None else "kv_heads", None)
    causal = cfg.causal if causal is None else causal
    # KV heads go in un-repeated: the attn op-class broadcasts each KV
    # head over its GQA group inside the kernel's BlockSpec index maps
    # (never materializing the repeat in HBM); the ring-buffer decode
    # path repeats inside sdpa.
    out = sdpa(q, k, v, causal=causal, window=window, q_offset=q_offset,
               kv_positions=kv_positions, valid=valid)
    out = facility.contract(DOT, out.reshape(b, s, h * hd), p["wo"],
                            residual=residual)
    return out, (k, v)


# ----------------------------------------------------------------------
# MLP (gated / plain)
# ----------------------------------------------------------------------

def init_mlp(key, cfg, d_ff=None, d_model=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": _dense_init(ks[0], (d, f)), "w2": _dense_init(ks[1], (f, d))}
    if cfg.gated_mlp:
        p["w3"] = _dense_init(ks[2], (d, f))
    return p


def mlp_axes(cfg, gated=None):
    gated = cfg.gated_mlp if gated is None else gated
    p = {"w1": ("embed", "mlp"), "w2": ("mlp", "embed")}
    if gated:
        p["w3"] = ("embed", "mlp")
    return p


def apply_mlp(p, x, cfg, residual=None):
    """MLP with both epilogues fused (epilogue-carrying Plans): the activation
    rides the w1 GEMM's deprime store — computed on the fp32 accumulator,
    not the cast-down activation dtype — and the block residual rides the
    w2 GEMM's, so neither intermediate makes an extra HBM round trip."""
    h = facility.contract(DOT, x, p["w1"],
                          plan=Plan(epilogue=Epilogue(activation=cfg.act)))
    h = shard(h, "batch", None, "mlp")
    if cfg.gated_mlp:
        h = h * facility.contract(DOT, x, p["w3"])
    return facility.contract(DOT, h, p["w2"], residual=residual)


# ----------------------------------------------------------------------
# Embeddings / logits
# ----------------------------------------------------------------------

def init_embed(key, cfg):
    ks = jax.random.split(key, 2)
    p = {"tok": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                  jnp.float32) * 0.02}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(ks[1], (cfg.d_model, cfg.vocab_size))
    return p


def embed_axes(cfg):
    p = {"tok": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["unembed"] = ("embed", "vocab")
    return p


def embed_tokens(p, tokens, cfg, dtype=jnp.bfloat16):
    with jax.named_scope("embed"):
        with jax.named_scope("weights.cast"):
            table = p["tok"].astype(dtype)
        return table[tokens]


def logits(p, x, cfg):
    with jax.named_scope("head"):
        w = (p["tok"].T if cfg.tie_embeddings else p["unembed"])
        with jax.named_scope("weights.cast"):
            w = w.astype(x.dtype)
        return facility.contract(DOT, x, w,
                                 plan=Plan(out_dtype=jnp.float32))
