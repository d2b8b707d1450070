"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

The SSD chunked algorithm is itself a sequence of small-matrix rank-k
updates (intra-chunk "attention-like" products, chunk-state outer products,
inter-chunk state propagation), which is why the paper's MMA claim — "the
instructions can be used as building blocks of other computations" —
extends to attention-free models: every einsum below routes through the
facility and lowers to resident-accumulator MXU loops.

Layout: x (B, L, H, P) with H = d_inner / headdim heads, P = headdim,
N = d_state, G = ``ssm_ngroups`` B/C groups: head k reads group k*G/H,
and the gated output norm is taken over each group's d_inner/G channels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import facility
from repro.core.facility import DOT, Epilogue, Plan
from repro.core.precision import Ger
from repro.models import layers
from repro.parallel.api import shard


def dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_headdim
    conv_dim = d_in + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return d_in, nheads, conv_dim


def init_mamba2(key, cfg):
    d = cfg.d_model
    d_in, nheads, conv_dim = dims(cfg)
    n = cfg.ssm_ngroups * cfg.ssm_state
    ks = jax.random.split(key, 4)
    return {
        # fused input projection: [z, x, B, C, dt]
        "in_proj": layers._dense_init(
            ks[0], (d, 2 * d_in + 2 * n + nheads)),
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_dim),
                                    jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads,
                                      dtype=jnp.float32)),
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), jnp.float32),
        "out_proj": layers._dense_init(ks[3], (d_in, d)),
    }


def mamba2_axes(cfg):
    return {"in_proj": ("embed", "mlp"), "conv_w": (None, "mlp"),
            "conv_b": ("mlp",), "A_log": ("ssm_heads",),
            "D": ("ssm_heads",), "dt_bias": ("ssm_heads",),
            "norm_scale": ("mlp",), "out_proj": ("mlp", "embed")}


def _split_proj(proj, cfg):
    d_in, nheads, _ = dims(cfg)
    n = cfg.ssm_ngroups * cfg.ssm_state
    z, xbc_dt = jnp.split(proj, [d_in], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [d_in + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc, conv_w, conv_b, conv_state=None):
    """Depthwise causal conv, width W.  conv_state: (B, W-1, C) history.

    Routed through the facility's ``conv`` op-class
    (``facility.CONV1D_DEPTHWISE``): the decode path prepends the ring
    history and runs VALID; the train path is the architected causal
    (left) padding.  Bias + silu fuse into the deprime store via the
    epilogue contract; F32GER keeps the tap products in f32, matching the
    old hand-rolled shift-and-sum numerics.
    """
    with jax.named_scope("ssm.conv"):
        w = conv_w.shape[0]
        if conv_state is not None:
            xin = jnp.concatenate([conv_state.astype(xbc.dtype), xbc],
                                  axis=1)
            padding = "valid"
        else:
            xin = xbc
            padding = "causal"
        out = facility.contract(
            facility.CONV1D_DEPTHWISE, xin, conv_w, bias=conv_b,
            plan=Plan(ger=Ger.F32GER, padding=padding,
                      epilogue=Epilogue(bias=True, activation="silu"),
                      out_dtype=xbc.dtype))
        if conv_state is not None:
            return out, xin[:, -(w - 1):, :]
        # New history = last W-1 input frames, zero-prefixed for short
        # seqs (the causal padding itself stays inside the conv lowering).
        l = xbc.shape[1]
        state = (xbc[:, -(w - 1):, :] if l >= w - 1
                 else jnp.pad(xbc, ((0, 0), (w - 1 - l, 0), (0, 0))))
        return out, state


def ssd_chunked(x, dt, A, B, C, D, chunk, return_state: bool = False):
    """SSD scan (ssd_minimal_discrete, Mamba2 paper listing 1).

    x (b,l,h,p); dt (b,l,h) [post-softplus]; A (h,) negative decay, or
    any array that broadcasts against dt; B, C (b,l,n); D (h,), or any
    array whose ``D[:, None]`` broadcasts against (b,nc,chunk,h,p).
    Returns y (b,l,h,p) [, final_state (b,h,n,p)] — the
    final state is the prefill->decode handoff.
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    # discretize
    dA = dt * A                                           # (b,l,h)
    xt = (x * dt[..., None]).astype(x.dtype)              # dt-weighted input
    r = lambda t: t.reshape(b, nc, chunk, *t.shape[2:])
    xc, dAc = r(xt), r(dA)
    Bc, Cc = r(B), r(C)
    dAc = dAc.transpose(0, 1, 3, 2)                       # (b,nc,h,L)
    dA_cum = jnp.cumsum(dAc, axis=-1)                     # (b,nc,h,L)

    # 1) intra-chunk (the "quadratic attention" branch of the duality):
    # scores C·Bᵀ under the causal mask exp(cum_l - cum_s), times x
    y_intra = facility.contract(facility.SSD, Cc, Bc, xc,
                                dA_cum)                   # (b,nc,L,h,p)

    # 2) chunk states: decayed outer products B^T (dt x)
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)     # (b,nc,h,L)
    states = facility.contract(
        "bcln,bclhp->bchnp",
        Bc, (xc * decay_states.transpose(0, 1, 3, 2)[..., None]).astype(x.dtype),
        plan=Plan(out_dtype=jnp.float32))                 # (b,nc,h,n,p)

    # 3) inter-chunk recurrence (sequential scan over chunks)
    chunk_decay = jnp.exp(dA_cum[..., -1])                # (b,nc,h)

    def step(carry, inp):
        st, dec = inp                                     # (b,h,n,p), (b,h)
        with jax.named_scope("ssm.state"):
            new = carry * dec[..., None, None] + st
        return new, carry                                  # emit *previous*

    init = jnp.zeros((b, h, n, p), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4),
                     chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (b,nc,h,n,p)

    # 4) state -> output contribution
    state_decay = jnp.exp(dA_cum)                         # (b,nc,h,L)
    y_inter = facility.contract(
        "bcln,bchnp->bclhp", Cc,
        prev_states.astype(x.dtype)) * state_decay.transpose(
            0, 1, 3, 2)[..., None].astype(x.dtype)

    y = (y_intra.astype(jnp.float32) + y_inter.astype(jnp.float32)
         + x.reshape(b, nc, chunk, h, p).astype(jnp.float32) * D[:, None])
    y = y.reshape(b, l, h, p).astype(x.dtype)
    if return_state:
        # scan carry after the last iteration = state after all chunks
        return y, final_state
    return y


def _fold_groups(t, groups):
    """(b, l, groups * k, ...) -> (b * groups, l, k, ...): each group's
    part of the third axis becomes a sequence of its own."""
    b, l = t.shape[:2]
    t = t.reshape(b, l, groups, -1, *t.shape[3:])
    return jnp.swapaxes(t, 1, 2).reshape(b * groups, l, *t.shape[3:])


def ssd_grouped(x, dt, A, B, C, D, chunk, groups):
    """``ssd_chunked`` with ``groups`` B/C groups, B, C (b,l,groups*n):
    the heads of one group read only its B and C, so each group's heads
    run as one more sequence of the batch.  Returns y and the final
    state."""
    if groups == 1:
        return ssd_chunked(x, dt, A, B, C, D, chunk, return_state=True)
    b, l, h, p = x.shape
    k = h // groups

    def per_seq(v, *tail):   # (h,) -> (b * groups, 1, k, *tail)
        v = jnp.broadcast_to(v.reshape(groups, k), (b, groups, k))
        return v.reshape(b * groups, 1, k, *tail)

    y, final = ssd_chunked(
        _fold_groups(x, groups), _fold_groups(dt, groups), per_seq(A),
        _fold_groups(B, groups), _fold_groups(C, groups), per_seq(D, 1),
        chunk, return_state=True)
    y = jnp.swapaxes(y.reshape(b, groups, l, k, p), 1, 2).reshape(b, l, h, p)
    return y, final.reshape(b, h, *final.shape[2:])


def _group_rms(g, groups, eps):
    """g over its root mean square, taken over each of ``groups`` equal
    parts of the last axis."""
    if groups > 1:
        return _group_rms(g.reshape(*g.shape[:-1], groups, -1), 1,
                          eps).reshape(g.shape)
    return g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)


def _write_conv(conv_all, conv_state, layer):
    return jax.lax.dynamic_update_index_in_dim(
        conv_all, conv_state.astype(conv_all.dtype), layer, 0)


def _update_state(ssm_all, sstate, dA, upd, layer):
    """Layer ``layer``'s new SSM state ``sstate * exp(dt A) + upd`` written
    into the stacked ``ssm_all``, and read back from it for the read-out:
    reading the new state from the old slice would keep that slice live
    past the write and force XLA to copy the buffer."""
    ssm_all = jax.lax.dynamic_update_index_in_dim(
        ssm_all, sstate * dA[..., None, None] + upd, layer, 0)
    return ssm_all, jax.lax.dynamic_index_in_dim(ssm_all, layer,
                                                 keepdims=False)


# Run eagerly (``model.eager_layers()``), op by op, an update would allocate
# and write a whole new stacked buffer; instead each runs as one program
# that donates the buffer, the eager step's own copy (``own_decode_state``).
_DONATED = {f: jax.jit(f, donate_argnums=0)
            for f in (_write_conv, _update_state)}


def _in_place(fn, buf, *args):
    """``fn(buf, *args)``, which updates the stacked decode buffer ``buf``:
    traced, XLA updates the carried buffer in place; eagerly, ``fn`` runs
    as a program that donates it."""
    if isinstance(buf, jax.core.Tracer):
        return fn(buf, *args)
    return _DONATED[fn](buf, *args)


def own_decode_state(cache):
    """The stacked ``ssm`` and ``conv`` decode buffers of ``cache``, for the
    blocks to update in place.  Run eagerly, each block's writes donate the
    buffer they are given (``_in_place``), so the step first takes one copy
    of its own and leaves the caller's cache intact; traced, nothing is
    copied."""
    ssm, conv = cache["ssm"], cache["conv"]
    if not isinstance(ssm, jax.core.Tracer):
        ssm, conv = jnp.copy(ssm), jnp.copy(conv)
    return ssm, conv


def apply_mamba2(p, x, cfg, state=None, layer=None):
    """Full block. Training/prefill: state=None, seq scanned chunked.
    Decode: x (B,1,d), ``state`` the whole stacked decode state
    {'ssm': (L,B,h,n,p), 'conv': (L,B,W-1,C)} and ``layer`` this block's
    index into it -> (out, state with the layer's slices updated in place).
    """
    b, l, d = x.shape
    d_in, nheads, conv_dim = dims(cfg)
    groups = cfg.ssm_ngroups
    n = groups * cfg.ssm_state
    proj = facility.contract(DOT, x, p["in_proj"])
    z, xbc, dt_raw = _split_proj(proj, cfg)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if state is None:
        xbc_raw = xbc
        xbc, _ = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        xs, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)
        xh = xs.reshape(b, l, nheads, cfg.ssm_headdim)
        xh = shard(xh, "batch", None, "ssm_heads", None)
        chunk = min(cfg.ssm_chunk, l)   # short-sequence smoke/training
        y, final = ssd_grouped(xh, dt, A, B, C, p["D"], chunk, groups)
        # prefill -> decode handoff: final SSM state + conv tail
        w = cfg.ssm_conv_width
        new_state = {"ssm": final,
                     "conv": jnp.pad(xbc_raw, ((0, 0), (w - 1, 0), (0, 0))
                                     )[:, -(w - 1):, :]}
    else:
        # The stacked buffers are read and written one layer slice at a
        # time, so a donated cache is updated in place: no whole-state
        # stacking or copy per step.
        conv_all = state["conv"]
        xbc, conv_state = _causal_conv(
            xbc, p["conv_w"], p["conv_b"],
            conv_state=jax.lax.dynamic_index_in_dim(conv_all, layer,
                                                    keepdims=False))
        with jax.named_scope("ssm.conv"):
            conv_all = _in_place(_write_conv, conv_all, conv_state, layer)
        xs, B, C = jnp.split(xbc, [d_in, d_in + n], axis=-1)
        xh = xs.reshape(b, l, nheads, cfg.ssm_headdim)
        # single-token recurrent update: s <- exp(dt A) s + dt B x; each
        # group's heads, with its B and C, as one more row of the batch
        dA = jnp.exp(dt[:, 0] * A)                        # (b,h)
        ssm_all = state["ssm"]                            # (L,b,h,n,p)
        bg, k = b * groups, nheads // groups
        with jax.named_scope("ssm.state"):
            sstate = jax.lax.dynamic_index_in_dim(ssm_all, layer,
                                                  keepdims=False)
            upd = facility.contract(
                "bn,bhp->bhnp", B[:, 0].reshape(bg, -1),
                (xh[:, 0] * dt[:, 0, :, None]).astype(x.dtype).reshape(
                    bg, k, -1),
                plan=Plan(out_dtype=jnp.float32)).reshape(sstate.shape)
            ssm_all, sstate = _in_place(_update_state, ssm_all, sstate, dA,
                                        upd, layer)
            y = facility.contract(
                "bn,bhnp->bhp", C[:, 0].reshape(bg, -1),
                sstate.astype(x.dtype).reshape(bg, k, *sstate.shape[2:])
            ).reshape(b, nheads, -1)
        y = (y.astype(jnp.float32)
             + xh[:, 0].astype(jnp.float32) * p["D"][:, None])
        y = y[:, None].astype(x.dtype)
        new_state = {"ssm": ssm_all, "conv": conv_all}

    y = y.reshape(b, l, d_in)
    # gated RMSNorm (mamba2 block output norm), over each group's d_in/G
    # channels.  Its mean runs on one device: a mesh-sharded d_in would be
    # summed as per-shard partials plus an all-reduce, which rounds
    # differently from one device's sum.
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    g = shard(g, "batch", None, None)
    gf = g.astype(jnp.float32)
    g = (_group_rms(gf, groups, cfg.norm_eps)
         * p["norm_scale"]).astype(x.dtype)
    return facility.contract(DOT, g, p["out_proj"]), new_state

