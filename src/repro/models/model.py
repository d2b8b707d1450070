"""Model assembly: init / forward / prefill / decode for all families.

Families (DESIGN.md section 4): dense (llama lineage incl. GQA + SWA),
moe (mixtral, deepseek-moe fine-grained + shared experts), ssm (mamba2),
hybrid (zamba2: mamba backbone + shared transformer blocks), audio (whisper
enc-dec, conv audio stem), vlm (qwen2-vl backbone, M-RoPE, conv
patch-embed vision stem).

Layer stacks are `lax.scan`s over stacked parameter pytrees (keeps HLO and
compile times O(1) in depth — essential for the 95-layer dry runs), with a
configurable remat policy applied to the scan body.

Attention routing: training / prefill / cross-attention (dense positions,
static q_offset) dispatch through the registry's ``attn`` op-class via
``layers.sdpa`` — never ``kernels.mma_attention`` directly (scripts/ci.sh
lints the import).  The ring-buffer decode steps below pass
``kv_positions``/``valid`` slot predicates, which keeps them on sdpa's
explicit chunked path (positions are data there, so the attn op-class's
structural causal/window grid bounds cannot apply).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.parallel.api import shard

Params = Any

# Dry-run cost accounting: XLA's HloCostAnalysis counts a while-loop body
# ONCE (not x trip count), so rolled layer scans would under-report FLOPs /
# bytes / collectives by ~num_layers.  launch/dryrun.py sets this to True to
# lower with fully unrolled layer loops; training/serving keep rolled scans
# (compile-time O(1) in depth).
SCAN_UNROLL = False


# ABFT serving (core/abft.py) needs every in-layer contract dispatch to
# see CONCRETE operands — checksum verification skips tracers — but a
# lax.scan traces its body once, so every contract inside the layer stack
# is invisible to it.  ``eager_layers()`` swaps the scan for a python
# loop over the stacked pytree for the dynamic extent of the block
# (decode steps are one token; the O(depth) eager cost is the documented
# price of verified decode, launch/serve.py --abft).
_EAGER_LAYERS: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_eager_layers", default=False)


@contextlib.contextmanager
def eager_layers():
    token = _EAGER_LAYERS.set(True)
    try:
        yield
    finally:
        _EAGER_LAYERS.reset(token)


def layer_scan(body, init, xs):
    if _EAGER_LAYERS.get():
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        carry, ys = init, []
        for i in range(n):
            carry, y = body(carry,
                            jax.tree_util.tree_map(lambda a: a[i], xs))
            ys.append(y)
        ys = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
        return carry, ys
    return jax.lax.scan(body, init, xs, unroll=SCAN_UNROLL or 1)


# Remat policy for the per-layer checkpoint wrapper.  'nothing' = full
# recompute (min memory, 2x fwd FLOPs in bwd); 'dots' = save matmul
# outputs (XLA's dots_with_no_batch_dims_saveable — trades HBM for FLOPs).
REMAT_POLICY = "nothing"


def _remat(body):
    policy = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
    }[REMAT_POLICY]
    return jax.checkpoint(body, policy=policy)

# ======================================================================
# Per-family layer init / axes
# ======================================================================

def _init_layer(key, cfg, kind: str):
    ks = jax.random.split(key, 6)
    if kind == "ssm":
        return {"norm": L.init_norm(cfg), "mamba": M2.init_mamba2(ks[0], cfg)}
    if kind == "hybrid":
        return {"norm": L.init_norm(cfg), "mamba": M2.init_mamba2(ks[0], cfg)}
    p = {"attn_norm": L.init_norm(cfg), "attn": L.init_attention(ks[0], cfg),
         "mlp_norm": L.init_norm(cfg)}
    if kind == "moe":
        p["moe"] = MOE.init_moe(ks[1], cfg)
    elif kind == "dense" or kind == "encoder":
        p["mlp"] = L.init_mlp(ks[1], cfg)
    if kind == "cross":  # whisper decoder layer
        p["mlp"] = L.init_mlp(ks[1], cfg)
        p["cross_norm"] = L.init_norm(cfg)
        p["cross"] = L.init_attention(ks[2], cfg)
    return p


def _layer_axes(cfg, kind: str):
    if kind in ("ssm", "hybrid"):
        return {"norm": L.norm_axes(cfg), "mamba": M2.mamba2_axes(cfg)}
    p = {"attn_norm": L.norm_axes(cfg), "attn": L.attention_axes(cfg),
         "mlp_norm": L.norm_axes(cfg)}
    if kind == "moe":
        p["moe"] = MOE.moe_axes(cfg)
    elif kind in ("dense", "encoder"):
        p["mlp"] = L.mlp_axes(cfg)
    if kind == "cross":
        p["mlp"] = L.mlp_axes(cfg)
        p["cross_norm"] = L.norm_axes(cfg)
        p["cross"] = L.attention_axes(cfg)
    return p


def _stack_init(key, cfg, kind, n):
    return jax.vmap(lambda k: _init_layer(k, cfg, kind))(
        jax.random.split(key, n))


def _stacked(axes):
    """Prefix every leaf's axes with the stacked layer axis."""
    return jax.tree.map(lambda ax: ("layers",) + ax, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def _stack_axes(cfg, kind):
    return _stacked(_layer_axes(cfg, kind))


# zamba2: ``num_mem_blocks`` shared transformer blocks, called in turn
# before the Mamba of each layer in ``cfg.shared_calls``; each call has its
# own MLP adapter and d x d output linear.

def _init_shared_block(key, cfg):
    """Attention from concat(h, emb0), 2 d wide, back to d; the gated
    MLP."""
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {"attn_norm": L.init_norm(cfg, 2 * d),
            "attn": L.init_attention(ks[0], cfg, 2 * d, d_out=d),
            "mlp_norm": L.init_norm(cfg), "mlp": L.init_mlp(ks[1], cfg)}


def _init_call(key, cfg):
    """A call's rank-``adapter_rank`` adapter on the MLP's gate and up
    projections, and its linear."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    ks = jax.random.split(key, 4)
    return {"adapter_in": L._dense_init(ks[0], (d, r)),
            "adapter_gate": L._dense_init(ks[1], (r, f)),
            "adapter_up": L._dense_init(ks[2], (r, f)),
            "linear": L._dense_init(ks[3], (d, d))}


def _shared_axes(cfg):
    return {"shared": _stacked({
                "attn_norm": L.norm_axes(cfg), "attn": L.attention_axes(cfg),
                "mlp_norm": L.norm_axes(cfg), "mlp": L.mlp_axes(cfg)}),
            "calls": _stacked({
                "adapter_in": ("embed", None), "adapter_gate": (None, "mlp"),
                "adapter_up": (None, "mlp"), "linear": ("embed", None)})}


def _main_kind(cfg) -> str:
    return {"dense": "dense", "moe": "moe", "ssm": "ssm",
            "hybrid": "hybrid", "audio": "cross", "vlm": "dense"}[cfg.family]


# ======================================================================
# Parameters
# ======================================================================

def init_params(cfg, key) -> Params:
    ks = jax.random.split(key, 8)
    kind = _main_kind(cfg)
    n_scan = cfg.num_layers - cfg.first_dense_layers
    p = {
        "embed": L.init_embed(ks[0], cfg),
        "layers": _stack_init(ks[1], cfg, kind, n_scan),
        "final_norm": L.init_norm(cfg),
    }
    if cfg.first_dense_layers:
        p["first_dense"] = _stack_init(ks[2], cfg, "dense",
                                       cfg.first_dense_layers)
    if kind == "hybrid":
        p["shared"] = jax.vmap(lambda k: _init_shared_block(k, cfg))(
            jax.random.split(ks[3], cfg.num_mem_blocks))
        p["calls"] = jax.vmap(lambda k: _init_call(k, cfg))(
            jax.random.split(ks[4], len(cfg.shared_calls)))
    if cfg.is_enc_dec:
        p["encoder"] = {
            "layers": _stack_init(ks[6], cfg, "encoder", cfg.encoder_layers),
            "norm": L.init_norm(cfg),
        }
        if not cfg.frontend_stub:
            kf = jax.random.split(ks[7], 2)
            d = cfg.d_model
            p["encoder"]["frontend"] = {
                # whisper stem: conv1 k3 s1 SAME + gelu, conv2 k3 s2 SAME
                # + gelu — both via the facility's CONV1D op-class.
                "conv1_w": jax.random.normal(
                    kf[0], (3, cfg.n_mels, d), jnp.float32)
                * (3 * cfg.n_mels) ** -0.5,
                "conv1_b": jnp.zeros((d,), jnp.float32),
                "conv2_w": jax.random.normal(
                    kf[1], (3, d, d), jnp.float32) * (3 * d) ** -0.5,
                "conv2_b": jnp.zeros((d,), jnp.float32),
            }
    if cfg.vision_prefix:
        kv = jax.random.split(ks[7], 2)
        p["vision_proj"] = L._dense_init(kv[0], (cfg.d_model, cfg.d_model))
        if not cfg.frontend_stub and cfg.patch_size:
            # qwen2-vl patch-embed stem (whisper audio-stem pattern):
            # one CONV2D with kernel = stride = patch_size over raw
            # images, bias fused into the conv deprime.
            ps, c, d = cfg.patch_size, cfg.image_channels, cfg.d_model
            p["vision_patch"] = {
                "patch_w": jax.random.normal(
                    kv[1], (ps, ps, c, d), jnp.float32)
                * (ps * ps * c) ** -0.5,
                "patch_b": jnp.zeros((d,), jnp.float32),
            }
    return p


def param_axes(cfg):
    kind = _main_kind(cfg)
    p = {
        "embed": L.embed_axes(cfg),
        "layers": _stack_axes(cfg, kind),
        "final_norm": L.norm_axes(cfg),
    }
    if cfg.first_dense_layers:
        p["first_dense"] = _stack_axes(cfg, "dense")
    if kind == "hybrid":
        p.update(_shared_axes(cfg))
    if cfg.is_enc_dec:
        p["encoder"] = {"layers": _stack_axes(cfg, "encoder"),
                        "norm": L.norm_axes(cfg)}
        if not cfg.frontend_stub:
            p["encoder"]["frontend"] = {
                "conv1_w": (None, None, "embed"), "conv1_b": ("embed",),
                "conv2_w": (None, None, "embed"), "conv2_b": ("embed",),
            }
    if cfg.vision_prefix:
        p["vision_proj"] = ("embed", None)
        if not cfg.frontend_stub and cfg.patch_size:
            p["vision_patch"] = {"patch_w": (None, None, None, "embed"),
                                 "patch_b": ("embed",)}
    return p


# ======================================================================
# Blocks
# ======================================================================

def _residual_shard(h):
    return shard(h, "batch", "seq", None)


def _apply_dense_block(bp, h, cfg, *, cos_sin, is_moe, causal=None,
                       cross_x=None, kv=None, window=None, q_offset=0,
                       kv_positions=None, valid=None):
    with jax.named_scope("block.attn"):
        hn = L.apply_norm(bp["attn_norm"], h, cfg)
        # Residual adds ride the output-projection / w2 GEMM epilogues
        # (layers.apply_attention / apply_mlp `residual=`): one fused store
        # instead of a separate read-modify-write of the activations.
        a, kv_out = L.apply_attention(
            bp["attn"], hn, cfg, cos_sin=cos_sin, kv=kv, causal=causal,
            window=window, q_offset=q_offset, kv_positions=kv_positions,
            valid=valid, residual=h)
        h = _residual_shard(a)
        aux = jnp.zeros((), jnp.float32)
        cross_kv = None
        if cross_x is not None and "cross" in bp:
            hn = L.apply_norm(bp["cross_norm"], h, cfg)
            ca, cross_kv = L.apply_attention(bp["cross"], hn, cfg,
                                             causal=False, cross_x=cross_x,
                                             residual=h)
            h = _residual_shard(ca)
    with jax.named_scope("block.mlp"):
        hn = L.apply_norm(bp["mlp_norm"], h, cfg)
        if is_moe:
            m, aux = MOE.apply_moe(bp["moe"], hn, cfg)
            h = _residual_shard(h + m)
        else:
            h = _residual_shard(L.apply_mlp(bp["mlp"], hn, cfg, residual=h))
    return h, aux, kv_out, cross_kv


def _apply_ssm_block(bp, h, cfg, state=None, layer=None, t=None):
    """h + Mamba(RMSNorm(h)); in a hybrid layer the norm reads h + t, where
    t is the shared call's output."""
    with jax.named_scope("block.ssm"):
        hn = L.apply_norm(bp["norm"], h if t is None else h + t, cfg)
        out, new_state = M2.apply_mamba2(bp["mamba"], hn, cfg, state=state,
                                         layer=layer)
        return _residual_shard(h + out), new_state


def _ssm_decode_layers(layers, h, cfg, ssm, conv):
    """Decode through a stack of SSM blocks, the state carried whole:
    block ``i`` updates slice ``i`` of the stacked ``ssm`` and ``conv``
    buffers in place (mamba2.apply_mamba2)."""
    def body(carry, lp):
        hh, i, ssm, conv = carry
        hh, st = _apply_ssm_block(lp, hh, cfg,
                                  state={"ssm": ssm, "conv": conv}, layer=i)
        return (hh, i + 1, st["ssm"], st["conv"]), None
    (h, _, ssm, conv), _ = layer_scan(
        body, (h, jnp.asarray(0, jnp.int32), ssm, conv), layers)
    return h, ssm, conv


# The shared blocks' softmax scale is (head_dim / 2) ** -0.5, as published:
# the queries take the extra sqrt(2) in their projection.
_SHARED_Q_SCALE = 2.0 ** 0.5


def _shared_attn(sp, h, emb0, cfg, **attn):
    """A shared block's attention over RMSNorm(concat(h, emb0)): (out,
    (k, v)), out d wide.  No residual inside the block."""
    with jax.named_scope("block.attn"):
        x = L.apply_norm(sp["attn_norm"], jnp.concatenate([h, emb0], -1),
                         cfg)
        return L.apply_attention(sp["attn"], x, cfg, q_scale=_SHARED_Q_SCALE,
                                 **attn)


def _shared_mlp(sp, cp, a, cfg):
    """The rest of a call: RMSNorm, the gated MLP whose gate and up
    projections each add the call's adapter B(A a) (the accumulator's
    seed, so the gate's activation sees the sum), and the call's linear.
    Returns t, which the hybrid layer adds to its Mamba's input."""
    from repro.core import facility
    from repro.core.facility import DOT, Epilogue, Plan
    with jax.named_scope("block.mlp"):
        a = L.apply_norm(sp["mlp_norm"], a, cfg)
        lo = facility.contract(DOT, a, cp["adapter_in"])
        f32 = Plan(out_dtype=jnp.float32)
        g = facility.contract(
            DOT, a, sp["mlp"]["w1"],
            acc=facility.contract(DOT, lo, cp["adapter_gate"], plan=f32),
            plan=Plan(epilogue=Epilogue(activation=cfg.act)))
        u = facility.contract(
            DOT, a, sp["mlp"]["w3"],
            acc=facility.contract(DOT, lo, cp["adapter_up"], plan=f32))
        m = facility.contract(DOT, g * u, sp["mlp"]["w2"])
        return facility.contract(DOT, m, cp["linear"])


def _pick(tree, i):
    """Entry ``i`` of every leaf of a stacked tree."""
    return jax.tree.map(lambda w: w[i], tree)


def _call_params(params, cfg, j):
    """Shared block ``j % num_mem_blocks`` and call ``j``'s own weights."""
    return (_pick(params["shared"], j % cfg.num_mem_blocks),
            _pick(params["calls"], j))


def _scan_layers(body, carry, params, lo, hi):
    """``layer_scan`` of ``body(carry, i, layer i's params)`` over layers
    lo .. hi-1, each layer's weights read from the whole stack inside the
    loop: a static slice of the stack would be a copy of it (and of its
    bfloat16 cast).  Returns the carry and the stacked outputs."""
    return layer_scan(lambda c, i: body(c, i, _pick(params["layers"], i)),
                      carry, jnp.arange(lo, hi))


# ======================================================================
# Position embeddings helper
# ======================================================================

def _cos_sin_for(cfg, positions, batch=None):
    """positions: (B, S) absolute, or (3, B, S) for M-RoPE."""
    if cfg.mrope:
        cos, sin = L.mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                   cfg.mrope_sections)
    else:
        cos, sin = L.rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    return (cos, sin, cos, sin)


# ======================================================================
# Forward (training / encoder)
# ======================================================================

def _vision_patch_embed(params, images, cfg):
    """qwen2-vl patch-embed stem: raw images (B, gh*ps, gw*ps, C) through
    ONE facility CONV2D with kernel = stride = patch_size (the stem IS a
    GEMM over the patch matrix — paper eq. 8), bias fused into the conv
    deprime.  Returns (B, vision_prefix, d_model) patch embeddings; the
    filter bank may arrive prepacked (``prepack_params_for_serving`` packs
    ``patch_w`` into its conv tile layout)."""
    from repro.core import facility
    from repro.core.facility import Epilogue, Plan
    fe = params["vision_patch"]
    ps = cfg.patch_size
    h = facility.contract(
        facility.CONV2D, images.astype(jnp.float32), fe["patch_w"],
        bias=fe["patch_b"],
        plan=Plan(stride=ps, padding="valid", epilogue=Epilogue(bias=True)))
    b, gh, gw, d = h.shape
    if gh * gw != cfg.vision_prefix:
        raise ValueError(
            f"image grid {gh}x{gw} does not cover vision_prefix="
            f"{cfg.vision_prefix}; expected {cfg.vision_grid()} patches "
            f"of edge {ps}")
    return h.reshape(b, gh * gw, d)


def _embed_inputs(params, batch, cfg):
    """Token (+ modality-frontend) embedding; returns (h, positions)."""
    from repro.core import facility
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.vision_prefix:
        # Real frontend: raw images through the patch-embed conv stem.
        # Precomputed "vision_embeds" stay accepted (stub configs, and
        # batches recorded before the frontend was de-stubbed).
        if not cfg.frontend_stub and cfg.patch_size and "images" in batch:
            ve = _vision_patch_embed(params, batch["images"], cfg)
        elif "vision_embeds" in batch:
            ve = batch["vision_embeds"]
        else:
            ve = None
        if ve is not None:
            ve = facility.contract(facility.DOT, ve.astype(h.dtype),
                                   params["vision_proj"])
            h = jnp.concatenate([ve, h[:, cfg.vision_prefix:]], axis=1)
    if cfg.mrope:
        positions = batch["positions"]        # (3, B, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return _residual_shard(h), positions


def _run_encoder(params, frames, cfg):
    """Whisper encoder.  ``frames`` is (B, T, n_mels) mel frames fed to
    the two-layer conv stem (k3 s1 + k3 s2, SAME, gelu — bias+gelu fused
    into the conv deprime via the epilogue contract), or precomputed
    (B, T, d_model) embeddings when ``cfg.frontend_stub``."""
    if cfg.frontend_stub:
        h = _residual_shard(frames.astype(jnp.bfloat16))
    else:
        from repro.core import facility
        from repro.core.facility import Epilogue, Plan
        fe = params["encoder"]["frontend"]
        gelu = Epilogue(bias=True, activation="gelu")
        h = facility.contract(
            facility.CONV1D, frames.astype(jnp.float32), fe["conv1_w"],
            bias=fe["conv1_b"], plan=Plan(padding="same", epilogue=gelu))
        h = facility.contract(
            facility.CONV1D, h, fe["conv2_w"], bias=fe["conv2_b"],
            plan=Plan(stride=2, padding="same", epilogue=gelu))
        h = _residual_shard(h)
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    cos_sin = _cos_sin_for(cfg, pos)

    def body(carry, lp):
        hh, _, _, _ = _apply_dense_block(lp, carry, cfg, cos_sin=cos_sin,
                                         is_moe=False, causal=False)
        return hh, None

    body = _remat(body)
    h, _ = layer_scan(body, h, params["encoder"]["layers"])
    return L.apply_norm(params["encoder"]["norm"], h, cfg)


def forward(params, batch, cfg, *, collect_cache: bool = False):
    """Teacher-forced forward pass.  Returns (logits, aux, cache|None)."""
    h, positions = _embed_inputs(params, batch, cfg)
    emb0 = h
    cross_x = None
    if cfg.is_enc_dec:
        cross_x = _run_encoder(params, batch["frames"], cfg)

    kind = _main_kind(cfg)
    cos_sin = (None if kind in ("ssm",)
               else _cos_sin_for(cfg, positions))
    window = cfg.sliding_window
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}

    # ---- leading dense layers (deepseek-moe) ----
    if cfg.first_dense_layers:
        def dense_body(carry, lp):
            hh, aux, kv, _ = _apply_dense_block(
                lp, carry, cfg, cos_sin=cos_sin, is_moe=False, window=window)
            return hh, (aux, kv if collect_cache else None)
        dense_body = _remat(dense_body)
        h, (auxs, kvs) = layer_scan(dense_body, h, params["first_dense"])
        aux_total += auxs.sum()
        if collect_cache:
            caches["first_dense_kv"] = kvs

    # ---- main stack ----
    if kind in ("dense", "moe", "cross"):
        def body(carry, lp):
            hh, aux, kv, ckv = _apply_dense_block(
                lp, carry, cfg, cos_sin=cos_sin, is_moe=(kind == "moe"),
                cross_x=cross_x, window=window)
            return hh, (aux, kv if collect_cache else None,
                        ckv if collect_cache else None)
        body = _remat(body)
        h, (auxs, kvs, ckvs) = layer_scan(body, h, params["layers"])
        aux_total += auxs.sum()
        if collect_cache:
            caches["kv"] = kvs
            if cfg.is_enc_dec:
                caches["cross_kv"] = ckvs
    elif kind == "ssm":
        def body(carry, lp):
            hh, st = _apply_ssm_block(lp, carry, cfg)
            return hh, (st if collect_cache else None)
        body = _remat(body)
        h, sts = layer_scan(body, h, params["layers"])
        if collect_cache:
            caches["ssm"] = sts["ssm"]
            caches["conv"] = sts["conv"]
    elif kind == "hybrid":
        h = _run_hybrid(params, h, emb0, cfg, cos_sin, collect_cache, caches)

    h = L.apply_norm(params["final_norm"], h, cfg)
    logits = L.logits(params["embed"] if cfg.tie_embeddings else
                      params["embed"], h, cfg)
    logits = shard(logits, "batch", None, "vocab")
    return logits, aux_total, (caches if collect_cache else None)


def _run_hybrid(params, h, emb0, cfg, cos_sin, collect_cache, caches):
    """zamba2: the Mamba layers, each of ``cfg.shared_calls`` preceded by
    a call of a shared block: h = h + Mamba(RMSNorm(h + t)).  Collects
    every layer's SSM and conv state and each call's K/V."""
    @_remat
    def body(carry, i, lp):
        h, t = carry
        h, st = _apply_ssm_block(lp, h, cfg, t=t)
        return (h, jnp.zeros_like(t)), (st if collect_cache else None)

    def call(j, h):
        sp, cp = _call_params(params, cfg, j)
        a, kv = _shared_attn(sp, h, emb0, cfg, cos_sin=cos_sin)
        return _shared_mlp(sp, cp, a, cfg), kv

    (h, _), states, kvs = _hybrid_layers(body, call, (h, jnp.zeros_like(h)),
                                         params, cfg)
    if collect_cache:
        stacked = jax.tree.map(lambda *a: jnp.concatenate(a), *states)
        caches["ssm"], caches["conv"] = stacked["ssm"], stacked["conv"]
        caches["kv"] = jax.tree.map(lambda *a: jnp.stack(a), *kvs)
    return h


def _hybrid_layers(body, call, carry, params, cfg):
    """The hybrid's layer order, for the forward and the decode step alike:
    the layers before the first call, then for each call ``j`` at layer
    ``i``, ``t, out = call(j, h)`` and a ``layer_scan`` of ``body`` over
    layers i .. (the next call's layer) - 1, whose carry, (h, t, ...),
    takes t to layer i; ``body`` gives the next layer a zero t.  (Each
    scan of more than one layer stays a loop, so a decode step updates
    the stacked state in place.)  Returns the carry, the scans' stacked
    outputs and the calls' outs."""
    calls = cfg.shared_calls
    bounds = calls + (cfg.num_layers,)
    states, outs = [], []
    if bounds[0]:
        carry, ys = _scan_layers(body, carry, params, 0, bounds[0])
        states.append(ys)
    for j, i in enumerate(calls):
        t, out = call(j, carry[0])
        carry, ys = _scan_layers(body, (carry[0], t) + carry[2:], params, i,
                                 bounds[j + 1])
        states.append(ys)
        outs.append(out)
    return carry, states, outs


# ======================================================================
# Loss
# ======================================================================

def loss_fn(params, batch, cfg):
    logits, aux, _ = forward(params, batch, cfg)
    with jax.named_scope("loss"):
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        loss = (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
        return loss + aux, {"nll": loss, "aux": aux}


# ======================================================================
# KV / state caches + decode
# ======================================================================

def cache_len(cfg, seq_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16):
    """Abstract/zero cache for a decode step at context length seq_len."""
    kind = _main_kind(cfg)
    n_scan = cfg.num_layers - cfg.first_dense_layers
    c: dict[str, Any] = {"cur": jnp.zeros((), jnp.int32)}
    clen = cache_len(cfg, seq_len)
    if cfg.is_enc_dec:
        # whisper: decoder self-KV is bounded by decoder_len; the *encoder*
        # (cross) KV carries the long seq_len context.
        clen = min(clen, cfg.decoder_len)
    kv_shape = (n_scan, batch, clen, cfg.num_kv_heads, cfg.head_dim)
    if kind in ("dense", "moe", "cross"):
        c["k"] = jnp.zeros(kv_shape, dtype)
        c["v"] = jnp.zeros(kv_shape, dtype)
        c["pos"] = jnp.full((clen,), -1, jnp.int32)
        if cfg.first_dense_layers:
            fd = (cfg.first_dense_layers, batch, clen, cfg.num_kv_heads,
                  cfg.head_dim)
            c["fd_k"] = jnp.zeros(fd, dtype)
            c["fd_v"] = jnp.zeros(fd, dtype)
        if cfg.is_enc_dec:
            # conv stem downsamples the frame axis (stride-2 second layer)
            enc_len = cfg.encoder_len(seq_len)
            xs = (cfg.num_layers, batch, enc_len, cfg.num_kv_heads,
                  cfg.head_dim)
            c["cross_k"] = jnp.zeros(xs, dtype)
            c["cross_v"] = jnp.zeros(xs, dtype)
    if kind == "ssm":
        d_in, nheads, conv_dim = M2.dims(cfg)
        c["ssm"] = jnp.zeros((cfg.num_layers, batch, nheads, cfg.ssm_state,
                              cfg.ssm_headdim), jnp.float32)
        c["conv"] = jnp.zeros((cfg.num_layers, batch,
                               cfg.ssm_conv_width - 1, conv_dim), dtype)
    if kind == "hybrid":
        d_in, nheads, conv_dim = M2.dims(cfg)
        c["ssm"] = jnp.zeros((cfg.num_layers, batch, nheads, cfg.ssm_state,
                              cfg.ssm_headdim), jnp.float32)
        c["conv"] = jnp.zeros((cfg.num_layers, batch,
                               cfg.ssm_conv_width - 1, conv_dim), dtype)
        # one ring for each call of a shared block
        c["k"] = jnp.zeros((len(cfg.shared_calls), batch, clen,
                            cfg.num_kv_heads, cfg.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
        c["pos"] = jnp.full((clen,), -1, jnp.int32)
    return c


def cache_axes(cfg):
    """Logical sharding axes for every cache leaf (decode dry-run)."""
    kind = _main_kind(cfg)
    c = {"cur": ()}
    # KV cache: batch over DP, cache-seq over TP (flash-decode style
    # partial softmax); heads stay unsharded here — 'model' is taken.
    kv_ax = ("layers", "batch", "seq_kv", None, None)
    if kind in ("dense", "moe", "cross"):
        c["k"] = kv_ax
        c["v"] = kv_ax
        c["pos"] = (None,)
        if cfg.first_dense_layers:
            c["fd_k"] = kv_ax
            c["fd_v"] = kv_ax
        if cfg.is_enc_dec:
            c["cross_k"] = kv_ax
            c["cross_v"] = kv_ax
    if kind == "ssm":
        c["ssm"] = ("layers", "batch", "ssm_heads", None, None)
        c["conv"] = ("layers", "batch", None, "mlp")
    if kind == "hybrid":
        c["ssm"] = ("layers", "batch", "ssm_heads", None, None)
        c["conv"] = ("layers", "batch", None, "mlp")
        c["k"] = kv_ax
        c["v"] = kv_ax
        c["pos"] = (None,)
    return c


def _decode_attn_inputs(cache, cfg, cur):
    clen = cache["pos"].shape[0]
    idx = cur % clen
    valid = cache["pos"] >= 0
    return idx, valid


def _ring_insert(lp, hh, k_c, v_c, slot, cos_sin, cfg):
    """Project the new token's key and value and insert them into the
    layer's ring cache at ``slot``."""
    from repro.core import facility
    b = hh.shape[0]
    with jax.named_scope("block.attn"):
        hn = L.apply_norm(lp["attn_norm"], hh, cfg)
        with jax.named_scope("weights.cast"):
            wk = lp["attn"]["wk"].astype(hn.dtype)
        knew = facility.contract(facility.DOT, hn, wk).reshape(
            b, 1, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope("weights.cast"):
            wv = lp["attn"]["wv"].astype(hn.dtype)
        vnew = facility.contract(facility.DOT, hn, wv).reshape(
            b, 1, cfg.num_kv_heads, cfg.head_dim)
        knew = L.apply_rope(knew, cos_sin[2], cos_sin[3])
        with jax.named_scope("kv.write"):
            k_c = jax.lax.dynamic_update_slice_in_dim(k_c, knew, slot, 1)
            v_c = jax.lax.dynamic_update_slice_in_dim(v_c, vnew, slot, 1)
    return k_c, v_c


def decode_step(params, cache, tokens, cfg):
    """One token for every sequence in the batch.  tokens (B, 1)."""
    kind = _main_kind(cfg)
    cur = cache["cur"]
    b = tokens.shape[0]
    h = L.embed_tokens(params["embed"], tokens, cfg)
    h = shard(h, "batch", None, None)
    emb0 = h
    window = cfg.sliding_window
    pos_b = jnp.broadcast_to(cur[None, None], (b, 1))
    if cfg.mrope:
        cos_sin = _cos_sin_for(cfg, jnp.broadcast_to(cur, (3, b, 1)))
    elif kind != "ssm":
        cos_sin = _cos_sin_for(cfg, pos_b)
    new_cache = dict(cache)

    if kind in ("dense", "moe", "cross"):
        clen = cache["pos"].shape[0]
        slot = cur % clen
        kv_positions = cache["pos"].at[slot].set(cur)[None]   # (1, clen)
        valid = (kv_positions >= 0)

        def make_body(is_moe):
            def body(carry, xs):
                hh = carry
                lp, k_c, v_c = xs
                k_c, v_c = _ring_insert(lp, hh, k_c, v_c, slot, cos_sin, cfg)
                hh, aux, _, _ = _apply_dense_block(
                    lp, hh, cfg, cos_sin=cos_sin, is_moe=is_moe,
                    kv=(k_c, v_c), window=window, q_offset=cur,
                    kv_positions=kv_positions, valid=valid)
                return hh, (k_c, v_c)
            return body

        body = make_body(kind == "moe")
        if cfg.first_dense_layers:
            h, (fk, fv) = layer_scan(make_body(False), h, (params["first_dense"], cache["fd_k"],
                                      cache["fd_v"]))
            new_cache["fd_k"], new_cache["fd_v"] = fk, fv

        if cfg.is_enc_dec:
            def body_cross(carry, xs):
                hh = carry
                lp, k_c, v_c, ck, cv = xs
                k_c, v_c = _ring_insert(lp, hh, k_c, v_c, slot, cos_sin, cfg)
                # self attention
                hh2, _, _, _ = _apply_dense_block(
                    lp, hh, cfg, cos_sin=cos_sin, is_moe=False,
                    kv=(k_c, v_c), q_offset=cur,
                    kv_positions=kv_positions, valid=valid)
                return hh2, (k_c, v_c)
            # decoder self-attn layers also carry precomputed cross kv:
            # fold cross attention via kv= on the 'cross' params
            def body_full(carry, xs):
                lp, k_c, v_c, ck, cv = xs
                hh, (k_c, v_c) = body_cross(carry, (lp, k_c, v_c, ck, cv))
                # cross attention with cached encoder kv
                with jax.named_scope("block.attn"):
                    hn = L.apply_norm(lp["cross_norm"], hh, cfg)
                    ca, _ = L.apply_attention(lp["cross"], hn, cfg,
                                              causal=False, kv=(ck, cv))
                    hh = hh + ca
                return hh, (k_c, v_c)
            h, (k, v) = layer_scan(body_full, h, (params["layers"], cache["k"], cache["v"],
                               cache["cross_k"], cache["cross_v"]))
        else:
            h, (k, v) = layer_scan(body, h, (params["layers"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = k, v
        new_cache["pos"] = kv_positions[0]

    elif kind == "ssm":
        h, new_cache["ssm"], new_cache["conv"] = _ssm_decode_layers(
            params["layers"], h, cfg, *M2.own_decode_state(cache))

    elif kind == "hybrid":
        clen = cache["pos"].shape[0]
        slot = cur % clen
        kv_positions = cache["pos"].at[slot].set(cur)[None]
        valid = kv_positions >= 0
        rings = {"k": cache["k"], "v": cache["v"]}

        def body(carry, i, lp):
            h, t, ssm, conv = carry
            h, st = _apply_ssm_block(lp, h, cfg,
                                     state={"ssm": ssm, "conv": conv},
                                     layer=i, t=t)
            return (h, jnp.zeros_like(t), st["ssm"], st["conv"]), None

        def call(j, h):
            """Call ``j`` with its own ring."""
            sp, cp = _call_params(params, cfg, j)
            k_c, v_c = _ring_insert(sp, jnp.concatenate([h, emb0], -1),
                                    rings["k"][j], rings["v"][j], slot,
                                    cos_sin, cfg)
            rings["k"] = rings["k"].at[j].set(k_c)
            rings["v"] = rings["v"].at[j].set(v_c)
            a, _ = _shared_attn(sp, h, emb0, cfg, cos_sin=cos_sin,
                                kv=(k_c, v_c), q_offset=cur,
                                kv_positions=kv_positions, valid=valid)
            return _shared_mlp(sp, cp, a, cfg), None

        (h, _, ssm, conv), _, _ = _hybrid_layers(
            body, call, (h, jnp.zeros_like(h), *M2.own_decode_state(cache)),
            params, cfg)
        new_cache.update(rings, ssm=ssm, conv=conv, pos=kv_positions[0])

    h = L.apply_norm(params["final_norm"], h, cfg)
    logits = L.logits(params["embed"], h, cfg)
    new_cache["cur"] = cur + 1
    return logits, new_cache


def prefill(params, batch, cfg):
    """Process a full prompt, return last-position logits (cache building
    is exercised via forward(collect_cache=True))."""
    logits, aux, caches = forward(params, batch, cfg, collect_cache=True)
    return logits[:, -1], caches
