"""Flash attention as accumulator-resident rank-k updates (beyond-paper).

The paper closes with "the instructions can be used as building blocks of
other computations".  Attention is the dominant such computation in the
assigned model zoo, and its inner loop IS the MMA pattern twice over:

    S_blk = Q_blk K_blkᵀ      — rank-d update into a (bq, bk) score tile
    O_blk += P_blk V_blk      — rank-bk update into a (bq, D) output tile

with the online-softmax running max/sum playing the role of the
accumulator rescale (an `xvf32gerpp` with a per-row scale).  The O tile,
running max m and normalizer l stay resident in VMEM scratch across the
whole KV loop; only Q/K/V panels stream from HBM — exactly the POWER10
MME execution model lifted to a fused two-GEMM kernel.

Since the attn-op-class PR this kernel is a registry lowering behind
``facility.contract(facility.ATTN, q, k, v, plan=Plan(...))`` — direct
``flash_attention`` calls survive as a deprecated shim.  Two structural
properties of the generalized kernel:

  * **Bounded causal grid.**  The KV loop is a *flattened* grid dimension
    built from ``attn_grid_plan``: only (qi, ki) block pairs with at least
    one structurally-live slot are issued (causal bound above, sliding-
    window bound below), with the block coordinates scalar-prefetched.
    Causal prefill therefore issues ~half the rank-k updates of the
    rectangular grid instead of predicating them off in-kernel.
  * **Masked-block guard.**  A block whose every slot is masked leaves the
    running max at ``NEG_INF``; the unguarded online-softmax update would
    then compute ``p = exp(NEG_INF - NEG_INF) = 1`` and corrupt the
    accumulator with a sum over V.  ``p`` is therefore gated on
    ``m_new == NEG_INF`` so fully-masked rows contribute exact zeros (and
    deprime to 0, the facility's fully-masked-row convention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import epilogue as _epilogue

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Grid plan: the bounded (qi, ki) block schedule (pure, host-side)
# ----------------------------------------------------------------------

def attn_k_bounds(qi: int, nk: int, *, bq: int, bk: int, causal: bool,
                  q_offset: int = 0, window: int | None = None
                  ) -> tuple[int, int]:
    """[k_lo, k_hi) — KV block range with any structurally-live slot for
    query block ``qi``.  Causal bounds above (no block past the diagonal
    of the last row), the sliding window bounds below (no block whose last
    slot is already outside the first row's window).  Always non-empty:
    a fully-masked query block still runs one (masked) step so its output
    tile is deprimed (to zeros, via the masked-block guard)."""
    hi = nk
    if causal:
        hi = min(nk, -(-(q_offset + (qi + 1) * bq) // bk))
        hi = max(hi, 1)
    lo = 0
    if window is not None:
        lo = max(0, (q_offset + qi * bq - (window - 1)) // bk)
        lo = min(lo, hi - 1)
    return lo, hi


def attn_live_steps(sq: int, sk: int, bq: int, bk: int, *, causal: bool,
                    q_offset: int = 0, window: int | None = None) -> int:
    """Total (qi, ki) grid steps the bounded schedule issues — the causal
    prefill count is ~half the rectangular ``(sq//bq) * (sk//bk)``."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    total = 0
    for qi in range(nq):
        lo, hi = attn_k_bounds(qi, nk, bq=bq, bk=bk, causal=causal,
                               q_offset=q_offset, window=window)
        total += hi - lo
    return total


def attn_live_pairs(sq: int, sk: int, *, causal: bool, q_offset: int = 0,
                    window: int | None = None) -> int:
    """Position-level live (q, k) pair count — the useful-FLOPs numerator
    of the roofline model (block-level padding is charged separately)."""
    q_pos = np.arange(sq) + q_offset
    hi = np.minimum(sk, q_pos + 1) if causal else np.full(sq, sk)
    lo = np.clip(q_pos - (window - 1), 0, sk) if window is not None \
        else np.zeros(sq, np.int64)
    return int(np.maximum(hi - lo, 0).sum())


def attn_grid_plan(sq: int, sk: int, bq: int, bk: int, *, causal: bool,
                   q_offset: int = 0, window: int | None = None,
                   bound: bool = True) -> np.ndarray:
    """The scalar-prefetched block schedule: a (4, T) int32 array with rows
    ``qi``, ``ki``, ``first`` (this step primes qi's accumulator) and
    ``last`` (this step deprimes/stores).  ``bound=False`` keeps the full
    rectangular schedule (every mask applied in-kernel) — the benchmark's
    causal-bounded-vs-full-grid baseline."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    rows = []
    for qi in range(nq):
        lo, hi = (attn_k_bounds(qi, nk, bq=bq, bk=bk, causal=causal,
                                q_offset=q_offset, window=window)
                  if bound else (0, nk))
        for ki in range(lo, hi):
            rows.append((qi, ki, int(ki == lo), int(ki == hi - 1)))
    return np.asarray(rows, np.int32).T


# ----------------------------------------------------------------------
# The kernel
# ----------------------------------------------------------------------

def _flash_kernel(maps_ref, *refs, bq: int, bk: int, causal: bool,
                  q_offset: int, window: int | None, sm_scale: float,
                  has_valid: bool, ep: _epilogue.Epilogue | None):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    pos = 3
    valid_ref = refs[pos] if has_valid else None
    pos += has_valid
    bias_ref = refs[pos] if ep and ep.bias else None
    pos += bool(ep and ep.bias)
    res_ref = refs[pos] if ep and ep.residual else None
    pos += bool(ep and ep.residual)
    out_ref, acc_ref, m_ref, l_ref = refs[pos:]
    t = pl.program_id(2)
    qi = maps_ref[0, t]
    ki = maps_ref[1, t]

    @pl.when(maps_ref[2, t] == 1)
    def _prime():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]                                   # (bq, d)
    k = k_ref[...]                                   # (bk, d)
    v = v_ref[...]                                   # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                 # (bq, bk)
    if causal or window is not None:
        q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        live = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            live &= q_pos >= k_pos
        if window is not None:
            live &= q_pos - k_pos < window
        s = jnp.where(live, s, NEG_INF)
    if valid_ref is not None:
        s = jnp.where(valid_ref[...] != 0, s, NEG_INF)   # (1, bk) broadcast

    m_prev = m_ref[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # Masked-block guard: a fully-masked row keeps m_new == NEG_INF, and
    # exp(NEG_INF - NEG_INF) == 1 would silently add this block's V rows
    # to the accumulator.  Gate p so masked rows contribute exact zeros
    # (l stays 0 and the deprime's l==0 guard emits 0 for the row).
    p = jnp.where(m_new == NEG_INF, 0.0, jnp.exp(s - m_new))
    corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(maps_ref[3, t] == 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        out = acc_ref[...] / l
        if ep is not None:
            out = _epilogue.apply(
                out, ep,
                bias=bias_ref[...] if bias_ref is not None else None,
                residual=res_ref[...] if res_ref is not None else None)
        out_ref[...] = out.astype(out_ref.dtype)


def mma_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, q_offset: int = 0,
                        window: int | None = None,
                        valid: jnp.ndarray | None = None,
                        block_q: int = 128, block_k: int = 128,
                        ep: _epilogue.Epilogue | None = None,
                        bias: jnp.ndarray | None = None,
                        residual: jnp.ndarray | None = None,
                        out_dtype=None, bound_grid: bool = True,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused attention, grid-native over batch x heads with GQA broadcast.

    q: (B, Sq, H, D); k, v: (B, Sk, KVH, D) with H % KVH == 0 — each KV
    head serves its group of H/KVH query heads through the BlockSpec index
    map (the broadcast never materializes in HBM).  Sq/Sk must divide the
    blocks (the registry's block resolver picks dividing blocks).

    ``q_offset`` is the absolute position of q[0] (decode continuation);
    ``window`` the sliding-window width (q attends k with
    ``q_pos - k_pos < window``); ``valid`` an optional (B, Sk) bool marking
    filled KV slots.  All three are in-kernel predicates on the streamed
    score tile, pm*-style — and causal/window additionally *bound the
    grid*: the flattened KV dimension only issues live (qi, ki) blocks
    (``attn_grid_plan``), so causal prefill skips ~half the rank-k updates.

    ``ep`` fuses bias (D,) / activation / residual (B, Sq, H, D) into the
    normalized deprime store (epilogue.py contract).
    """
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    if k.shape != v.shape or k.shape[0] != b or k.shape[3] != d:
        raise ValueError(f"attention shapes {q.shape} x {k.shape} x "
                         f"{v.shape} are inconsistent")
    if h % kvh:
        raise ValueError(f"H ({h}) must be a multiple of KVH ({kvh})")
    group = h // kvh
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"S ({sq},{sk}) must divide blocks ({bq},{bk})")
    sm_scale = d ** -0.5
    ep = ep if ep is not None and not ep.is_identity else None
    if ep is not None:
        ep.validate(jnp.float32, bias=bias, residual=residual)
    elif bias is not None or residual is not None:
        raise ValueError("bias/residual operands need an Epilogue")

    maps = jnp.asarray(attn_grid_plan(
        sq, sk, bq, bk, causal=causal, q_offset=q_offset, window=window,
        bound=bound_grid))
    grid = (b, h, maps.shape[1])

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, q_offset=q_offset,
        window=window, sm_scale=sm_scale, has_valid=valid is not None,
        ep=ep)

    # The kernel runs head-major: (B, H, S, D) operands, so the head axis
    # is a squeezed block dim and each block's last two dims are (rows, D)
    # — the TPU tiling wants those divisible by (8, 128) or whole.
    q_blk = pl.BlockSpec((None, None, bq, d),
                         lambda bb, hh, t, m: (bb, hh, m[0, t], 0))
    kv_blk = pl.BlockSpec((None, None, bk, d),
                          lambda bb, hh, t, m: (bb, hh // group, m[1, t], 0))
    in_specs = [q_blk, kv_blk, kv_blk]
    inputs = [q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2)]
    if valid is not None:
        valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32)
                                 .reshape(-1, 1, sk), (b, 1, sk))
        in_specs.append(pl.BlockSpec(
            (None, 1, bk), lambda bb, hh, t, m: (bb, 0, m[1, t])))
        inputs.append(valid)
    if ep is not None and ep.bias:
        in_specs.append(pl.BlockSpec((1, d), lambda bb, hh, t, m: (0, 0)))
        inputs.append(bias.reshape(1, d))
    if ep is not None and ep.residual:
        in_specs.append(q_blk)
        inputs.append(residual.swapaxes(1, 2))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=q_blk,
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d),
                                       out_dtype or q.dtype),
        interpret=interpret,
    )(maps, *inputs).swapaxes(1, 2)


# ----------------------------------------------------------------------
# Deprecated shim + the pinned oracle
# ----------------------------------------------------------------------

def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    ep: _epilogue.Epilogue | None = None,
                    bias: jnp.ndarray | None = None,
                    residual: jnp.ndarray | None = None,
                    interpret: bool = False):
    """Deprecated: ``facility.contract(facility.ATTN, q, k, v,
    plan=Plan(causal=..., block=(bq, bk), ...))``.

    The legacy (BH, S, D) entry point — now a shim over the registry's
    ``attn`` op-class (a singleton head axis is added/stripped around the
    canonical (B, S, H, D) layout).
    """
    # Deprecated shim: by definition it reaches up into the facility it
    # predates.
    # repro: allow(layer-stratification)
    from repro.core import facility, precision

    facility.deprecated_shim(
        "mma_attention.flash_attention",
        "contract(facility.ATTN, q, k, v, plan=Plan(causal=..., "
        "block=(block_q, block_k)))")
    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, :, None], k[:, :, None], v[:, :, None]
        residual = residual[:, :, None] if residual is not None else None
    plan = facility.Plan(
        ger=precision.default_ger_for(q.dtype), backend="pallas",
        causal=causal, block=(min(block_q, q.shape[1]),
                              min(block_k, k.shape[1])),
        epilogue=ep, out_dtype=q.dtype, interpret=interpret)
    out = facility.contract(facility.ATTN, q, k, v, plan=plan, bias=bias,
                            residual=residual)
    return out[:, :, 0] if squeeze else out


def _repeat_heads(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def ref_attention(q, k, v, *, causal: bool = True,
                  window: int | None = None, q_offset: int = 0,
                  valid: jnp.ndarray | None = None):
    """Facility-routed oracle (score/value contractions are architected
    rank-k updates too; the XLA backend is pinned so the oracle never
    recurses into the kernel under test).  Takes (B, S, H, D) or the
    legacy (BH, S, D); returns the fp32 accumulator-dtype result.  Rows
    whose every slot is masked yield exact zeros — the facility's
    fully-masked-row convention shared by all three attn lowerings."""
    # Facility-routed by design (the oracle exercises the architected
    # path, XLA backend pinned).
    # repro: allow(layer-stratification)
    from repro.core import facility, precision

    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[:, :, None], k[:, :, None], v[:, :, None]
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    k = _repeat_heads(k, h // kvh)
    v = _repeat_heads(v, h // kvh)
    xla32 = facility.Plan(ger=precision.Ger.F32GER, backend="xla",
                          out_dtype=jnp.float32)
    s = facility.contract("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                          k.astype(jnp.float32), plan=xla32) * (d ** -0.5)
    sk = k.shape[1]
    q_pos = (jnp.arange(sq) + q_offset)[:, None]          # (Sq, 1)
    k_pos = jnp.arange(sk)[None, :]                       # (1, Sk)
    mask = jnp.ones((1, sq, sk), bool)
    if causal:
        mask &= (q_pos >= k_pos)[None]
    if window is not None:
        mask &= (q_pos - k_pos < window)[None]
    if valid is not None:
        mask = mask & jnp.asarray(valid, bool).reshape(-1, 1, sk)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1)[:, None, :, None], p, 0.0)
    out = facility.contract(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        plan=facility.Plan(ger=precision.default_ger_for(v.dtype),
                           backend="xla", out_dtype=jnp.float32))
    return out[:, :, 0] if squeeze else out
