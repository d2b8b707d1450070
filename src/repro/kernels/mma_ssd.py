"""The SSD chunk scan's intra-chunk branch as one kernel (beyond-paper).

Mamba2's state-space duality (arXiv:2405.21060) computes each chunk's
outputs from inputs of the same chunk as a masked "attention":

    y[l, h] = sum_{s <= l} (C_l . B_s) exp(cum[h, l] - cum[h, s]) x[s, h]

with ``cum`` the within-chunk cumulative sum of the log-decays dt * A.
Written as XLA ops, the decays, the scores and their product are
(L, L) tensors per head and chunk that cross HBM several times.  Here one
grid step holds one chunk's ``C``, ``B`` and ``x`` for a block of heads in
VMEM and runs the whole branch there, on the paper's pattern twice over:

    S = C Bᵀ                 — rank-n update into an (L, L) score tile,
                               once per step, shared by the block's heads
    Y_h = (S ∘ exp(Δcum_h)) X_h — per head, a rank-L update into (L, p)

Only row tiles on or below the diagonal are computed: a (tq, L) row tile
reads the columns up to its own last row, and the causal mask applies to
its diagonal tile alone.  The decays are the difference of the cumulative
sums, ``cum[l] - cum[s]``, exactly as the XLA lowering forms them.

Dispatched by the ``ssd`` op-class of the lowering registry
(``facility.contract(facility.SSD, C, B, x, cum)``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 128


def ssd_head_block(h: int) -> int:
    """Heads per grid step: 8 (the f32 sublane tile of the ``cum`` block)
    where it divides ``h``, else every head."""
    return 8 if h % 8 == 0 else h


def ssd_vmem_bytes(l: int, n: int, hb: int, p: int, *, in_bytes: int,
                   x_bytes: int, out_bytes: int) -> int:
    """VMEM one grid step holds: the double-buffered C, B, cum, x and y
    blocks, the (L, L) f32 scores, and a row tile's f32 temporaries."""
    blocks = 2 * (2 * l * n * in_bytes + hb * l * 4
                  + l * hb * p * (x_bytes + out_bytes))
    return blocks + l * l * 4 + 4 * ROW_TILE * l * 4


def _ssd_kernel(c_ref, b_ref, cum_ref, x_ref, out_ref, *, hb: int, p: int,
                c_dtype, b_dtype, att_dtype, prod_dtype, x_dtype):
    l = c_ref.shape[0]
    scores = jax.lax.dot_general(
        c_ref[...].astype(c_dtype), b_ref[...].astype(b_dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    cum = cum_ref[...]                                   # (hb, L) f32
    cum_col = cum.T                                      # (L, hb)
    # Heads that share one 128-lane tile of x and y: each head's product
    # reads x with the other heads' lanes zeroed, so the tile's columns
    # are each head's own product, summed with exact zeros.
    per_tile = max(1, LANES // p)
    width = per_tile * p
    tq = min(ROW_TILE, l)
    for t in range(l // tq):
        rows = slice(t * tq, (t + 1) * tq)
        cols = (t + 1) * tq                              # causal bound
        live = (jax.lax.broadcasted_iota(jnp.int32, (tq, cols), 0) + t * tq
                >= jax.lax.broadcasted_iota(jnp.int32, (tq, cols), 1))
        s_t = scores[rows, :cols]
        lane = jax.lax.broadcasted_iota(jnp.int32, (cols, width), 1)
        for g in range(hb // per_tile):
            lanes = slice(g * width, (g + 1) * width)
            xg = x_ref[:cols, lanes].astype(x_dtype)     # (cols, width)
            acc = None
            for k in range(per_tile):
                hh = g * per_tile + k
                seg = cum_col[rows, hh:hh + 1] - cum[hh:hh + 1, :cols]
                att = jnp.where(live, s_t * jnp.exp(seg), 0.0)
                att = att.astype(att_dtype).astype(prod_dtype)
                xk = xg if per_tile == 1 else jnp.where(
                    (lane >= k * p) & (lane < (k + 1) * p),
                    xg, jnp.zeros_like(xg))
                part = jax.lax.dot_general(
                    att, xk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = part if acc is None else acc + part
            out_ref[rows, lanes] = acc.astype(out_ref.dtype)


def mma_ssd(c: jnp.ndarray, b: jnp.ndarray, x: jnp.ndarray,
            cum: jnp.ndarray, *, c_dtype, b_dtype, att_dtype, prod_dtype,
            x_dtype, out_dtype, interpret: bool = False) -> jnp.ndarray:
    """The intra-chunk SSD outputs.

    c, b (S, NC, L, N); x (S, NC, L, H, P); cum (S, NC, H, L) f32, the
    within-chunk cumulative log-decays.  Returns (S, NC, L, H, P) in
    ``out_dtype``: the f32 sum over s <= l of
    ``(c[l] . b[s]) * exp(cum[h, l] - cum[h, s])`` times ``x[s, h]``.

    The scores multiply ``c`` and ``b`` cast to ``c_dtype``/``b_dtype``
    with f32 accumulation; the decayed scores are rounded to
    ``att_dtype`` then ``prod_dtype``, and multiply ``x`` cast to
    ``x_dtype``, again into f32.  Grid: (sequence, chunk, head block).
    """
    s, nc, l, n = c.shape
    h, p = x.shape[3:]
    hb = ssd_head_block(h)
    if h % hb or l % min(ROW_TILE, l):
        raise ValueError(f"ssd blocks: H {h} by {hb}, L {l} by {ROW_TILE}")
    if (hb * p) % LANES or (p % LANES and LANES % p):
        raise ValueError(f"ssd lanes: {hb} heads of {p} per block")
    kernel = functools.partial(
        _ssd_kernel, hb=hb, p=p, c_dtype=c_dtype, b_dtype=b_dtype,
        att_dtype=att_dtype, prod_dtype=prod_dtype, x_dtype=x_dtype)
    cb_blk = pl.BlockSpec((None, None, l, n), lambda i, j, k: (i, j, 0, 0))
    xy_blk = pl.BlockSpec((None, None, l, hb * p),
                          lambda i, j, k: (i, j, 0, k))
    out = pl.pallas_call(
        kernel,
        grid=(s, nc, h // hb),
        in_specs=[cb_blk, cb_blk,
                  pl.BlockSpec((None, None, hb, l),
                               lambda i, j, k: (i, j, k, 0)),
                  xy_blk],
        out_specs=xy_blk,
        out_shape=jax.ShapeDtypeStruct((s, nc, l, h * p), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(c, b, cum.astype(jnp.float32), x.reshape(s, nc, l, h * p))
    return out.reshape(s, nc, l, h, p)
