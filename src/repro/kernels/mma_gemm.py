"""Accumulator-resident blocked GEMM — the MMA facility's core, on TPU.

Maps the paper's POWER10 Matrix Math Engine execution model onto Pallas:

  * The output tile (the *virtual accumulator*, paper fig. 4) lives in a
    VMEM scratch buffer for the whole k-loop and is written to HBM exactly
    once — the analogue of accumulators being resident in the MME so that
    "no output is placed on the results buses" during the compute phase
    (paper section III).
  * Each grid step along k streams one (bm, bk) X-panel and one (bk, bn)
    Y-panel through VMEM and issues MXU rank-bk updates — the analogue of
    the xv*ger* instructions streaming 128-bit VSR pairs.
  * The pm* prefixed masked forms (paper section II-C) appear twice: iota
    masks on the fringe blocks (arbitrary M/N/K never require padded
    operands in HBM), and — via ``masks`` — architected row/column/rank
    predicates streamed into VMEM and applied to the panels *inside* the
    kernel, so disabled lanes contribute exact zeros without the operands
    ever being pre-masked in HBM (the ``gemm.masked`` op-class).
  * Batched contractions fold the batch axis into the grid — grid
    ``(b, i, j, k)`` with batch-indexed BlockSpecs — so one ``pallas_call``
    covers every batch element with its own resident accumulator tile,
    instead of a vmapped trace per element.

Supported ger kinds (see repro.core.precision): f64 (interpret/VPU), f32,
bf16, f16, int16 (adapted), int8 x uint8, packed int4.  The beyond-paper
f32-as-3xbf16 MXU emulation is an expansion hook in the lowering registry
(core/lowering.py): three chained kernel passes over one accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision, tiling
from repro.kernels import epilogue as _epilogue


def _unpack_int4(v: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Unpack 2x int4 (two's complement, low nibble first) along ``axis``."""
    axis = axis % v.ndim
    lo = jnp.right_shift(jnp.left_shift(v, 4), 4)
    hi = jnp.right_shift(v, 4)
    stacked = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(v.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def _make_kernel(*, pol, k_steps, k_size, bk_logical, neg_product, neg_acc,
                 has_c, alpha, beta, ep: _epilogue.Epilogue | None = None,
                 batched: bool = False,
                 has_masks=(False, False, False),
                 x_lead: int | None = None, y_lead: int | None = None,
                 checksum: bool = False,
                 m_size: int = 0, n_size: int = 0):
    ep = ep if ep is not None and not ep.is_identity else None
    has_xm, has_ym, has_pm = has_masks
    # Leading singleton block dims to strip per operand read: 1 for a
    # batch-gridded natural panel, 2 (+1 batched) for a prepacked panel
    # whose (g*, gk) tile coordinates are block-indexed away.
    if x_lead is None:
        x_lead = 1 if batched else 0
    if y_lead is None:
        y_lead = 1 if batched else 0

    def kernel(*refs):
        refs = list(refs)
        x_ref, y_ref = refs[:2]
        pos = 2
        xm_ref = refs[pos] if has_xm else None
        pos += has_xm
        ym_ref = refs[pos] if has_ym else None
        pos += has_ym
        pm_ref = refs[pos] if has_pm else None
        pos += has_pm
        c_ref = refs[pos] if has_c else None
        pos += has_c
        bias_ref = refs[pos] if ep and ep.bias else None
        pos += bool(ep and ep.bias)
        res_ref = refs[pos] if ep and ep.residual else None
        pos += bool(ep and ep.residual)
        if checksum:
            out_ref, ckc_ref, ckr_ref, acc_ref = refs[pos:]
        else:
            out_ref, acc_ref = refs[pos:]
            ckc_ref = ckr_ref = None
        ki = pl.program_id(3 if batched else 2)
        if checksum:
            # grid indices read at kernel top level (program_id has no
            # lowering inside the pl.when-traced store body on interpret)
            ti = pl.program_id(1 if batched else 0)
            tj = pl.program_id(2 if batched else 1)

        # ---- prime the accumulator (xxsetaccz / accumulate forms) ----
        @pl.when(ki == 0)
        def _prime():
            if has_c:
                c = c_ref[0] if batched else c_ref[...]
                init = c.astype(pol.acc_dtype)
                if beta != 1.0:
                    init = init * jnp.asarray(beta, pol.acc_dtype)
                acc_ref[...] = -init if neg_acc else init
            else:
                acc_ref[...] = jnp.zeros_like(acc_ref)

        # ---- one rank-bk update:  acc += [-] X_panel @ Y_panel ----
        x = x_ref[(0,) * x_lead] if x_lead else x_ref[...]
        y = y_ref[(0,) * y_lead] if y_lead else y_ref[...]
        if pol.packed_int4:
            # int4 nibble dtype decode on the VMEM-resident panel (two
            # lanes per byte), not a relayout of the streamed tile.
            x = _unpack_int4(x, axis=1)  # repro: allow(pack-once)
            y = _unpack_int4(y, axis=0)  # repro: allow(pack-once)
        # pm* architected predicates (paper eq. 3), applied to the streamed
        # panels in VMEM: disabled rows/columns/ranks contribute exact
        # zeros; the operands in HBM are never pre-masked.  The rank
        # predicate zeroes BOTH panels so a disabled partial product can
        # never pair a zero with a non-finite operand lane.
        if xm_ref is not None:
            x = jnp.where(xm_ref[...], x, jnp.zeros_like(x))
        if pm_ref is not None:
            x = jnp.where(pm_ref[...], x, jnp.zeros_like(x))
            y = jnp.where(pm_ref[...].reshape(-1, 1), y, jnp.zeros_like(y))
        if ym_ref is not None:
            y = jnp.where(ym_ref[...], y, jnp.zeros_like(y))
        # pm*-style fringe mask along k: zero partial products past K.  Both
        # panels are masked — out-of-bounds reads are undefined (NaN in
        # interpret mode) and 0 * NaN would poison the accumulator.
        # (m/n fringe is handled by Pallas dropping out-of-bounds stores.)
        # The predicates take each panel's full shape.  Where X has fewer
        # than 8 rows and a packed sub-32-bit dtype, its panel is selected
        # in f32, which holds every such value exactly: Mosaic cannot lay
        # the predicate over that panel ("Sublane broadcast").
        if k_steps * bk_logical != k_size:
            kx = ki * bk_logical + jax.lax.broadcasted_iota(
                jnp.int32, x.shape, 1)
            ky = ki * bk_logical + jax.lax.broadcasted_iota(
                jnp.int32, y.shape, 0)
            widen = m_size < 8 and jnp.dtype(x.dtype).itemsize < 4
            xs = x.astype(jnp.float32) if widen else x
            x = jnp.where(kx < k_size, xs, jnp.zeros_like(xs)).astype(
                x.dtype)
            y = jnp.where(ky < k_size, y, jnp.zeros_like(y))
        if jnp.issubdtype(pol.acc_dtype, jnp.integer):
            x = x.astype(jnp.int32)
            y = y.astype(jnp.int32)
        prod = jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                   preferred_element_type=pol.acc_dtype)
        acc_ref[...] += -prod if neg_product else prod

        # ---- depriming: single HBM store of the virtual accumulator,
        # with the epilogue fused so the tile never revisits HBM ----
        @pl.when(ki == k_steps - 1)
        def _store():
            out = acc_ref[...]
            if alpha != 1.0:
                out = out * jnp.asarray(alpha, pol.acc_dtype)
            if ep is not None:
                res = None
                if res_ref is not None:
                    res = res_ref[0] if batched else res_ref[...]
                out = _epilogue.apply(
                    out, ep,
                    bias=bias_ref[...] if bias_ref is not None else None,
                    residual=res)
            if checksum:
                # ABFT sidecar (core/abft.py): fold the tile's column and
                # row sums into the deprime — one extra VMEM row + col per
                # resident accumulator tile, summed in acc dtype before
                # the out-dtype cast, never re-reading the stored output.
                # The m/n fringe lanes are masked out (their stores are
                # dropped, but their accumulator lanes saw undefined
                # operand reads and must not poison the sums).
                val = out
                bm_t, bn_t = val.shape
                if (m_size % bm_t) != 0:
                    rm = ti * bm_t + jax.lax.broadcasted_iota(
                        jnp.int32, (bm_t, 1), 0)
                    val = jnp.where(rm < m_size, val, jnp.zeros_like(val))
                if (n_size % bn_t) != 0:
                    cn = tj * bn_t + jax.lax.broadcasted_iota(
                        jnp.int32, (1, bn_t), 1)
                    val = jnp.where(cn < n_size, val, jnp.zeros_like(val))
                ck_col = val.sum(axis=0, keepdims=True)   # (1, bn)
                ck_row = val.sum(axis=1, keepdims=True)   # (bm, 1)
                if batched:
                    ckc_ref[0] = ck_col
                    ckr_ref[0] = ck_row
                else:
                    ckc_ref[...] = ck_col
                    ckr_ref[...] = ck_row
            out = out.astype(out_ref.dtype)
            if batched:
                out_ref[0] = out
            else:
                out_ref[...] = out

    return kernel


def mma_gemm(x: jnp.ndarray, y: jnp.ndarray,
             c: jnp.ndarray | None = None, *,
             kind: precision.Ger = precision.Ger.BF16GER2,
             block: tuple[int, int, int] | None = None,
             neg_product: bool = False, neg_acc: bool = False,
             alpha: float = 1.0, beta: float = 1.0,
             ep: _epilogue.Epilogue | None = None,
             bias: jnp.ndarray | None = None,
             residual: jnp.ndarray | None = None,
             masks: tuple | None = None,
             out_dtype=None, interpret: bool = False,
             x_layout=None, y_layout=None,
             checksum: bool = False) -> jnp.ndarray:
    """C <- alpha * [-](X @ Y)  [+ beta * (+/-)C]  with resident accumulator.

    x: (M, K) or batched (B, M, K); y: (K, N) / (B, K, N); c: optional
    (M, N) / (B, M, N) accumulator input (the pp/np/pn/nn accumulate
    forms).  int4 kind: K axis packed 2-per-byte.

    ``x_layout`` / ``y_layout`` (``packing.GemmLayout``) mark a prepacked
    operand: the raw panel-major tile array (``(gm, gk, bm, bk)`` X-side,
    ``(gn, gk, bk, bn)`` Y-side, optional leading batch) whose BlockSpec
    index maps stream one packed panel per grid step straight into VMEM —
    no per-call relayout.  The layout's block config must equal the
    dispatch block; fringe panels are zero-padded at pack time, which the
    k-fringe mask and dropped out-of-bounds stores make bitwise-inert.

    Batched operands run as ONE ``pallas_call`` with grid ``(B, gm, gn,
    gk)`` — the batch axis is a grid dimension with batch-indexed
    BlockSpecs, not a vmapped re-trace — and every (b, i, j) output tile
    keeps its own resident VMEM accumulator across the k-loop.

    ``ep`` fuses bias (N,), activation, and residual ((B,) M, N) into the
    final k-step store (epilogue.py contract): the accumulator tile leaves
    VMEM exactly once, already post-processed.

    ``masks`` carries the pm* prefixed-form predicates ``(xmask, ymask,
    pmask)`` — shapes (M,), (N,), (K,), bool, each optional — applied to
    the streamed panels inside the kernel (paper section II-C).

    ``checksum=True`` folds ABFT column/row sums into the deprime store
    (core/abft.py): returns ``(out, ck_col, ck_row)`` where ``ck_col`` is
    ``((B,) gm, N)`` per-tile column sums and ``ck_row`` ``((B,) M, gn)``
    per-tile row sums, both in acc dtype and summed *before* the
    out-dtype cast.  The main output is bitwise-identical to the
    ``checksum=False`` call.
    """
    pol = precision.policy(kind)
    if kind == precision.Ger.F32GER_3XBF16:
        raise ValueError(
            "F32GER_3XBF16 is a registered expansion hook — lower it "
            "through facility.contract (core/lowering.py), which chains "
            "three BF16GER2 kernel passes over one resident accumulator")
    if (x_layout is not None or y_layout is not None) and pol.packed_int4:
        raise ValueError("prepacked layouts are byte-addressable tiles; "
                         "packed-int4 kinds keep their nibble packing")
    if x_layout is not None:
        if x.ndim != 4 + bool(x_layout.batched):
            raise ValueError(f"packed x rank {x.ndim} does not match "
                             f"layout {x_layout!r}")
        bx = x.shape[0] if x_layout.batched else None
        m, k_packed = x_layout.rows, x_layout.cols
    elif x.ndim == 3:
        bx, m, k_packed = x.shape
    else:
        bx = None
        m, k_packed = x.shape
    if y_layout is not None:
        if y.ndim != 4 + bool(y_layout.batched):
            raise ValueError(f"packed y rank {y.ndim} does not match "
                             f"layout {y_layout!r}")
        by = y.shape[0] if y_layout.batched else None
        k2, n = y_layout.rows, y_layout.cols
    elif y.ndim == 3:
        by, k2, n = y.shape
    else:
        by = None
        k2, n = y.shape
    if k_packed != k2 or (bx is not None and by is not None and bx != by):
        raise ValueError(f"shape mismatch x{(bx, m, k_packed)} @ "
                         f"y{(by, k2, n)}")
    b = bx if bx is not None else by
    batched = b is not None
    if batched and x_layout is None and x.ndim != 3:
        raise ValueError("batched y operand needs a batched (B, M, K) x")
    if batched and y_layout is None and y.ndim != 3:
        raise ValueError("batched x operand needs a batched (B, K, N) y")
    pack = 2 if pol.packed_int4 else 1
    k = k_packed * pack
    out_dtype = out_dtype or pol.acc_dtype
    ep = ep if ep is not None and not ep.is_identity else None
    if ep is not None:
        ep.validate(pol.acc_dtype, bias=bias, residual=residual)
    elif bias is not None or residual is not None:
        raise ValueError("bias/residual operands need an Epilogue")
    xm, ym, pm = masks if masks is not None else (None, None, None)
    if (xm is not None or pm is not None) and pol.packed_int4:
        raise ValueError(
            "packed-int4 masked forms lower through the ref.pm_ger oracle "
            "(nibble unpacking and rank predicates do not compose in the "
            "streamed kernel)")

    if block is None and y_layout is not None:
        block = y_layout.block
    if block is None and x_layout is not None:
        block = x_layout.block
    cfg = (tiling.choose_blocks(m, n, k, kind) if block is None
           else tiling.BlockConfig(*block))
    tiling.assert_fits_vmem(cfg, kind)
    bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
    for lay in (x_layout, y_layout):
        if lay is not None and tuple(lay.block) != (bm, bn, bk):
            raise ValueError(
                f"stale packed layout: packed at block {lay.block} but "
                f"dispatched at {(bm, bn, bk)} — repack (packing.repack) "
                f"or demote (packing.demote_op); never read stale panels")
    bk_packed = max(bk // pack, 1)
    bk_logical = bk_packed * pack
    grid2d = (-(-m // bm), -(-n // bn), -(-k_packed // bk_packed))
    grid = (b,) + grid2d if batched else grid2d

    # Index maps: the batch coordinate (when present) selects the batch
    # element of x/y/c/residual/out blocks and is ignored by the shared
    # bias/mask vectors.
    def imap(fn, with_b: bool = False):
        if not batched:
            return fn
        if with_b:
            return lambda bb, i, j, kk: (bb,) + fn(i, j, kk)
        return lambda bb, i, j, kk: fn(i, j, kk)

    def bspec(shape2, fn, with_b: bool = False):
        if batched and with_b:
            return pl.BlockSpec((1,) + shape2, imap(fn, True))
        return pl.BlockSpec(shape2, imap(fn))

    def packed_spec(lay, fn):
        # Packed panel stream: the (g*, gk) tile coordinates are block
        # indices, the panel itself is the trailing 2-D block.  A packed
        # operand without a batch axis under a batched grid is shared —
        # its index map simply ignores the batch coordinate.
        shape = (1, 1) + fn("panel")
        if lay.batched:
            return pl.BlockSpec(
                (1,) + shape, lambda bb, i, j, kk: (bb,) + fn((i, j, kk)))
        if batched:
            return pl.BlockSpec(shape, lambda bb, i, j, kk: fn((i, j, kk)))
        return pl.BlockSpec(shape, lambda i, j, kk: fn((i, j, kk)))

    def x_tile(at):
        if at == "panel":
            return (bm, bk_packed)
        i, j, kk = at
        return (i, kk, 0, 0)

    def y_tile(at):
        if at == "panel":
            return (bk_packed, bn)
        i, j, kk = at
        return (j, kk, 0, 0)

    in_specs = [
        (bspec((bm, bk_packed), lambda i, j, kk: (i, kk), with_b=True)
         if x_layout is None else packed_spec(x_layout, x_tile)),
        (bspec((bk_packed, bn), lambda i, j, kk: (kk, j), with_b=True)
         if y_layout is None else packed_spec(y_layout, y_tile)),
    ]
    inputs = [x, y]
    if xm is not None:
        # Row predicate as a (bm, 1) block of an (M, 1) bool operand.
        in_specs.append(bspec((bm, 1), lambda i, j, kk: (i, 0)))
        inputs.append(xm.reshape(m, 1))
    if ym is not None:
        in_specs.append(bspec((1, bn), lambda i, j, kk: (0, j)))
        inputs.append(ym.reshape(1, n))
    if pm is not None:
        in_specs.append(bspec((1, bk_logical), lambda i, j, kk: (0, kk)))
        inputs.append(pm.reshape(1, k))
    if c is not None:
        in_specs.append(bspec((bm, bn), lambda i, j, kk: (i, j),
                              with_b=True))
        inputs.append(c)
    if ep is not None and ep.bias:
        # Row-broadcast vector as a (1, bn) block of a (1, N) operand.
        in_specs.append(bspec((1, bn), lambda i, j, kk: (0, j)))
        inputs.append(bias.reshape(1, n))
    if ep is not None and ep.residual:
        in_specs.append(bspec((bm, bn), lambda i, j, kk: (i, j),
                              with_b=True))
        inputs.append(residual)

    def lead(lay):
        if lay is None:
            return None                      # natural: 1 if batched else 0
        return 2 + (1 if lay.batched else 0)

    kernel = _make_kernel(
        pol=pol, k_steps=grid2d[2], k_size=k, bk_logical=bk_logical,
        neg_product=neg_product, neg_acc=neg_acc, has_c=c is not None,
        alpha=alpha, beta=beta, ep=ep, batched=batched,
        has_masks=(xm is not None, ym is not None, pm is not None),
        x_lead=lead(x_layout), y_lead=lead(y_layout),
        checksum=checksum, m_size=m, n_size=n)

    out_shape = (b, m, n) if batched else (m, n)
    out_specs = bspec((bm, bn), lambda i, j, kk: (i, j), with_b=True)
    out_shapes = jax.ShapeDtypeStruct(out_shape, out_dtype)
    if checksum:
        gm, gn = grid2d[0], grid2d[1]
        ck = lambda s: (b,) + s if batched else s
        out_specs = [
            out_specs,
            bspec((1, bn), lambda i, j, kk: (i, j), with_b=True),
            bspec((bm, 1), lambda i, j, kk: (i, j), with_b=True),
        ]
        out_shapes = [
            out_shapes,
            jax.ShapeDtypeStruct(ck((gm, n)), pol.acc_dtype),
            jax.ShapeDtypeStruct(ck((m, gn)), pol.acc_dtype),
        ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((bm, bn), pol.acc_dtype)],
        interpret=interpret,
    )(*inputs)
