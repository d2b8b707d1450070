"""The arithmetic that the per-layer metric readers share.

Each reader in ``bench/metrics/<metric>.py`` calls one of these with the
kind of job it belongs to, and gets ``None`` back where the run has
nothing for it to read: another kind of job, no trace, or no event of the
kind it measures.  A share of a roofline or of a peak is never made up as
0.

Every share of a peak is per chip: the work of the traced units, which
the ``chips`` of the run share, against ``chips`` times one chip's peak
and the device time averaged over the chips.
"""

from __future__ import annotations

import dataclasses
import statistics


@dataclasses.dataclass
class Context:
    kind: str                 # the job's kind: generate | score | train
    trace: object             # trace.Reduced, or None
    peaks: dict | None        # device.PEAKS entry
    unit_work: tuple          # (contractions, attention) Work of one unit
    units: int                # units of work completed in the trace
    chips: int = 1            # devices the run used


def _ready(ctx, kind) -> bool:
    return (ctx.kind == kind and ctx.trace is not None and ctx.peaks
            is not None and ctx.units > 0)


def step_ms(ctx, kind: str, program: str):
    """Median device time of one execution of the program ``program`` (the
    median, as the trace's end may cut the last execution short)."""
    if not _ready(ctx, kind):
        return None
    times = ctx.trace.module_seconds(program)
    return statistics.median(times) * 1e3 if times else None


def mfu(ctx, kind: str):
    """Needed model FLOPs of the traced units, over the traced window,
    over the chips' bf16 peak, in %."""
    if not _ready(ctx, kind):
        return None
    contr, attn = ctx.unit_work
    flops = (contr.flops + attn.flops) * ctx.units
    return 100.0 * flops / ctx.trace.window_s() / (ctx.peaks["bf16_flops"]
                                                   * ctx.chips)


def roofline(ctx, kind: str, which: str):
    """The least time the traced units' work of class ``which`` (gemm:
    every contraction but attention; attention) needs at the chips'
    peaks, over the device time of the operations that compute it
    (averaged over the chips), in %."""
    if not _ready(ctx, kind):
        return None
    work = ctx.unit_work[0 if which == "gemm" else 1]
    spent = ctx.trace.class_seconds(which)
    if work.flops == 0 or spent <= 0:
        return None
    least = work.least_seconds(ctx.peaks["bf16_flops"] * ctx.chips,
                               ctx.peaks["hbm_bytes_per_s"] * ctx.chips
                               ) * ctx.units
    return 100.0 * least / spent


def class_ms(ctx, kind: str, program: str, cls_name: str):
    """Median device ms per execution of the program ``program``, on chip
    0, of the operations of class ``cls_name``; ``None`` where those
    executions hold none."""
    if not _ready(ctx, kind):
        return None
    per = ctx.trace.class_seconds_per_run(program, cls_name)
    return statistics.median(per) * 1e3 if any(per) else None


def idle_share(ctx, kind: str):
    """Share of the traced window in which no operation ran, in %."""
    if not _ready(ctx, kind):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())

