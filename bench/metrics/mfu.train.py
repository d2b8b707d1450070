"""Needed model FLOPs over the traced window, as a share of the bf16 peak
(train cells)."""
from benchlib import readers


def read(ctx):
    return readers.mfu(ctx, "train")
