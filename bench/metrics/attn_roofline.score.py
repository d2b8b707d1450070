"""Causal attention's least time at the chip's peaks over the device time
of the attention kernel (score cells)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "score", "attention")
