"""The contractions' least time at the chip's peaks over the device time
of the operations that compute them (generate cells)."""
from benchlib import readers


def read(ctx):
    return readers.roofline(ctx, "generate", "gemm")
