"""Share of the traced window in which the device ran nothing (train
cells)."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx, "train")
