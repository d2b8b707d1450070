"""Share of the traced window in which the device ran nothing (score
cells)."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx, "score")
