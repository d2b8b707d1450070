"""Share of the traced window in which the device ran nothing (generate
cells)."""
from benchlib import readers


def read(ctx):
    return readers.idle_share(ctx, "generate")
