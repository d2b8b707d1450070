"""Device time of one train_step execution, in ms (train cells)."""
from benchlib import readers


def read(ctx):
    return readers.step_ms(ctx, "train", "train_step")
