"""Device time of one prefill_step execution, in ms (score cells)."""
from benchlib import readers


def read(ctx):
    return readers.step_ms(ctx, "score", "prefill_step")
