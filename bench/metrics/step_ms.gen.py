"""Device time of one serve_step execution, in ms (generate cells)."""
from benchlib import readers


def read(ctx):
    return readers.step_ms(ctx, "generate", "serve_step")
