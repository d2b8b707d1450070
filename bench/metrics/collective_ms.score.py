"""Device ms per prompt (`prefill_step` execution) on chip 0 in the
exchanges between chips: operations classed `collective`, an async pair
counted by its start and done ops' own durations (score cells on a
mesh)."""
from benchlib import readers


def read(ctx):
    return readers.class_ms(ctx, "score", "prefill_step", "collective")
