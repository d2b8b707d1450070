"""Device ms per prompt (`prefill_step` execution) in ops under no
program scope (score cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx, "score", "prefill_step")
