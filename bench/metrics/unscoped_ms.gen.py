"""Device ms per decode step (`serve_step` execution) in ops under no
program scope (generate cells)."""
from benchlib import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx, "generate", "serve_step")
